"""Clustered coordinates: hypercubes of ``edge`` cells per mode placed
uniformly at random; each holds ``round(edge**N * density)`` distinct
cells drawn without replacement (a seeded bijection of the cube's cell
index), and the cubes are filled in turn until nnz is reached. The rare
cells where two cubes overlap stay as summed entries. The mix's
``coords`` gives ``edge`` and ``density``."""
import math

import jax
import jax.numpy as jnp


def _bijection(x, consts, bits: int):
    """A seeded permutation of [0, 2**bits): rounds of odd multiply,
    xor-shift and add, each a bijection modulo 2**bits."""
    mask = jnp.uint32((1 << bits) - 1)
    shift = max(1, bits // 2)
    for mul, add in consts:
        x = (x * (mul | jnp.uint32(1))) & mask
        x = x ^ (x >> shift)
        x = (x + add) & mask
    return x


def coords(key, dims, nnz: int, spec: dict):
    """One int32 column of ``nnz`` coordinates per mode."""
    edge, density = spec["edge"], spec["density"]
    N = len(dims)
    if any(edge > I for I in dims):
        raise ValueError(f"cube edge {edge} exceeds a mode of {dims}")
    cells = edge ** N
    per_block = max(1, min(cells, round(cells * density)))
    n_blocks = -(-nnz // per_block)
    bits = max(1, math.ceil(math.log2(cells)))
    k_base, k_perm = jax.random.split(key)
    base = [jax.random.randint(k, (n_blocks,), 0, I - edge + 1)
            for k, I in zip(jax.random.split(k_base, N), dims)]
    rounds = 3
    table = jax.random.bits(k_perm, (rounds, 2, n_blocks), jnp.uint32)
    g = jnp.arange(nnz, dtype=jnp.int32)
    block = g // per_block
    x = (g % per_block).astype(jnp.uint32)

    def step(x):
        # Per-block constants gathered where they are used, so no
        # (nnz, rounds, 2) array is ever materialized.
        return _bijection(x, [(table[r, 0][block], table[r, 1][block])
                              for r in range(rounds)], bits)

    # Cycle-walking: a bijection of [0, 2**bits) iterated until it lands
    # below `cells` is a bijection of [0, cells).
    x = step(x)
    x = jax.lax.while_loop(lambda x: jnp.any(x >= cells),
                           lambda x: jnp.where(x >= cells, step(x), x), x)
    cell = x.astype(jnp.int32)
    # One column per mode: an (nnz, N) intermediate would be laid out
    # with N padded to 128 lanes on a TPU.
    return [base[m][block] + (cell // edge ** m) % edge for m in range(N)]
