"""The benchmark's tensor generator: a COO tensor made on the device.

One jitted call per (shape, traffic) turns ``--seed`` into coordinates and
values at a configuration's published dims and nnz; nothing is built on
the host. The traffic file names the coordinate distribution and its
parameters, the configuration names the value kind:

* ``uniform`` — i.i.d. coordinates in every mode. Repeated coordinates
  stay as separate COO entries, which every consumer sums.
* ``clustered`` — hypercubes of ``edge`` cells per mode placed uniformly
  at random; each holds ``round(edge**N * density)`` distinct cells drawn
  without replacement (a seeded bijection of the cube's cell index), and
  the cubes are filled in turn until the published nnz is reached. The
  rare cells where two cubes overlap stay as summed entries.

Values are ``gaussian`` (standard normal) or ``counts`` (integers from
``low`` to ``high`` inclusive), in float32.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Coo:
    """A COO tensor on the device: what `alto.build_device` reads."""
    dims: tuple[int, ...]
    coords: jax.Array          # (nnz, N) int32
    values: jax.Array          # (nnz,) float32

    @property
    def nnz(self) -> int:
        return int(self.coords.shape[0])


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative integer seed: the low and high 32
    bits both count, so seeds past 2**32 do not alias smaller ones."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


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


def _clustered_coords(key, dims, nnz: int, edge: int, density: float):
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


def _values(key, nnz: int, spec: dict):
    kind = spec["kind"]
    if kind == "gaussian":
        return jax.random.normal(key, (nnz,), jnp.float32)
    if kind == "counts":
        return jax.random.randint(key, (nnz,), spec["low"], spec["high"] + 1
                                  ).astype(jnp.float32)
    raise ValueError(f"unknown value kind {kind!r}")


def _freeze(spec: dict) -> tuple:
    return tuple(sorted((k, _freeze(v) if isinstance(v, dict) else v)
                        for k, v in spec.items()))


@functools.cache
def _generator(dims: tuple, nnz: int, coords_spec: tuple, values_spec: tuple):
    cs, vs = dict(coords_spec), dict(values_spec)

    def make(key):
        k_c, k_v = jax.random.split(key)
        if cs["distribution"] == "uniform":
            cols = [jax.random.randint(k, (nnz,), 0, I) for k, I in
                    zip(jax.random.split(k_c, len(dims)), dims)]
        elif cs["distribution"] == "clustered":
            cols = _clustered_coords(k_c, dims, nnz, cs["edge"],
                                     cs["density"])
        else:
            raise ValueError(f"unknown distribution {cs['distribution']!r}")
        coords = jnp.stack([c.astype(jnp.int32) for c in cols], axis=1)
        return coords, _values(k_v, nnz, vs)

    return jax.jit(make)


def generate(dims, nnz: int, coords_spec: dict, values_spec: dict,
             seed: int) -> Coo:
    """The COO tensor of one run, made on the default device."""
    dims = tuple(int(d) for d in dims)
    fn = _generator(dims, int(nnz), _freeze(coords_spec), _freeze(values_spec))
    coords, values = fn(seed_key(seed))
    return Coo(dims, coords, values)

