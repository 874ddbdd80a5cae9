"""The benchmark's tensor generator: a COO tensor made on the device.

One jitted call per (shape, traffic) turns ``--seed`` into coordinates and
values at a configuration's published dims and nnz; nothing is built on
the host. The traffic file's ``coords`` names the coordinate law by its
``distribution`` and gives its parameters; the law is the file
``bench/coords/<distribution>.py``, whose ``coords(key, dims, nnz, spec)``
gives one int32 column per mode. A law is added by adding its file.

The configuration names the value kind: ``gaussian`` (standard normal)
or ``counts`` (integers from ``low`` to ``high`` inclusive), in float32.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from bench.files import BENCH, load_module


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
    law = load_module(BENCH / "coords" / f"{cs['distribution']}.py")

    def make(key):
        k_c, k_v = jax.random.split(key)
        cols = law.coords(k_c, dims, nnz, cs)
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

