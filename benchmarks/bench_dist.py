"""Distributed CP-ALS sweep scaling on 1/2/4/8 devices.

Rows: ``dist_cpals/<tensor>/dev<N>`` — one full sharded CP-ALS sweep
(sharded MTTKRP all modes + psum'd Grams) per call.

On a TPU host every device count runs in this process over the first N
chips (a chip belongs to one process, so a child could not reach it).
Off the chip each device count runs in a fresh CPU-only subprocess,
because ``--xla_force_host_platform_device_count`` must be set before
the first jax import; the fake devices timeshare the host's cores, so
that measures collective + partitioning overhead, not speedup — the
scaling *shape* (flat ≈ free sharding) is the signal.
"""
from __future__ import annotations

import os
import subprocess
import sys

DEVICE_COUNTS = (1, 2, 4, 8)


def run(quick: bool = False) -> None:
    import jax
    if jax.default_backend() == "tpu":
        devices = jax.devices()
        for n in DEVICE_COUNTS:
            if n <= len(devices):
                _worker(n, quick, devices=devices[:n])
        return
    for n in DEVICE_COUNTS:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
        env.setdefault("PYTHONPATH", "src:.")
        cmd = [sys.executable, "-m", "benchmarks.bench_dist",
               "--worker", str(n)] + (["--quick"] if quick else [])
        r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                           timeout=1800)
        sys.stdout.write(r.stdout)
        sys.stdout.flush()
        if r.returncode != 0:
            raise RuntimeError(f"dev{n} worker failed:\n{r.stderr[-2000:]}")


def _worker(n_dev: int, quick: bool, devices=None) -> None:
    import functools

    import jax
    import jax.numpy as jnp

    from benchmarks.common import emit, time_call
    from repro.core import alto, cpals, plan as plan_mod
    from repro.dist import cpd
    from repro.sparse import synthetic

    mesh = jax.make_mesh((n_dev,), ("data",), devices=devices)
    rank = 8
    dims, nnz = ((1024, 256, 128), 30_000) if quick else \
        ((4096, 1024, 256), 120_000)
    x = synthetic.uniform_tensor(dims, nnz, seed=0)
    at = alto.build(x, n_partitions=8)
    plan = plan_mod.make_plan(at.meta, rank, mesh=mesh)
    views = plan_mod.build_views(at, plan)
    factors = cpals.init_factors(at.dims, rank, seed=0)
    lam = jnp.ones((rank,), jnp.float32)

    sweep = jax.jit(functools.partial(
        cpals._sweep, plan,
        gram_fn=functools.partial(cpd.sharded_gram, mesh)))
    us = time_call(lambda: sweep(at, views, factors, lam))
    emit(f"dist_cpals/uniform/dev{n_dev}", us,
         f"nnz={at.nnz};shards={plan.n_shards}")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", type=int, default=0)
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    if args.worker:
        _worker(args.worker, args.quick)
    else:
        run(quick=args.quick)
