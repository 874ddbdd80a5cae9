"""Readings that set the limits of `correct`: the program against the plain
reference, and the precision control in the program's place.

    python bench/calibrate.py --workload <name> --seeds 1 2 3

For each seed, in one process so that the programs compile once: the
cell's tensor is made and ingested as in `run`, the check steps of the
mix's ``bench/drivers/`` module run through the public call from the
seeded state, the program's state is freed, and the driver's ``gaps``
judge the answer. The control is the reference computed one precision
step below the configuration's (that module's ``reference(control=True)``),
judged by the same ``gaps``. Each seed prints one JSON line:
``{"seed", "program", "control", "seconds"}``, the numbers `run`
compares for each. A limit goes between the largest program reading and
the smallest control reading.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from bench.run import (BENCH, CHECKOUT, SPEC, configure_jax,  # noqa: E402
                       free, ingest, load_json, load_module, make_coo,
                       require_chip, resolve)


def readings(workload: str, seeds, *, spec: dict | None = None,
             chip: bool = True):
    """Yield one dict of readings per seed."""
    cell = resolve(spec if spec is not None else load_json(SPEC), workload)
    jax = configure_jax(chip)
    if chip:
        require_chip(jax, cell.chips)
    sys.path.insert(0, str(CHECKOUT / "src"))
    import jax.numpy as jnp
    from bench import gen, reference

    config, traffic = cell.config, cell.traffic
    driver = load_module(BENCH / "drivers" / f"{traffic['driver']}.py")
    for seed in seeds:
        t = time.perf_counter()
        coo = make_coo(cell, seed)
        total = float(jnp.sum(coo.values, dtype=jnp.float32))
        ctx = ingest(cell, coo, chip)
        del coo
        state0 = driver.initial(jax.random.fold_in(gen.seed_key(seed), 1),
                                config["dims"], ctx.rank, total)
        warm = driver.solve(ctx, state0, driver.CHECK_STEPS)
        got = jax.block_until_ready(driver.outputs(warm))
        del warm
        free(ctx)
        coo = make_coo(cell, seed)
        low = driver.reference(reference, coo, state0, config, traffic,
                               control=True)
        out = {"seed": seed,
               "program": driver.gaps(reference, coo, state0, got, config,
                                      traffic),
               "control": driver.gaps(reference, coo, state0, low, config,
                                      traffic)}
        del coo, got, low
        out["seconds"] = time.perf_counter() - t
        yield out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for out in readings(args.workload, args.seeds):
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
