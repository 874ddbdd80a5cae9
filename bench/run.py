"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in the checkout's ``BENCHMARK.json``.
It names a configuration (a file of sizes under ``bench/configs/``) and a
traffic mix (``bench/traffic/<traffic>.json``). The mix names its driver
(``bench/drivers/<driver>.py``) and its coordinate law
(``bench/coords/<distribution>.py``, read by `gen`). Each metric is read
by its own file ``bench/metrics/<metric>.py``. A cell's own file,
``bench/cells/<workload>.json``, gives the limit of each number that
decides ``correct`` and its nominal step time. Nothing here names a
cell, configuration, law, mix or metric; each is added by adding files:

* a configuration: ``bench/configs/<name>.json`` with its published
  ``dims``, ``nnz``, ``rank``, ``values`` and ``dtype``, and a
  ``cpu_cut`` (``{"dims": [...], "nnz": n}``, one dim per mode, none
  above the published one nor below a cube edge its mixes ask for) that
  the CPU tests run in its place; plus its ``configs`` entry;
* a coordinate law: ``bench/coords/<distribution>.py`` with
  ``coords(key, dims, nnz, spec)``;
* a traffic mix: ``bench/traffic/<traffic>.json``;
* a cell: ``bench/cells/<workload>.json`` and its ``workloads`` entry;
* a metric: ``bench/metrics/<metric>.py`` with ``read(run)``, and its
  entry.

The only entries already there that change are the ``workloads`` lists
of the metrics the new cell reports.

One run:

1. fails unless JAX finds a TPU with as many chips as the cell asks for;
2. makes the cell's COO tensor on the device from ``--seed`` (`gen`);
3. ingests it through `alto.build_device`, plans it with
   `plan.make_plan` at the defaults (no tuning, the default backend) and
   builds the plan's oriented views; every mode has to run a compiled
   Pallas kernel, none the reference backend or interpret mode;
4. runs the check steps of the mix's ``bench/drivers/`` module from
   seeded state through the public call the window makes;
5. runs the window from their state: one public call of
   ``round(--seconds / nominal_step_s)`` steps, a number fixed per cell so
   every run does the same work, traced by the profiler with
   ``--trace 1``;
6. reads the device's peak memory, frees the program's state, and
   judges the check steps' answer by the plain reference (`reference`):
   the driver's ``gaps`` gives the numbers, the cell's file their limits;
7. prints the numbers compared, each beside its limit, as the last lines
   of standard error, and the result as the last line of standard output.

Set-up (``setup_s``) runs from process start to the window. JAX's
persistent compilation cache lives at ``.cache/jax`` in the checkout, so
only a checkout's first run of a cell compiles.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from bench.files import (BENCH, CHECKOUT, BenchError,  # noqa: E402
                         load_json, load_module)

SPEC = CHECKOUT / "BENCHMARK.json"
COMPILE_CACHE = CHECKOUT / ".cache" / "jax"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Cell:
    """One workload entry with everything it names, found by name."""
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    nominal_step_s: float
    end_to_end: list[dict]
    per_layer: list[dict]


def applies(metric: dict, cell: str, e2e_names) -> bool:
    """A metric with ``workloads`` is reported in those cells; one
    without, in every cell that reports the metric it moves (or every
    cell, for an end-to-end metric)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def resolve(spec: dict, workload: str) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(CHECKOUT / configs[w["config"]]["file"])
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    own = load_json(BENCH / "cells" / f"{workload}.json")
    e2e = [m for m in spec["end_to_end"] if applies(m, workload, ())]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"] if applies(m, workload, names)]
    return Cell(workload, int(w["chips"]), config, traffic, own["limits"],
                float(own["nominal_step_s"]), e2e, layer)


@dataclasses.dataclass
class Ctx:
    """What a ``bench/drivers/`` module's calls need: the tensor and plan."""
    cell: Cell
    rank: int
    at: object = None
    plan: object = None
    views: dict | None = None

    @property
    def traffic(self) -> dict:
        return self.cell.traffic


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read about one run."""
    cell: Cell
    driver: str
    device_kind: str
    steps: int
    setup_s: float
    window_s: float
    ingest_s: float
    trace: object = None          # trace.Trace of the window, or None
    summary: object = None        # trace.Summary of the window, or None

    @property
    def step_s(self) -> float:
        return self.window_s / self.steps

    @property
    def config(self) -> dict:
        return self.cell.config

    def metric(self, name: str):
        """Another metric's reader, for the names it keeps."""
        return load_module(BENCH / "metrics" / f"{name}.py")

    def kernel_s(self, patterns) -> float | None:
        """Device seconds per step of the operations matching
        ``patterns`` in the traced window; None without a trace or when
        nothing matches."""
        if self.trace is None:
            return None
        from bench import trace as trace_mod
        s = trace_mod.kernel_s(self.trace, patterns)
        return s / self.steps if s > 0 else None


def configure_jax(chip: bool):
    """Import JAX with the cache in the checkout and, on the chip path,
    with the TPU as the only platform unless the environment says
    otherwise."""
    if chip:
        os.environ.setdefault("JAX_PLATFORMS", "tpu")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(COMPILE_CACHE)
    import jax
    if chip:
        jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def require_chip(jax, chips: int):
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise BenchError(f"JAX finds no TPU: {e}") from None
    if devices[0].platform != "tpu":
        raise BenchError(f"JAX runs on {devices[0].platform}, not a TPU")
    if len(devices) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX finds "
                         f"{len(devices)}")
    return devices


def make_coo(cell: Cell, seed: int):
    """The cell's COO tensor for ``seed``, made on the device."""
    from bench import gen
    return gen.generate(cell.config["dims"], cell.config["nnz"],
                        cell.traffic["coords"], cell.config["values"], seed)


def ingest(cell: Cell, coo, chip: bool) -> Ctx:
    """The public ingest path at the defaults users get: `build_device`,
    `make_plan` without tuning on the default backend, `build_views`."""
    from repro.core import alto, plan as plan_mod
    ctx = Ctx(cell, int(cell.config["rank"]))
    ctx.at = alto.build_device(coo)
    ctx.plan = plan_mod.make_plan(ctx.at.meta, ctx.rank, tune="off")
    if chip:
        require_chip_plan(ctx.plan)
    ctx.views = plan_mod.build_views(ctx.at, ctx.plan)
    return ctx


def free(ctx: Ctx) -> None:
    """Drop the program's state, its cached views included."""
    from repro.core import views as views_mod
    views_mod.invalidate(ctx.at)
    ctx.at = ctx.plan = ctx.views = None


def require_chip_plan(plan):
    """Every mode on compiled Pallas kernels: no reference, no interpret."""
    from repro.kernels import ops
    if plan.backend != "pallas":
        raise BenchError(f"plan backend is {plan.backend!r}, not 'pallas'")
    if ops._auto_interpret(plan.interpret) is not False:
        raise BenchError("Pallas kernels would run in interpret mode")


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        spec: dict | None = None, chip: bool = True) -> dict:
    """One run of a cell; returns the result object. ``chip=False`` skips
    the look for a TPU and for a compiled Pallas plan, so the tests can
    drive the rest of a run on the CPU."""
    cell = resolve(spec if spec is not None else load_json(SPEC), workload)
    jax = configure_jax(chip)
    devices = require_chip(jax, cell.chips) if chip else jax.devices()
    sys.path.insert(0, str(CHECKOUT / "src"))
    import jax.numpy as jnp
    from bench import gen, reference, trace as trace_mod

    config, traffic = cell.config, cell.traffic
    driver = load_module(BENCH / "drivers" / f"{traffic['driver']}.py")

    marks = {"start and imports": time.perf_counter()}
    coo = make_coo(cell, seed)
    total = float(jnp.sum(coo.values, dtype=jnp.float32))
    marks["generate"] = time.perf_counter()
    ctx = ingest(cell, coo, chip)
    jax.block_until_ready((ctx.at, ctx.views))
    marks["ingest"] = time.perf_counter()
    ingest_s = marks["ingest"] - marks["generate"]
    del coo
    for mp in ctx.plan.modes:
        log(f"mode {mp.mode}: {mp.traversal.value}, r_block {mp.r_block}, "
            f"block_m {mp.block_m}, fiber reuse "
            f"{ctx.at.meta.fiber_reuse[mp.mode]:.4f}")
    log(f"oriented views: {sorted(ctx.views)}")

    state0 = driver.initial(jax.random.fold_in(gen.seed_key(seed), 1),
                            config["dims"], ctx.rank, total)
    warm = driver.solve(ctx, state0, driver.CHECK_STEPS)
    checked = jax.block_until_ready(driver.outputs(warm))
    if driver.steps_run(warm) != driver.CHECK_STEPS:
        raise BenchError(f"check call ran {driver.steps_run(warm)} steps, "
                         f"not {driver.CHECK_STEPS}")
    state = driver.next_state(warm)
    del warm
    marks["check steps"] = time.perf_counter()
    steps = max(1, round(seconds / cell.nominal_step_s))

    trace_dir = None
    if trace:
        trace_dir = tempfile.TemporaryDirectory(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir.name)
    setup_s = time.perf_counter() - T0
    last = T0
    for phase, t in marks.items():
        log(f"set-up: {phase} {t - last:.3f} s")
        last = t
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
        result = driver.solve(ctx, state, steps)
        jax.block_until_ready(driver.outputs(result))
    window_s = time.perf_counter() - t
    if trace:
        jax.profiler.stop_trace()
    done = driver.steps_run(result)
    log(f"window {window_s:.4f} s, {done} of {steps} steps")

    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices[:cell.chips])
    del result, state
    free(ctx)

    coo = make_coo(cell, seed)
    numbers = driver.gaps(reference, coo, state0, checked, config, traffic)
    del coo
    checks = {}
    for name, limit in cell.limits.items():
        if name not in numbers:
            raise BenchError(f"the {traffic['driver']} driver gives no "
                             f"number {name!r}")
        checks[name] = {"value": numbers[name],
                        "limit": float(limit["limit"])}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values()) and done == steps

    r = Run(cell, traffic["driver"], devices[0].device_kind, done, setup_s,
            window_s, ingest_s)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": steps, "failed": steps - done}
    if trace:
        r.trace = trace_mod.load(trace_dir.name)
        trace_dir.cleanup()
        r.summary = trace_mod.summarize(r.trace)
        device["busy_s"] = r.summary.busy_s
        device["window_s"] = r.summary.window_s
        out["metrics"] = read_metrics(cell.per_layer, r, required=False)
        out["device"] = device
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in r.summary.device_ops],
            "idle_gaps": [[n, s] for n, s in r.summary.idle_gaps]}
    else:
        out["metrics"] = read_metrics(cell.end_to_end, r, required=True)
        out["device"] = device
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return out


def read_metrics(metrics: list[dict], r: Run, required: bool) -> dict:
    """Each metric read by its own file; a per-layer reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in metrics:
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(r)
        if value is None:
            if required:
                raise BenchError(f"metric {m['name']} read nothing")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except BenchError as e:
        log(f"bench: {e}")
        return 2
    except Exception:            # noqa: BLE001 — any failure is no result
        traceback.print_exc()
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
