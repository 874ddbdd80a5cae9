"""Measured-candidate plan autotuner with a persistent on-disk plan store.

The paper's §4.3 dynamic adaptation picks algorithms from *static* tensor
characteristics; ReLATE (PAPERS.md) shows the next order of performance
comes from replacing those hand heuristics with measured/learned selection
over the same candidate space. This module is that measurement layer for
the plan stack:

* **candidate space** — `core.plan.candidate_mode_plans` enumerates the
  feasible (traversal × r_block × block_m) tilings per mode, pruned by
  the corrected per-kernel VMEM footprints (including the fused Φ
  kernel's full-rank resident B — the model the static heuristics got
  wrong, see `plan.phi_oriented_vmem_bytes`). The static analytic choice
  is always candidate 0, so the measured winner can never be worse than
  the static model *under the measurement*.
* **timing protocol** — every candidate is materialized as a full
  `ExecutionPlan` and timed through `plan.execute_mttkrp` /
  `plan.execute_phi` wrapped in one jitted executable per candidate,
  registered in the compiled-executable cache in `kernels.ops` (key: the
  hashable candidate plan itself). `ops.median_time` takes the median of
  k blocking calls after warmup runs that absorb compilation. On CPU the
  Pallas kernels run under the interpreter, so timings are a *proxy*
  ranking (documented in docs/known-issues.md); on TPU the same protocol
  times real Mosaic executables.
* **plan store** — winners persist in a versioned JSON file
  (``$REPRO_PLAN_CACHE`` or the checkout's ``.cache/plans.json``,
  `repro.caches`), keyed on a
  stable hash of (meta fingerprint, rank, backend, device platform,
  shard count, dtype/vmem budget, jax version, store version). A second
  process calling ``make_plan(..., tune="auto"|"force")`` gets the
  identical measured plan back with **zero timing runs**
  (`ops.timing_runs` proves it). Corrupted or stale-version store files
  are ignored, never fatal — the tuner just re-measures.

Mesh-bearing tuning times the *actual sharded executables* (the
candidate plan routes `execute_mttkrp` through `dist.cpd`), with the
candidate space sized against the per-shard budget exactly as
`make_plan(mesh=...)` sizes static plans.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import pathlib
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro import caches
from repro.core import faults
from repro.core import heuristics
from repro.core import mttkrp as core_mttkrp
from repro.core import plan as plan_mod
from repro.core.alto import AltoMeta, AltoTensor, delinearize

# v2: the ORIENTED_CARRY traversal joined the candidate space. Bumping the
# store version makes every pre-carry store load as empty (stale winners,
# measured without the carry candidates, must not mask the new traversal).
# v3: streaming plans joined the store (records carry a ``streaming``
# chunk block, keys a ``dev=`` component) and records carry measurement
# ``samples`` that train the search cost model (`core.search`). Pre-search
# v2 stores load as empty — never clobbered until the first new write.
PLAN_STORE_VERSION = 3
PLAN_CACHE_ENV = "REPRO_PLAN_CACHE"
DEFAULT_STORE = str(caches.CACHE_DIR / "plans.json")

DEFAULT_WARMUP = 1
DEFAULT_ITERS = 3
DEFAULT_MAX_CANDIDATES = 24


# ---------------------------------------------------------------------------
# Store keys: stable fingerprints of everything a measurement depends on
# ---------------------------------------------------------------------------

def meta_fingerprint(meta: AltoMeta) -> str:
    """Canonical string of every AltoMeta field a plan decision reads.

    The encoding's bit assignment is a pure function of ``dims`` but is
    fingerprinted anyway (``bit_mode``) so an encoder change invalidates
    stored plans instead of silently mismatching them.
    """
    enc = meta.enc
    return ";".join([
        "dims=" + ",".join(map(str, enc.dims)),
        "bitmode=" + ",".join(map(str, enc.bit_mode)),
        f"nnz={meta.nnz}",
        f"L={meta.n_partitions}",
        "temp=" + ",".join(map(str, meta.temp_rows)),
        "reuse=" + ",".join(repr(float(r)) for r in meta.fiber_reuse),
    ])


def plan_key(meta: AltoMeta, rank: int, backend: str, *,
             n_shards: int = 1, dtype_bytes: int = 4,
             vmem_limit: int = plan_mod.VMEM_BYTES,
             fast_mem_bytes: int = heuristics.DEFAULT_FAST_MEM_BYTES,
             objective: str = "mttkrp",
             platform: str | None = None,
             device_bytes: int | None = None) -> str:
    """Stable store key: sha256 over everything a measurement depends on.

    ``platform`` (``jax.default_backend()``) is part of the key so
    CPU-interpret proxy timings never masquerade as TPU measurements,
    and ``jax.__version__`` so a toolchain upgrade re-measures.
    ``objective`` keeps mttkrp- and Φ-tuned plans apart (their winners
    differ), and ``fast_mem_bytes`` pins the Π-policy decision baked
    into the stored plan. ``device_bytes`` is the out-of-core budget a
    *streaming* plan was sized against (None for in-core plans — the
    same tensor tuned in core and tuned against a chunking budget are
    different measurements and must never share a record).
    """
    platform = platform or jax.default_backend()
    blob = "|".join([
        f"store_v{PLAN_STORE_VERSION}",
        meta_fingerprint(meta),
        f"rank={rank}",
        f"backend={backend}",
        f"platform={platform}",
        f"shards={n_shards}",
        f"dtype_bytes={dtype_bytes}",
        f"vmem={vmem_limit}",
        f"fast_mem={fast_mem_bytes}",
        f"objective={objective}",
        f"dev={device_bytes}",
        f"jax={jax.__version__}",
    ])
    return hashlib.sha256(blob.encode()).hexdigest()[:32]


def class_plan_key(sc, backend: str, **kwargs) -> str:
    """Store key for a shape class (`core.shapeclass.ShapeClass`).

    Delegates to `plan_key` over the class's canonical meta — a pure
    function of the class, with no data-dependent fields — so every
    tenant the class admits resolves to the SAME store entry: the class
    is measured once, then every subsequent tenant's dispatch is a
    zero-timing-run store hit (the serving layer's warm start).
    """
    from repro.core import shapeclass
    return plan_key(shapeclass.canonical_meta(sc), sc.rank, backend,
                    **kwargs)


# ---------------------------------------------------------------------------
# The on-disk store (versioned JSON; corrupt/stale files are ignored)
# ---------------------------------------------------------------------------

def store_path(override=None) -> pathlib.Path:
    """Resolve the plan-store file: explicit arg > $REPRO_PLAN_CACHE >
    the checkout's .cache/plans.json."""
    if override is not None:
        return pathlib.Path(override).expanduser()
    env = os.environ.get(PLAN_CACHE_ENV)
    if env:
        return pathlib.Path(env).expanduser()
    return pathlib.Path(DEFAULT_STORE).expanduser()


def load_store(path=None) -> dict:
    """The store's ``plans`` mapping. Missing, unreadable, corrupted, or
    stale-version files all load as empty — a bad cache can cost a
    re-measurement, never a crash."""
    try:
        faults.inject("autotune.store")    # corrupt/unreadable store file
        raw = json.loads(store_path(path).read_text())
    except (OSError, ValueError):
        return {}
    if not isinstance(raw, dict) or raw.get("version") != PLAN_STORE_VERSION:
        return {}
    plans = raw.get("plans")
    return plans if isinstance(plans, dict) else {}


def save_store(plans: dict, path=None) -> pathlib.Path:
    """Atomically write the store (tmp file + rename, survives a crash
    mid-write as either the old or the new file, never a torn one)."""
    target = store_path(path)
    target.parent.mkdir(parents=True, exist_ok=True)
    payload = {"version": PLAN_STORE_VERSION, "jax": jax.__version__,
               "plans": plans}
    fd, tmp = tempfile.mkstemp(dir=str(target.parent),
                               prefix=target.name + ".", suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        os.replace(tmp, target)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    return target


def evict(key: str, path=None) -> bool:
    """Drop one stored plan (the evict-and-retune recovery rung).

    A stored plan that fails at *dispatch* — tiling from another
    device generation, a record that deserializes but whose kernel no
    longer builds — would otherwise fail every future process that
    trusts the store. The serving runtime evicts the key and falls back
    to an untuned static plan for the request in hand; the next tuned
    solve re-measures and re-populates. Returns True iff present.
    """
    plans = load_store(path)
    if key not in plans:
        return False
    del plans[key]
    save_store(plans, path)
    return True


def serialize_plan(plan: plan_mod.ExecutionPlan) -> dict:
    """JSON record of a plan. ``meta`` itself is NOT stored — the store
    key already pins it, and deserialization re-attaches the caller's
    meta/mesh — only a human-readable summary (dims, nnz) rides along."""
    return {
        "rank": plan.rank,
        "backend": plan.backend,
        "pi_policy": plan.pi_policy.value,
        "n_shards": plan.n_shards,
        "modes": [{
            "mode": m.mode,
            "traversal": m.traversal.value,
            "r_block": m.r_block,
            "block_m": m.block_m,
            "temp_rows": m.temp_rows,
            "vmem_bytes": m.vmem_bytes,
            "phi_vmem_bytes": m.phi_vmem_bytes,
        } for m in plan.modes],
        "streaming": None if plan.streaming is None else {
            "chunk_m": plan.streaming.chunk_m,
            "n_chunks": plan.streaming.n_chunks,
            "device_bytes": plan.streaming.device_bytes,
            "stream_bytes": plan.streaming.stream_bytes,
        },
        "dims": list(plan.meta.dims),
        "nnz": plan.meta.nnz,
    }


def deserialize_plan(record: dict, meta: AltoMeta, *,
                     mesh=None, interpret: bool | None = None
                     ) -> plan_mod.ExecutionPlan:
    """Rebuild an ExecutionPlan from a store record + the caller's meta.

    Raises KeyError/ValueError on malformed records — `lookup` treats
    those as a store miss.
    """
    modes = tuple(plan_mod.ModePlan(
        mode=int(m["mode"]),
        traversal=heuristics.Traversal(m["traversal"]),
        r_block=int(m["r_block"]),
        block_m=int(m["block_m"]),
        temp_rows=int(m["temp_rows"]),
        vmem_bytes=int(m["vmem_bytes"]),
        phi_vmem_bytes=int(m["phi_vmem_bytes"]),
    ) for m in record["modes"])
    if len(modes) != meta.enc.ndim:
        raise ValueError("record mode count does not match meta")
    rank = int(record["rank"])
    for m in modes:
        if m.r_block <= 0 or rank % m.r_block:
            raise ValueError(f"stored r_block {m.r_block} does not divide "
                             f"rank {rank}")
    streaming = None
    s = record.get("streaming")
    if s is not None:
        if mesh is not None:
            raise ValueError("streaming records do not compose with mesh")
        chunk_m = int(s["chunk_m"])
        align = max(m.block_m for m in modes)
        if chunk_m <= 0 or chunk_m % align:
            raise ValueError(f"stored chunk_m {chunk_m} is not a multiple "
                             f"of the plan's max block_m {align}")
        # n_chunks is a pure function of (meta, chunk_m): recompute
        # rather than trust the record, so a stale count can't desync
        # the executed grid from the stream.
        streaming = plan_mod.StreamPlan(
            chunk_m=chunk_m,
            n_chunks=plan_mod.chunk_count(meta, chunk_m),
            device_bytes=int(s["device_bytes"]),
            stream_bytes=int(s["stream_bytes"]))
    return plan_mod.ExecutionPlan(
        meta=meta, rank=rank, backend=str(record["backend"]),
        interpret=interpret,
        pi_policy=heuristics.PiPolicy(record["pi_policy"]),
        modes=modes, mesh=mesh, streaming=streaming)


def lookup(meta: AltoMeta, rank: int, *, backend: str,
           dtype_bytes: int = 4, vmem_limit: int = plan_mod.VMEM_BYTES,
           fast_mem_bytes: int = heuristics.DEFAULT_FAST_MEM_BYTES,
           objective: str = "mttkrp",
           mesh=None, interpret: bool | None = None,
           device_bytes: int | None = None,
           path=None) -> plan_mod.ExecutionPlan | None:
    """Stored measured plan for this configuration, or None. Zero timing
    runs either way. ``device_bytes`` selects the streaming record for
    that out-of-core budget (None = the in-core record)."""
    n_shards = 1 if mesh is None else int(mesh.shape[mesh.axis_names[0]])
    key = plan_key(meta, rank, backend, n_shards=n_shards,
                   dtype_bytes=dtype_bytes, vmem_limit=vmem_limit,
                   fast_mem_bytes=fast_mem_bytes, objective=objective,
                   device_bytes=device_bytes)
    record = load_store(path).get(key)
    if record is None:
        return None
    try:
        return deserialize_plan(record, meta, mesh=mesh,
                                interpret=interpret)
    except (KeyError, ValueError, TypeError):
        return None       # malformed entry == miss; tuner will overwrite


# ---------------------------------------------------------------------------
# Timing
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CandidateTiming:
    """One measured candidate for one mode."""
    mode: int
    traversal: str
    r_block: int
    block_m: int
    median_s: float
    is_static: bool      # True iff this is the analytic-model choice


@dataclasses.dataclass(frozen=True)
class ModeReport:
    mode: int
    candidates: tuple[CandidateTiming, ...]

    @property
    def best(self) -> CandidateTiming:
        return min(self.candidates, key=lambda c: c.median_s)

    @property
    def static(self) -> CandidateTiming:
        return next(c for c in self.candidates if c.is_static)


@dataclasses.dataclass(frozen=True)
class TuneReport:
    """Per-mode candidate timings + where the winner was persisted."""
    modes: tuple[ModeReport, ...]
    key: str
    store: str          # path the plan was persisted to ("" if not)
    objective: str


def _candidate_plan(meta, rank, backend, interpret, pi_policy, mode,
                    candidate, base_modes, mesh):
    """A full ExecutionPlan with ``candidate`` swapped in at ``mode`` —
    hashable, so it doubles as the timing executable's cache key."""
    modes = list(base_modes)
    modes[mode] = candidate
    return plan_mod.ExecutionPlan(meta=meta, rank=rank, backend=backend,
                                  interpret=interpret, pi_policy=pi_policy,
                                  modes=tuple(modes), mesh=mesh)


def _time_mttkrp(cand_plan, at, views, factors, mode, warmup, iters):
    from repro.kernels import ops

    def build():
        def run(at, views, factors):
            return plan_mod.execute_mttkrp(cand_plan, at, views, factors,
                                           mode)
        return jax.jit(run)

    fn = ops._cached_executable(("tune_mttkrp", cand_plan, mode), build)
    return ops.median_time(fn, at, views, factors,
                           warmup=warmup, iters=iters)


def _time_phi(cand_plan, at, view, B, factors, pi, mode, warmup, iters,
              eps=1e-10):
    from repro.kernels import ops
    pre_pi = pi is not None

    def build():
        def run(at, view, B, factors, pi):
            return plan_mod.execute_phi(
                cand_plan, at, view, B, mode,
                factors=None if pre_pi else factors,
                pi=pi, eps=eps)
        return jax.jit(run)

    fn = ops._cached_executable(("tune_phi", cand_plan, mode, pre_pi, eps),
                                build)
    return ops.median_time(fn, at, view, B, factors, pi,
                           warmup=warmup, iters=iters)


def tune_plan(at: AltoTensor, rank: int, *, backend: str | None = None,
              interpret: bool | None = None, dtype_bytes: int = 4,
              vmem_limit: int = plan_mod.VMEM_BYTES,
              fast_mem_bytes: int = heuristics.DEFAULT_FAST_MEM_BYTES,
              mesh=None, objective: str = "mttkrp",
              warmup: int = DEFAULT_WARMUP, iters: int = DEFAULT_ITERS,
              max_candidates: int | None = None,
              seed: int = 0, persist: bool = True,
              store_path=None) -> tuple[plan_mod.ExecutionPlan, TuneReport]:
    """Measure the feasible tiling space and return the winning plan.

    ``objective`` picks the timed kernel: ``"mttkrp"`` (CP-ALS's
    bottleneck, the default) or ``"phi"`` (CP-APR's fused model update;
    r_block is dead there, so candidates collapse to traversal ×
    block_m). Factors are synthetic (seeded), so timings depend only on
    the static meta the store key fingerprints.

    Returns ``(plan, report)``; the report carries every candidate's
    median so callers (bench_autotune, tests) can verify the winner is
    never slower than the static-model choice under the measurement —
    guaranteed by construction since the static choice is candidate 0
    and the winner is the argmin.
    """
    if objective not in ("mttkrp", "phi"):
        raise ValueError(f"unknown objective {objective!r}")
    if max_candidates is None:
        max_candidates = DEFAULT_MAX_CANDIDATES   # late-bound: patchable
    meta = at.meta
    backend = backend or plan_mod.default_backend()
    n_shards = 1 if mesh is None else int(mesh.shape[mesh.axis_names[0]])
    budget = max(1, vmem_limit // n_shards)
    pi_policy = heuristics.choose_pi_policy(
        meta, rank, value_bytes=dtype_bytes, fast_mem_bytes=fast_mem_bytes)
    pre_pi = pi_policy is heuristics.PiPolicy.PRE

    rng = np.random.default_rng(seed)
    factors = [jnp.asarray(rng.standard_normal((I, rank))
                           .astype(np.float32)) for I in meta.dims]
    # Static baseline plan: candidate plans swap ONE mode at a time so
    # the timed executable differs from the baseline only in that mode.
    base_modes = tuple(
        plan_mod.static_mode_plan(meta, n, rank, dtype_bytes=dtype_bytes,
                                  vmem_limit=budget,
                                  force_oriented=mesh is not None,
                                  pre_pi=pre_pi)
        for n in range(meta.enc.ndim))

    winners, reports = [], []
    for n in range(meta.enc.ndim):
        cands = plan_mod.candidate_mode_plans(
            meta, n, rank, dtype_bytes=dtype_bytes, vmem_limit=budget,
            force_oriented=mesh is not None, pre_pi=pre_pi,
            max_candidates=max_candidates)
        if backend == "reference":
            # The pure-jnp traversals have no tiling knobs, and both
            # oriented variants run the same sorted segment_sum: one
            # candidate per traversal *family*, everything else times
            # identically.
            dedupe_key = lambda c: (                             # noqa: E731
                "oriented" if heuristics.is_oriented(c.traversal)
                else c.traversal,)
        elif objective == "phi":
            # The fused Φ kernel has no rank tiling: candidates that
            # differ only in r_block time identically, keep the first
            # (largest fitting r_block, or the static choice).
            dedupe_key = lambda c: (c.traversal, c.block_m)      # noqa: E731
        else:
            dedupe_key = None
        if dedupe_key is not None:
            seen, deduped = set(), []
            for c in cands:
                k = dedupe_key(c)
                if k not in seen:
                    seen.add(k)
                    deduped.append(c)
            cands = tuple(deduped)
        needs_view = (mesh is not None) or any(
            heuristics.is_oriented(c.traversal) for c in cands)
        # Shared view cache: the tuner's timing views are the very views
        # the driver will consume afterwards — built once per (tensor,
        # mode), on device by default (core.views routing).
        from repro.core import views as views_mod
        view = views_mod.get_view(at, n) if needs_view else None
        views = {n: view} if view is not None else {}
        if objective == "phi":
            B = jnp.abs(factors[n]) + jnp.float32(0.1)
            # ALTO-PRE Π rows must be in the element order the timed
            # traversal consumes (same rule as cpapr._mode_update).
            pi_alto = pi_view = None
            if pre_pi:
                pi_alto = core_mttkrp.krp_rows(
                    delinearize(meta.enc, at.words), factors, n)
                if view is not None:
                    pi_view = core_mttkrp.krp_rows(
                        delinearize(meta.enc, view.words), factors, n)
        timings = []
        for i, mp in enumerate(cands):
            cand_plan = _candidate_plan(meta, rank, backend, interpret,
                                        pi_policy, n, mp, base_modes, mesh)
            if objective == "phi":
                oriented = (view is not None
                            and heuristics.is_oriented(mp.traversal))
                pi = (pi_view if oriented else pi_alto) if pre_pi else None
                t = _time_phi(cand_plan, at, view, B, factors, pi, n,
                              warmup, iters)
            else:
                t = _time_mttkrp(cand_plan, at, views, factors, n,
                                 warmup, iters)
            timings.append(CandidateTiming(
                mode=n, traversal=mp.traversal.value, r_block=mp.r_block,
                block_m=mp.block_m, median_s=float(t), is_static=(i == 0)))
        best_i = min(range(len(cands)), key=lambda i: timings[i].median_s)
        winners.append(cands[best_i])
        reports.append(ModeReport(mode=n, candidates=tuple(timings)))

    plan = plan_mod.ExecutionPlan(meta=meta, rank=rank, backend=backend,
                                  interpret=interpret, pi_policy=pi_policy,
                                  modes=tuple(winners), mesh=mesh)
    key = plan_key(meta, rank, backend, n_shards=n_shards,
                   dtype_bytes=dtype_bytes, vmem_limit=vmem_limit,
                   fast_mem_bytes=fast_mem_bytes, objective=objective)
    stored = ""
    if persist:
        from repro.core import search as search_mod
        record = serialize_plan(plan)
        record["tuned"] = {
            "mode": "exhaustive",
            "platform": jax.default_backend(),
            "objective": objective,
            "warmup": warmup,
            "iters": iters,
            "modes": [{
                "mode": r.mode,
                "best_us": r.best.median_s * 1e6,
                "static_us": r.static.median_s * 1e6,
                "n_candidates": len(r.candidates),
            } for r in reports],
        }
        # Every exhaustive measurement doubles as a training sample for
        # the search cost model (`core.search`): exhaustive runs warm
        # the model that later budgeted searches rank candidates with.
        samples = []
        for r in reports:
            for c in r.candidates:
                samples.append({
                    "f": [round(f, 6) for f in search_mod.gene_features(
                        meta, rank, r.mode,
                        heuristics.Traversal(c.traversal), c.r_block,
                        c.block_m, objective=objective,
                        dtype_bytes=dtype_bytes)],
                    "s": c.median_s,
                })
        record["samples"] = samples[:search_mod.MAX_RECORD_SAMPLES]
        plans = load_store(store_path)
        plans[key] = record
        stored = str(save_store(plans, store_path))
    return plan, TuneReport(modes=tuple(reports), key=key, store=stored,
                            objective=objective)


# ---------------------------------------------------------------------------
# make_plan's entry point (tune="auto"|"force")
# ---------------------------------------------------------------------------

def tuned_plan(meta: AltoMeta, rank: int, *, backend: str,
               interpret: bool | None, dtype_bytes: int, vmem_limit: int,
               fast_mem_bytes: int, mesh, at: AltoTensor | None,
               require: bool, objective: str = "mttkrp",
               search: bool = False, device_bytes: int | None = None,
               search_budget_runs: int | None = None,
               search_budget_s: float | None = None,
               search_seed: int = 0,
               store_path=None) -> plan_mod.ExecutionPlan | None:
    """Store lookup, else measured tuning; ``None`` tells `make_plan` to
    fall back to the static analytic plan (tune="auto" with no data).

    ``search=True`` (``tune="search"``) routes the measurement through
    the budgeted GA + cost-model engine (`core.search`) instead of the
    exhaustive tuner. ``device_bytes`` non-None marks a *streaming*
    plan: those always tune through the search engine (the exhaustive
    tuner's jitted timing closures cannot take a host-resident stream,
    and chunk_m is part of the search genome, not the exhaustive
    space) and are stored under a device-budget-keyed record. Mesh
    plans keep the exhaustive path — the sharded timing protocol lives
    there (streaming+mesh is rejected upstream by `make_plan`).
    """
    hit = lookup(meta, rank, backend=backend, dtype_bytes=dtype_bytes,
                 vmem_limit=vmem_limit, fast_mem_bytes=fast_mem_bytes,
                 objective=objective, mesh=mesh, interpret=interpret,
                 device_bytes=device_bytes, path=store_path)
    if hit is not None:
        return hit
    if at is not None:
        if at.meta != meta:
            raise ValueError("tune: at.meta does not match the meta the "
                             "plan is being built for")
        if (search or device_bytes is not None) and mesh is None:
            from repro.core import search as search_mod
            plan, _ = search_mod.search_plan(
                at, rank, backend=backend, interpret=interpret,
                dtype_bytes=dtype_bytes, vmem_limit=vmem_limit,
                fast_mem_bytes=fast_mem_bytes, objective=objective,
                device_bytes=device_bytes,
                budget_runs=search_budget_runs,
                budget_s=search_budget_s, seed=search_seed,
                store_path=store_path)
            return plan
        plan, _ = tune_plan(at, rank, backend=backend, interpret=interpret,
                            dtype_bytes=dtype_bytes, vmem_limit=vmem_limit,
                            fast_mem_bytes=fast_mem_bytes, mesh=mesh,
                            objective=objective, store_path=store_path)
        return plan
    if require:
        raise ValueError(
            "tune='force': no stored measured plan for this tensor and no "
            "tensor data to measure — pass the built tensor (at=..., or "
            "use plan_for / the drivers' tune= kwarg) or pre-populate the "
            f"plan store ({store_path or store_path_hint()})")
    return None


def store_path_hint() -> str:
    return os.environ.get(PLAN_CACHE_ENV) or DEFAULT_STORE
