"""Budgeted plan search: a seeded GA over plan candidates + a learned
cost model, replacing exhaustive candidate timing.

The exhaustive tuner (`core.autotune.tune_plan`) times EVERY feasible
(traversal × r_block × block_m) candidate per mode. That does not
survive the plan space the later tiers created — × chunk_m for
streaming plans, × shape class for serving — so this module spends a
*measurement budget* (run count and/or wall-clock seconds) instead:

* **Genome** — per-mode genes are (traversal, r_block, block_m)
  triples drawn from the feasible pool `plan.candidate_mode_plans`
  already prunes by the per-kernel VMEM models; streaming plans add a
  genome-level ``chunk_m`` gene (block-aligned, byte-model-clamped by
  `plan.choose_chunk_m`). Mutation and crossover operate on the raw
  triple, then a **repair step** snaps the child to the nearest pool
  member — re-applying `plan.carry_fits_vmem` and the VMEM/byte-model
  feasibility by construction, so no infeasible candidate is ever timed.
* **Fitness** — measured wall-clock through the same protocol as the
  exhaustive tuner: one cached executable per candidate plan,
  `ops.timing_stats` (median, IQR) of blocking calls after warmup.
  Per-(mode, gene, chunk) measurements are memoized, so re-visiting a
  gene is free; the fitness of a full plan is separable across modes
  (each mode's kernel runs independently), which is what lets a
  per-mode GA share one global budget.
* **Cost model** — ridge regression on log-seconds over analytic
  features of (meta fingerprint, gene): nnz, density, mode extents,
  fiber-reuse stats, the modelled HBM traffic of the gene's traversal,
  its VMEM footprint, tile/chunk geometry. Fit from the measurement
  samples persisted in the plan store (every exhaustive OR search run
  contributes), so the model **transfers across tensors**: a new tensor
  with a warm store gets model-ranked candidates before any
  measurement, and ``budget_runs=0`` returns a zero-measurement
  model-picked plan. The model only decides *what to measure*
  (pre-ranking the population so just the top-k per generation are
  timed); the plan store stays the ground truth.
* **Seeding** — the population starts from the static analytic gene
  (always measured first, so the search winner is never worse than the
  static choice under the measurement whenever the budget allows ≥ 1
  run per mode) plus the winners of the nearest store records by
  meta-feature distance (same ndim; log-dims/log-nnz/log-rank).

Every measurement is appended as a JSONL record under
``$REPRO_TUNE_LOG`` (generation, candidate, predicted vs measured,
budget spent) — greppable observability for tuning regressions.

On CPU the kernels run under the Pallas interpreter, so both the
measurements and the model trained on them are *proxy* rankings
(docs/known-issues.md); on TPU the same protocol measures real Mosaic
executables.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import pathlib
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import heuristics
from repro.core import mttkrp as core_mttkrp
from repro.core import plan as plan_mod
from repro.core.alto import AltoMeta, AltoTensor, delinearize

TUNE_LOG_ENV = "REPRO_TUNE_LOG"

DEFAULT_GENERATIONS = 4
DEFAULT_POPULATION = 8
DEFAULT_TOP_K = 2            # measured candidates per mode per generation
DEFAULT_MUTATE_P = 0.35
MODEL_MIN_SAMPLES = 8        # below this the model stays unfit (prior order)
RIDGE_LAMBDA = 1e-2
MAX_RECORD_SAMPLES = 48      # samples persisted per store record (capped)
MAX_CHUNK_CANDIDATES = 4     # halving ladder below the byte-model maximum
N_FEATURES = 18


# ---------------------------------------------------------------------------
# JSONL experiment log ($REPRO_TUNE_LOG)
# ---------------------------------------------------------------------------

class TuneLogger:
    """Append-only JSONL experiment log; disabled when no path is set.

    One line per event (``search_start`` / ``measure`` / ``search_end``),
    flat JSON with sorted keys so the log greps and diffs cleanly.
    """

    def __init__(self, path=None):
        p = path if path is not None else os.environ.get(TUNE_LOG_ENV)
        self.path = pathlib.Path(p).expanduser() if p else None
        self._lock = threading.Lock()

    @property
    def enabled(self) -> bool:
        return self.path is not None

    def write(self, event: str, **fields) -> None:
        if self.path is None:
            return
        fields["event"] = event
        fields["ts"] = time.time()
        line = json.dumps(fields, sort_keys=True)
        with self._lock:
            self.path.parent.mkdir(parents=True, exist_ok=True)
            with open(self.path, "a") as f:
                f.write(line + "\n")


# ---------------------------------------------------------------------------
# Measurement budget
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SearchBudget:
    """Measurement budget: run count and/or wall-clock seconds.

    ``None`` means unlimited on that axis; both None means the caller
    gets the default run budget (25% of the feasible space, at least
    two runs per mode). ``max_runs=0`` is the zero-measurement warm
    start: nothing is timed, the cost model picks the plan.
    """
    max_runs: int | None = None
    max_seconds: float | None = None
    runs_used: int = 0
    seconds_used: float = 0.0

    def allows(self) -> bool:
        if self.max_runs is not None and self.runs_used >= self.max_runs:
            return False
        if (self.max_seconds is not None
                and self.seconds_used >= self.max_seconds):
            return False
        return True

    def charge(self, seconds: float) -> None:
        self.runs_used += 1
        self.seconds_used += seconds


# ---------------------------------------------------------------------------
# Analytic candidate features + the ridge cost model
# ---------------------------------------------------------------------------

def gene_features(meta: AltoMeta, rank: int, mode: int,
                  traversal: heuristics.Traversal, r_block: int,
                  block_m: int, *, chunk_m: int = 0,
                  objective: str = "mttkrp",
                  dtype_bytes: int = 4) -> list[float]:
    """Feature vector of one (tensor, mode, gene) pair — all analytic,
    computable with zero measurements, so predictions transfer to
    never-measured tensors through the shared feature space."""
    log = math.log
    M = heuristics.stream_len(meta)
    dims = meta.dims
    log_vol = sum(log(d) for d in dims)            # log ∏ dims, no overflow
    density = log(max(meta.nnz, 1)) - log_vol
    if traversal is heuristics.Traversal.RECURSIVE:
        traffic = plan_mod.recursive_vmem_bytes(meta, mode, r_block,
                                                dtype_bytes)
    elif traversal is heuristics.Traversal.ORIENTED_CARRY:
        traffic = heuristics.carry_traffic_bytes(meta, mode, rank,
                                                 dtype_bytes)
    else:
        traffic = heuristics.oriented_merge_traffic_bytes(meta, mode, rank,
                                                          dtype_bytes)
    vmem = plan_mod._mode_plan(meta, mode, rank, traversal, r_block,
                               block_m, dtype_bytes, False).vmem_bytes
    n_chunks = plan_mod.chunk_count(meta, chunk_m) if chunk_m else 1
    return [
        1.0,                                           # bias
        log(max(meta.nnz, 1)),
        log(max(M, 1)),
        log(dims[mode]),
        log(sum(dims)),
        density,
        float(meta.fiber_reuse[mode]),
        float(np.mean(meta.fiber_reuse)),
        log(rank),
        log(r_block),
        log(block_m),
        log(max(1, -(-M // block_m))),                 # oriented grid steps
        1.0 if traversal is heuristics.Traversal.RECURSIVE else 0.0,
        1.0 if traversal is heuristics.Traversal.ORIENTED_CARRY else 0.0,
        log(max(traffic + M * plan_mod.stream_elem_bytes(meta,
                                                         dtype_bytes), 1)),
        log(max(vmem, 1)),
        log(max(n_chunks, 1)),
        1.0 if objective == "phi" else 0.0,
    ]


class CostModel:
    """Ridge regression on log-seconds over `gene_features` vectors.

    Closed-form fit on standardized features (numpy only). Unfit until
    ``MODEL_MIN_SAMPLES`` samples exist — predictions return None then
    and the search falls back to the pool's analytic prior order.
    """

    def __init__(self):
        self._X: list[list[float]] = []
        self._y: list[float] = []
        self._w = None
        self._mu = None
        self._sd = None

    @property
    def n_samples(self) -> int:
        return len(self._y)

    @property
    def ready(self) -> bool:
        return self._w is not None

    def add_sample(self, features, seconds: float) -> None:
        if len(features) != N_FEATURES or not (seconds > 0):
            return                      # malformed store sample: skip
        self._X.append([float(f) for f in features])
        self._y.append(math.log(seconds))
        self._w = None                  # stale until the next fit

    def fit(self) -> bool:
        if len(self._y) < MODEL_MIN_SAMPLES:
            return False
        X = np.asarray(self._X, dtype=np.float64)
        y = np.asarray(self._y, dtype=np.float64)
        mu = X.mean(axis=0)
        sd = X.std(axis=0)
        sd[sd < 1e-12] = 1.0
        mu[0], sd[0] = 0.0, 1.0         # keep the bias column as-is
        Z = (X - mu) / sd
        A = Z.T @ Z + RIDGE_LAMBDA * len(y) * np.eye(N_FEATURES)
        try:
            self._w = np.linalg.solve(A, Z.T @ y)
        except np.linalg.LinAlgError:
            return False
        self._mu, self._sd = mu, sd
        return True

    def predict(self, features) -> float | None:
        """Predicted seconds, or None while unfit."""
        if self._w is None:
            return None
        z = (np.asarray(features, dtype=np.float64) - self._mu) / self._sd
        return float(math.exp(float(z @ self._w)))


def model_from_store(plans: dict, platform: str | None = None) -> CostModel:
    """Cost model trained on every sample persisted in the plan store.

    Samples are gated on the platform they were measured on — a CPU
    proxy sample must never train a model that ranks TPU candidates.
    """
    platform = platform or jax.default_backend()
    model = CostModel()
    for record in plans.values():
        if not isinstance(record, dict):
            continue
        meta_p = (record.get("tuned") or {}).get("platform")
        if meta_p is not None and meta_p != platform:
            continue
        for sample in record.get("samples") or []:
            try:
                model.add_sample(sample["f"], float(sample["s"]))
            except (KeyError, TypeError, ValueError):
                continue
    model.fit()
    return model


def store_neighbors(plans: dict, meta: AltoMeta, rank: int, *,
                    objective: str = "mttkrp",
                    limit: int = 3) -> list[dict]:
    """Nearest store records by meta-feature distance (same ndim only).

    Distance: Σ|Δlog dims| + |Δlog nnz| + |Δlog rank| — the fingerprint
    axes a plan decision actually reads. Their winning mode genes seed
    the GA population, so a tensor similar to an already-tuned one
    starts the search at (a neighborhood of) that tensor's winner.
    """
    scored = []
    for record in plans.values():
        if not isinstance(record, dict):
            continue
        dims = record.get("dims")
        if (not isinstance(dims, list) or len(dims) != len(meta.dims)
                or not record.get("modes")):
            continue
        obj = (record.get("tuned") or {}).get("objective")
        if obj is not None and obj != objective:
            continue
        try:
            d = sum(abs(math.log(int(a)) - math.log(b))
                    for a, b in zip(dims, meta.dims))
            d += abs(math.log(max(int(record.get("nnz", 1)), 1))
                     - math.log(max(meta.nnz, 1)))
            d += abs(math.log(max(int(record.get("rank", rank)), 1))
                     - math.log(rank))
        except (TypeError, ValueError):
            continue
        scored.append((d, record))
    scored.sort(key=lambda t: t[0])
    return [r for _, r in scored[:limit]]


# ---------------------------------------------------------------------------
# The candidate pools (feasible-by-construction gene spaces)
# ---------------------------------------------------------------------------

def _dedupe_pool(pool, backend: str, objective: str,
                 streaming: bool):
    """Collapse genes that time identically — same rules the exhaustive
    tuner applies, so budgets are spent on distinguishable candidates.

    Reference-backend chunked executors have no tiling knobs at all
    (one gene); in-core reference collapses to one per traversal
    family; the fused Φ kernel has no rank tiling (r_block is dead)."""
    if backend == "reference":
        if streaming:
            key = lambda g: ()                               # noqa: E731
        else:
            key = lambda g: (                                # noqa: E731
                "oriented" if heuristics.is_oriented(g.traversal)
                else g.traversal,)
    elif objective == "phi":
        key = lambda g: (g.traversal, g.block_m)             # noqa: E731
    else:
        return pool
    seen, out = set(), []
    for g in pool:
        k = key(g)
        if k not in seen:
            seen.add(k)
            out.append(g)
    return tuple(out)


def mode_pool(meta: AltoMeta, mode: int, rank: int, *,
              backend: str, objective: str = "mttkrp",
              dtype_bytes: int = 4,
              vmem_limit: int = plan_mod.VMEM_BYTES,
              pre_pi: bool = False,
              streaming: bool = False) -> tuple[plan_mod.ModePlan, ...]:
    """The feasible gene pool for one mode, static analytic gene FIRST.

    This IS the repair domain: every pool member already passed the
    VMEM models and the `carry_fits_vmem` gate inside
    `plan.candidate_mode_plans`, so snapping a mutated gene into the
    pool re-applies feasibility for free. Streaming pools pin the
    scratch-carry traversal (the chunked executors ARE the carry scan)
    with the static force-carry gene kept even when the carry gate
    fails (the budget turns advisory out-of-core, exactly as in
    `plan.static_mode_plan`)."""
    if not streaming:
        pool = plan_mod.candidate_mode_plans(
            meta, mode, rank, dtype_bytes=dtype_bytes,
            vmem_limit=vmem_limit, pre_pi=pre_pi)
        return _dedupe_pool(pool, backend, objective, streaming=False)
    static = plan_mod.static_mode_plan(
        meta, mode, rank, dtype_bytes=dtype_bytes, vmem_limit=vmem_limit,
        force_carry=True, pre_pi=pre_pi)
    pool = [static]
    seen = {(static.r_block, static.block_m)}
    for rb in plan_mod.rank_tiles(rank):
        for bm in plan_mod.block_sizes():
            if ((rb, bm) not in seen
                    and plan_mod.oriented_carry_vmem_bytes(
                        meta, mode, bm, rb, dtype_bytes) <= vmem_limit):
                seen.add((rb, bm))
                pool.append(plan_mod._mode_plan(
                    meta, mode, rank, heuristics.Traversal.ORIENTED_CARRY,
                    rb, bm, dtype_bytes, pre_pi))
    return _dedupe_pool(tuple(pool), backend, objective, streaming=True)


def _gene_distance(g: plan_mod.ModePlan, traversal, r_block: int,
                   block_m: int) -> float:
    d = 0.0 if g.traversal is traversal else 4.0
    d += abs(math.log2(g.block_m) - math.log2(max(block_m, 1)))
    d += abs(math.log2(g.r_block) - math.log2(max(r_block, 1)))
    return d


def repair(pool, traversal, r_block: int, block_m: int) -> int:
    """Snap an arbitrary (traversal, r_block, block_m) triple to the
    nearest feasible pool gene (index). Deterministic: ties break to
    the earlier pool entry (the pool orders static-first, larger tiles
    first)."""
    return min(range(len(pool)),
               key=lambda i: (_gene_distance(pool[i], traversal, r_block,
                                             block_m), i))


def chunk_ladder(meta: AltoMeta, rank: int, device_bytes: int,
                 align: int, dtype_bytes: int = 4) -> list[int]:
    """Feasible chunk_m candidates: the byte-model maximum (the analytic
    choice, always first) then a halving ladder down to one block.
    Every entry is ``align``-aligned (``align`` = max block_m, a power
    of two, so chunk boundaries sit on block boundaries for every mode
    — the bitwise-parity precondition) and fits the double-buffer byte
    model by construction (smaller chunks need fewer bytes)."""
    top = plan_mod.choose_chunk_m(meta, rank, device_bytes, align,
                                  dtype_bytes)
    ladder, cm = [], top
    while cm >= align and len(ladder) < MAX_CHUNK_CANDIDATES:
        ladder.append(cm)
        nxt = ((cm // 2) // align) * align
        if nxt == cm:
            break
        cm = nxt
    return ladder


# ---------------------------------------------------------------------------
# Timing (same protocol + executable cache as the exhaustive tuner)
# ---------------------------------------------------------------------------

def _time_mttkrp(cand_plan, at, views, factors, mode, warmup, iters):
    from repro.kernels import ops
    if cand_plan.streaming is not None:
        # The chunked executors are host loops over a host-resident
        # stream — not a jit operand, so the candidate is timed as-is
        # (each per-chunk call inside is itself jitted/cached).
        def fn():
            return plan_mod.execute_mttkrp(cand_plan, at, views, factors,
                                           mode)
        return ops.timing_stats(fn, warmup=warmup, iters=iters)

    def build():
        def run(at, views, factors):
            return plan_mod.execute_mttkrp(cand_plan, at, views, factors,
                                           mode)
        return jax.jit(run)

    fn = ops._cached_executable(("tune_mttkrp", cand_plan, mode), build)
    return ops.timing_stats(fn, at, views, factors,
                            warmup=warmup, iters=iters)


def _time_phi(cand_plan, at, view, B, factors, pi, mode, warmup, iters,
              eps=1e-10):
    from repro.kernels import ops
    if cand_plan.streaming is not None:
        def fn():
            return plan_mod.execute_phi(cand_plan, at, view, B, mode,
                                        factors=factors, eps=eps)
        return ops.timing_stats(fn, warmup=warmup, iters=iters)
    pre_pi = pi is not None

    def build():
        def run(at, view, B, factors, pi):
            return plan_mod.execute_phi(
                cand_plan, at, view, B, mode,
                factors=None if pre_pi else factors, pi=pi, eps=eps)
        return jax.jit(run)

    fn = ops._cached_executable(("tune_phi", cand_plan, mode, pre_pi, eps),
                                build)
    return ops.timing_stats(fn, at, view, B, factors, pi,
                            warmup=warmup, iters=iters)


# ---------------------------------------------------------------------------
# Search report
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ModeWinner:
    mode: int
    traversal: str
    r_block: int
    block_m: int
    measured_s: float | None      # None on a zero-measurement warm start
    predicted_s: float | None
    is_static: bool               # the analytic gene won (or was the only)


@dataclasses.dataclass(frozen=True)
class SearchReport:
    key: str
    store: str                    # path persisted to ("" if not)
    objective: str
    backend: str
    budget_runs: int | None
    budget_s: float | None
    runs_used: int
    seconds_used: float
    generations: int
    pool_sizes: tuple[int, ...]
    model_samples: int            # training samples available at start
    model_used: bool              # the model pre-ranked candidates
    warm_start: bool              # zero measurements, model picked the plan
    neighbors: int                # store records that seeded the population
    winners: tuple[ModeWinner, ...]
    chunk_m: int | None           # streaming plans only
    chunk_candidates: int

    @property
    def best_time_s(self) -> float | None:
        """Sum of the winners' measured medians (None if any unmeasured)."""
        ts = [w.measured_s for w in self.winners]
        return None if any(t is None for t in ts) else float(sum(ts))


# ---------------------------------------------------------------------------
# The GA search
# ---------------------------------------------------------------------------

class _ModeSearch:
    """GA state for one mode: population of pool indices + memoized
    measurements. The pool is the feasible space; indices never leave
    it, so every genome is feasible by construction."""

    def __init__(self, mode, pool, rng, population, seeds):
        self.mode = mode
        self.pool = pool
        self.rng = rng
        self.size = max(2, min(population, max(2, len(pool))))
        pop = [0]                       # the static analytic gene, always
        for s in seeds:
            if s not in pop:
                pop.append(s)
        while len(pop) < self.size:
            c = int(rng.integers(len(pool)))
            if c not in pop or len(pop) >= len(pool):
                pop.append(c)
        self.population = pop[:self.size]
        self.measured: dict[int, float] = {}     # pool idx -> median_s
        self.predicted: dict[int, float | None] = {}

    def fitness(self, i: int) -> float:
        if i in self.measured:
            return self.measured[i]
        p = self.predicted.get(i)
        if p is not None:
            return p
        # Unfit model: the pool's analytic prior order (static first,
        # larger tiles first) as a pseudo-time far above any real one.
        return 1e6 * (1.0 + i)

    def to_measure(self, top_k: int, first_generation: bool) -> list[int]:
        ranked = sorted(set(self.population),
                        key=lambda i: (self.fitness(i), i))
        picks = [i for i in ranked if i not in self.measured][:top_k]
        if first_generation and 0 not in self.measured and 0 not in picks:
            picks = [0] + picks[:max(0, top_k - 1)]
        return picks

    def _tournament(self) -> int:
        a, b = (int(self.rng.integers(len(self.population)))
                for _ in range(2))
        ia, ib = self.population[a], self.population[b]
        return ia if self.fitness(ia) <= self.fitness(ib) else ib

    def evolve(self, mutate_p: float) -> None:
        if len(self.pool) <= 2:
            return                      # nothing to evolve toward
        elite = sorted(set(self.population),
                       key=lambda i: (self.fitness(i), i))[:2]
        nxt = list(elite)
        while len(nxt) < self.size:
            p1, p2 = self.pool[self._tournament()], \
                self.pool[self._tournament()]
            # Uniform crossover over the three gene fields.
            trav = p1.traversal if self.rng.random() < 0.5 else p2.traversal
            rb = p1.r_block if self.rng.random() < 0.5 else p2.r_block
            bm = p1.block_m if self.rng.random() < 0.5 else p2.block_m
            # Mutation: nudge one field.
            if self.rng.random() < mutate_p:
                field = int(self.rng.integers(3))
                if field == 0:
                    trav = self.pool[int(self.rng.integers(
                        len(self.pool)))].traversal
                elif field == 1:
                    rb = max(1, rb * 2 if self.rng.random() < 0.5
                             else rb // 2)
                else:
                    bm = min(plan_mod.MAX_BLOCK_M,
                             max(plan_mod.MIN_BLOCK_M,
                                 bm * 2 if self.rng.random() < 0.5
                                 else bm // 2))
            # Repair: snap to the nearest feasible pool gene.
            nxt.append(repair(self.pool, trav, rb, bm))
        self.population = nxt[:self.size]

    def winner(self) -> tuple[int, float | None, float | None]:
        """(pool idx, measured_s, predicted_s) — best measured gene if
        anything was measured, else the model's pick, else static."""
        if self.measured:
            i = min(self.measured, key=lambda i: (self.measured[i], i))
            return i, self.measured[i], self.predicted.get(i)
        preds = {i: p for i, p in self.predicted.items() if p is not None}
        if preds:
            i = min(preds, key=lambda i: (preds[i], i))
            return i, None, preds[i]
        return 0, None, None


def search_plan(at: AltoTensor, rank: int, *, backend: str | None = None,
                interpret: bool | None = None, dtype_bytes: int = 4,
                vmem_limit: int = plan_mod.VMEM_BYTES,
                fast_mem_bytes: int = heuristics.DEFAULT_FAST_MEM_BYTES,
                objective: str = "mttkrp",
                device_bytes: int | None = None,
                budget_runs: int | None = None,
                budget_s: float | None = None,
                seed: int = 0,
                generations: int = DEFAULT_GENERATIONS,
                population: int = DEFAULT_POPULATION,
                top_k: int = DEFAULT_TOP_K,
                mutate_p: float = DEFAULT_MUTATE_P,
                warmup: int = 1, iters: int = 3,
                persist: bool = True, store_path=None, log_path=None,
                ) -> tuple[plan_mod.ExecutionPlan, SearchReport]:
    """Budgeted GA + cost-model plan search. Returns (plan, report).

    ``device_bytes`` non-None (and overflowing) makes the genome
    streaming: the per-mode pools pin the scratch-carry traversal and
    ``chunk_m`` joins the search space (a block-aligned halving ladder
    under the byte-model maximum, evaluated on the bottleneck mode
    after the tiling genes converge).

    Determinism: same (seed, store, tensor, budget) runs measure the
    same candidates in the same order and return the identical winning
    plan — the only nondeterminism is which candidate *times* fastest
    on the host, and the memoized measurement protocol is shared with
    the exhaustive tuner. A subsequent `make_plan(..., tune="search")`
    with the winner persisted is a store hit: zero timing runs.
    """
    from repro.core import autotune
    from repro.core import views as views_mod

    if objective not in ("mttkrp", "phi"):
        raise ValueError(f"unknown objective {objective!r}")
    meta = at.meta
    backend = backend or plan_mod.default_backend()
    streaming = (device_bytes is not None
                 and plan_mod.needs_streaming(meta, rank, device_bytes,
                                              dtype_bytes))
    if not streaming:
        device_bytes = None
    pi_policy = heuristics.choose_pi_policy(
        meta, rank, value_bytes=dtype_bytes, fast_mem_bytes=fast_mem_bytes)
    pre_pi = pi_policy is heuristics.PiPolicy.PRE
    ndim = meta.enc.ndim

    pools = [mode_pool(meta, n, rank, backend=backend, objective=objective,
                       dtype_bytes=dtype_bytes, vmem_limit=vmem_limit,
                       pre_pi=pre_pi, streaming=streaming)
             for n in range(ndim)]
    space = sum(len(p) for p in pools)
    if budget_runs is None and budget_s is None:
        budget_runs = max(2 * ndim, -(-space // 4))
    budget = SearchBudget(max_runs=budget_runs, max_seconds=budget_s)

    plans = autotune.load_store(store_path)
    model = model_from_store(plans)
    model_samples = model.n_samples
    neighbors = store_neighbors(plans, meta, rank, objective=objective)

    rng = np.random.default_rng(seed)
    searches = []
    for n in range(ndim):
        seeds = []
        for record in neighbors:
            try:
                g = record["modes"][n]
                seeds.append(repair(
                    pools[n], heuristics.Traversal(g["traversal"]),
                    int(g["r_block"]), int(g["block_m"])))
            except (KeyError, IndexError, ValueError, TypeError):
                continue
        searches.append(_ModeSearch(n, pools[n], rng, population, seeds))

    # --- measurement setup (exhaustive tuner's protocol) ---------------
    rng_f = np.random.default_rng(seed)
    factors = [jnp.asarray(rng_f.standard_normal((I, rank))
                           .astype(np.float32)) for I in meta.dims]
    analytic_chunk = None
    if streaming:
        align0 = max(max(g.block_m for g in p) for p in pools)
        analytic_chunk = plan_mod.choose_chunk_m(meta, rank, device_bytes,
                                                 align0, dtype_bytes)

    def candidate_plan(mode: int, gene: plan_mod.ModePlan,
                       chunk_m: int | None) -> plan_mod.ExecutionPlan:
        modes = [searches[m].pool[0] for m in range(ndim)]
        modes[mode] = gene
        stream = None
        if streaming:
            cm = chunk_m if chunk_m is not None else analytic_chunk
            # Only the measured mode's kernel runs under this candidate:
            # align the chunk to ITS block (powers of two, so rounding up
            # suffices) — never to the unmeasured base modes, which would
            # silently distort a chunk-ladder measurement.
            cm = -(-cm // gene.block_m) * gene.block_m
            stream = plan_mod.StreamPlan(
                chunk_m=cm, n_chunks=plan_mod.chunk_count(meta, cm),
                device_bytes=device_bytes,
                stream_bytes=plan_mod.incore_working_set_bytes(
                    meta, rank, dtype_bytes))
        return plan_mod.ExecutionPlan(
            meta=meta, rank=rank, backend=backend, interpret=interpret,
            pi_policy=pi_policy, modes=tuple(modes), streaming=stream)

    mode_operands: dict[int, tuple] = {}

    def operands(mode: int):
        """(views, view, B, pi_alto, pi_view) for one mode, lazy-built."""
        if mode in mode_operands:
            return mode_operands[mode]
        if streaming:
            view = views_mod.get_stream(at, mode)
        else:
            oriented_any = any(heuristics.is_oriented(g.traversal)
                               for g in pools[mode])
            view = views_mod.get_view(at, mode) if oriented_any else None
        views = {mode: view} if view is not None else {}
        B = pi_alto = pi_view = None
        if objective == "phi":
            B = jnp.abs(factors[mode]) + jnp.float32(0.1)
            if pre_pi and not streaming:
                pi_alto = core_mttkrp.krp_rows(
                    delinearize(meta.enc, at.words), factors, mode)
                if view is not None:
                    pi_view = core_mttkrp.krp_rows(
                        delinearize(meta.enc, view.words), factors, mode)
        out = (views, view, B, pi_alto, pi_view)
        mode_operands[mode] = out
        return out

    logger = TuneLogger(log_path)
    key = autotune.plan_key(meta, rank, backend, dtype_bytes=dtype_bytes,
                            vmem_limit=vmem_limit,
                            fast_mem_bytes=fast_mem_bytes,
                            objective=objective, device_bytes=device_bytes)
    logger.write("search_start", key=key, objective=objective,
                 backend=backend, streaming=streaming,
                 budget_runs=budget_runs, budget_s=budget_s,
                 pool_sizes=[len(p) for p in pools],
                 model_samples=model_samples, neighbors=len(neighbors),
                 seed=seed, dims=list(meta.dims), nnz=meta.nnz, rank=rank)

    memo: dict[tuple, float] = {}
    new_samples: list[dict] = []

    def measure(mode: int, pool_i: int, chunk_m: int | None,
                generation) -> float | None:
        gene = searches[mode].pool[pool_i]
        cm = (chunk_m if chunk_m is not None else analytic_chunk) \
            if streaming else 0
        mkey = (mode, gene.traversal, gene.r_block, gene.block_m, cm)
        if mkey in memo:
            return memo[mkey]
        if not budget.allows():
            return None
        views, view, B, pi_alto, pi_view = operands(mode)
        cand = candidate_plan(mode, gene, chunk_m)
        feats = gene_features(meta, rank, mode, gene.traversal,
                              gene.r_block, gene.block_m, chunk_m=cm,
                              objective=objective, dtype_bytes=dtype_bytes)
        predicted = model.predict(feats)
        t0 = time.perf_counter()
        if objective == "phi":
            oriented = (view is not None
                        and heuristics.is_oriented(gene.traversal))
            pi = ((pi_view if oriented else pi_alto)
                  if (pre_pi and not streaming) else None)
            median, iqr = _time_phi(cand, at, view, B, factors, pi, mode,
                                    warmup, iters)
        else:
            median, iqr = _time_mttkrp(cand, at, views, factors, mode,
                                       warmup, iters)
        budget.charge(time.perf_counter() - t0)
        median = float(median)
        memo[mkey] = median
        model.add_sample(feats, median)
        new_samples.append({"f": [round(f, 6) for f in feats],
                            "s": median})
        logger.write("measure", key=key, generation=generation, mode=mode,
                     traversal=gene.traversal.value, r_block=gene.r_block,
                     block_m=gene.block_m, chunk_m=cm or None,
                     predicted_us=(None if predicted is None
                                   else predicted * 1e6),
                     measured_us=median * 1e6, iqr_us=iqr * 1e6,
                     budget_runs_used=budget.runs_used,
                     budget_seconds_used=round(budget.seconds_used, 6))
        return median

    # --- the GA loop: round-robin generations over modes ---------------
    def refresh_predictions(ms: _ModeSearch) -> None:
        for i in set(ms.population):
            g = ms.pool[i]
            ms.predicted[i] = model.predict(gene_features(
                meta, rank, ms.mode, g.traversal, g.r_block, g.block_m,
                chunk_m=analytic_chunk or 0, objective=objective,
                dtype_bytes=dtype_bytes))

    model_used = model.ready
    gens_run = 0
    for gen in range(generations):
        if not budget.allows() and gen > 0:
            break
        gens_run = gen + 1
        for ms in searches:
            refresh_predictions(ms)
            for i in ms.to_measure(top_k, first_generation=(gen == 0)):
                t = measure(ms.mode, i, None, generation=gen)
                if t is None:
                    break
                ms.measured[i] = t
            ms.evolve(mutate_p)
        model.fit()

    # --- streaming: the chunk_m gene, on the bottleneck mode ------------
    chunk_winner = analytic_chunk
    n_chunk_cands = 0
    if streaming:
        win_genes = [ms.pool[ms.winner()[0]] for ms in searches]
        align = max(g.block_m for g in win_genes)
        ladder = chunk_ladder(meta, rank, device_bytes, align, dtype_bytes)
        n_chunk_cands = len(ladder)
        measured_modes = [ms for ms in searches if ms.measured]
        if measured_modes:
            bottleneck = max(measured_modes,
                             key=lambda ms: ms.winner()[1]).mode
        else:
            bottleneck = int(np.argmax(meta.dims))
        chunk_times = {}
        for cm in ladder:
            wi = searches[bottleneck].winner()[0]
            t = measure(bottleneck, wi, cm, generation="chunk")
            if t is None:
                break
            chunk_times[cm] = t
        if chunk_times:
            chunk_winner = min(chunk_times,
                               key=lambda c: (chunk_times[c], -c))
        else:
            chunk_winner = ladder[0] if ladder else analytic_chunk
        # The winning chunk must stay aligned to the winning tiling.
        chunk_winner = max(chunk_winner, align)

    # --- assemble the winner plan ---------------------------------------
    winners, win_modes = [], []
    warm = budget.runs_used == 0 and model.ready
    for ms in searches:
        refresh_predictions(ms)
        i, measured_s, predicted_s = ms.winner()
        g = ms.pool[i]
        win_modes.append(g)
        winners.append(ModeWinner(
            mode=ms.mode, traversal=g.traversal.value, r_block=g.r_block,
            block_m=g.block_m, measured_s=measured_s,
            predicted_s=(predicted_s if predicted_s is not None
                         else ms.predicted.get(i)),
            is_static=(i == 0)))
    stream = None
    if streaming:
        stream = plan_mod.StreamPlan(
            chunk_m=chunk_winner,
            n_chunks=plan_mod.chunk_count(meta, chunk_winner),
            device_bytes=device_bytes,
            stream_bytes=plan_mod.incore_working_set_bytes(meta, rank,
                                                           dtype_bytes))
    plan = plan_mod.ExecutionPlan(
        meta=meta, rank=rank, backend=backend, interpret=interpret,
        pi_policy=pi_policy, modes=tuple(win_modes), streaming=stream)

    stored = ""
    if persist:
        record = autotune.serialize_plan(plan)
        record["tuned"] = {
            "mode": "search",
            "platform": jax.default_backend(),
            "objective": objective,
            "seed": seed,
            "generations": gens_run,
            "budget_runs": budget_runs,
            "budget_s": budget_s,
            "runs_used": budget.runs_used,
            "seconds_used": round(budget.seconds_used, 6),
            "warm_start": warm,
        }
        old = plans.get(key) or {}
        keep = (old.get("samples") or [])[:MAX_RECORD_SAMPLES]
        merged = (new_samples + keep)[:MAX_RECORD_SAMPLES]
        record["samples"] = merged
        # Re-load before writing: another process may have persisted
        # since our read, and the store write must not drop its plans.
        plans = autotune.load_store(store_path)
        plans[key] = record
        stored = str(autotune.save_store(plans, store_path))

    report = SearchReport(
        key=key, store=stored, objective=objective, backend=backend,
        budget_runs=budget_runs, budget_s=budget_s,
        runs_used=budget.runs_used,
        seconds_used=budget.seconds_used, generations=gens_run,
        pool_sizes=tuple(len(p) for p in pools),
        model_samples=model_samples, model_used=model_used,
        warm_start=warm, neighbors=len(neighbors),
        winners=tuple(winners),
        chunk_m=chunk_winner if streaming else None,
        chunk_candidates=n_chunk_cands)
    logger.write("search_end", key=key, runs_used=budget.runs_used,
                 seconds_used=round(budget.seconds_used, 6),
                 generations=gens_run, warm_start=warm,
                 chunk_m=report.chunk_m,
                 winners=[{"mode": w.mode, "traversal": w.traversal,
                           "r_block": w.r_block, "block_m": w.block_m,
                           "measured_us": (None if w.measured_s is None
                                           else w.measured_s * 1e6)}
                          for w in winners],
                 store=stored)
    return plan, report
