"""Program spans and trace counters (`core.telemetry`) on the CPU.

A tiny `cp_apr` and `cp_als` run under `jax.profiler.trace`; the
recorded ``.xplane.pb`` is read back with `ProfileData`, as the
benchmark reads a chip's trace. The spans must nest as documented
(`docs/tracing.md`), the trace counters must agree with the trace spans,
a profiler session must not change a result, and each Pallas kernel must
carry its name into the lowered program.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import alto, cpals, cpapr, plan as plan_mod, telemetry
from repro.kernels import ops
from repro.sparse import synthetic

DIMS = (13, 11, 9)
RANK = 3
K = 3
# Statics no other test shares, so this file's first solve traces.
APR = cpapr.CpaprParams(k_max=K, l_max=2, tau=0.0, eps_div=1.25e-10)


@pytest.fixture(scope="module")
def count_tensor():
    x, _ = synthetic.lowrank_count(DIMS, rank=RANK, nnz_target=300, seed=7)
    return alto.build_device(x, n_partitions=3)


def _host_spans(log_dir, prefixes=("cpapr.", "cpals.", "ingest.")):
    """(name, start, end, stats) of the program's spans in the trace."""
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefixes):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                {k: v for k, v in ev.stats}))
    return sorted(out, key=lambda s: s[1])


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _within(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _profiled(tmp_path, fn):
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
        jax.block_until_ready(out)
    return out, _host_spans(str(tmp_path))


def test_cp_apr_spans_nest_and_count_traces(count_tensor, tmp_path):
    before = telemetry.counts().get("cpapr.trace", 0)
    res, spans = _profiled(tmp_path, lambda: cpapr.cp_apr(
        count_tensor, RANK, params=APR, seed=1))
    traces = telemetry.counts().get("cpapr.trace", 0) - before
    assert res.n_outer == K
    N = len(DIMS)

    (call,) = _named(spans, "cpapr.call")
    (prepare,) = _named(spans, "cpapr.prepare")
    assert _within(prepare, call)
    outers = _named(spans, "cpapr.outer")
    assert [s[3]["outer"] for s in outers] == list(range(1, K + 1))
    dispatch = _named(spans, "cpapr.dispatch")
    sync = _named(spans, "cpapr.sync")
    for k, outer in enumerate(outers, start=1):
        assert _within(outer, call) and outer[1] >= prepare[2]
        d = [s for s in dispatch if _within(s, outer)]
        y = [s for s in sync if _within(s, outer)]
        assert [s[3]["mode"] for s in d] == list(range(N))
        assert [s[3]["mode"] for s in y] == list(range(N))
        assert all(bool(s[3]["first_outer"]) == (k == 1) for s in d)
    assert len(dispatch) == len(sync) == K * N

    trace_spans = _named(spans, "cpapr.trace")
    assert len(trace_spans) == traces >= 1
    assert all(any(_within(t, d) for d in dispatch) for t in trace_spans)


def test_cp_als_spans_nest_and_count_traces(count_tensor, tmp_path):
    before = telemetry.counts().get("cpals.trace", 0)
    res, spans = _profiled(tmp_path, lambda: cpals.cp_als(
        count_tensor, RANK, n_iters=K, tol=0.0, seed=2))
    traces = telemetry.counts().get("cpals.trace", 0) - before
    assert res.n_iters == K

    (call,) = _named(spans, "cpals.call")
    (prepare,) = _named(spans, "cpals.prepare")
    assert _within(prepare, call)
    dispatch = _named(spans, "cpals.dispatch")
    fit = _named(spans, "cpals.fit")
    assert [s[3]["it"] for s in dispatch] == list(range(1, K + 1))
    assert [s[3]["it"] for s in fit] == list(range(1, K + 1))
    assert all(_within(s, call) and s[1] >= prepare[2]
               for s in dispatch + fit)
    # A sweep's fit follows its dispatch.
    assert all(d[2] <= f[1] for d, f in zip(dispatch, fit))

    trace_spans = _named(spans, "cpals.trace")
    assert len(trace_spans) == traces >= 1
    assert all(any(_within(t, d) for d in dispatch) for t in trace_spans)


def test_ingest_spans(tmp_path):
    x = synthetic.uniform_tensor((14, 12, 10), 250, seed=3)

    def ingest():
        at = alto.build_device(x, n_partitions=2)
        plan = plan_mod.make_plan(at.meta, RANK, tune="off")
        return at, plan_mod.build_views(at, plan)

    _, spans = _profiled(tmp_path, ingest)
    names = [s[0] for s in spans]
    for name in ("ingest.build_device", "ingest.make_plan",
                 "ingest.build_views"):
        assert names.count(name) == 1, names
    (build,) = _named(spans, "ingest.build_device")
    assert all(_within(s, build) for s in _named(spans, "ingest.build.trace"))


@pytest.mark.parametrize("driver", ["cp_apr", "cp_als"])
def test_a_session_changes_no_result(count_tensor, driver, tmp_path):
    def solve():
        if driver == "cp_apr":
            return cpapr.cp_apr(count_tensor, RANK, params=APR, seed=4)
        return cpals.cp_als(count_tensor, RANK, n_iters=K, tol=0.0, seed=4)

    plain = solve()
    traced, _ = _profiled(tmp_path, solve)
    for a, b in zip([plain.lam, *plain.factors],
                    [traced.lam, *traced.factors]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_traced_counts_traces_not_calls():
    name = "test.telemetry.trace"
    f = jax.jit(telemetry.traced(name)(lambda x: x * 2))
    before = telemetry.counts().get(name, 0)
    f(jnp.ones(3))
    f(jnp.zeros(3))                          # same shape: cached
    assert telemetry.counts()[name] == before + 1
    f(jnp.ones(4))                           # new shape: traced again
    assert telemetry.counts()[name] == before + 2
    telemetry.counts()[name] = -1            # a copy
    assert telemetry.counts()[name] == before + 2


def test_trace_counter_views_read_the_registry(count_tensor):
    assert set(alto.device_ingest_traces()) == {"build", "view", "merge"}
    c = telemetry.counts()
    assert alto.device_ingest_traces()["build"] == c.get(
        "ingest.build.trace", 0)


def _kernel_calls(count_tensor):
    """Each kernel's public entry point, as a function of the factors."""
    at = count_tensor
    view = alto.oriented_view_device(at, 0)
    B = jnp.full((DIMS[0], RANK), 0.5, jnp.float32)
    return {
        "alto_delinearize": lambda fs: ops.delinearize(
            at.meta.enc, at.words, interpret=True),
        "alto_mttkrp_recursive": lambda fs: ops.mttkrp(
            at, fs, 0, interpret=True),
        "alto_mttkrp_oriented": lambda fs: ops.mttkrp_oriented(
            view, fs, interpret=True),
        "alto_mttkrp_carry": lambda fs: ops.mttkrp_oriented_carry(
            view, fs, interpret=True),
        "alto_phi_recursive": lambda fs: ops.cpapr_phi(
            at, B, 0, factors=fs, interpret=True),
        "alto_phi_oriented": lambda fs: ops.cpapr_phi_oriented(
            view, B, factors=fs, interpret=True),
        "alto_phi_carry": lambda fs: ops.cpapr_phi_oriented_carry(
            view, B, factors=fs, interpret=True),
    }


@pytest.mark.parametrize("kernel", [
    "alto_delinearize", "alto_mttkrp_recursive", "alto_mttkrp_oriented",
    "alto_mttkrp_carry", "alto_phi_recursive", "alto_phi_oriented",
    "alto_phi_carry"])
def test_kernel_name_reaches_the_lowered_program(count_tensor, kernel):
    fn = _kernel_calls(count_tensor)[kernel]
    factors = [jnp.full((I, RANK), 0.25, jnp.float32) for I in DIMS]
    text = jax.jit(fn).lower(factors).as_text(debug_info=True)
    assert f"{kernel}/pallas_call" in text
    others = set(_kernel_calls(count_tensor)) - {kernel}
    assert not any(f"{k}/pallas_call" in text for k in others)
