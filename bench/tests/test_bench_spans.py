"""The readers of the program's spans on synthetic traces
(`idle_dispatch_s.apr`, `idle_sync_s.apr`, `retraces.apr`), and on a
CPU traced run of the harness."""
import types

import pytest

from bench import run as bench_run
from bench import spans as S
from bench import trace as T
from bench.tests.test_bench_harness import SPEC, small_spec

WINDOW = T.Event(T.WINDOW_SPAN, 1000, 10000)
METRICS = ("idle_dispatch_s.apr", "idle_sync_s.apr", "retraces.apr")


def _reader(name):
    return bench_run.load_module(bench_run.BENCH / "metrics" / f"{name}.py")


def _op(start, dur):
    return T.Event("%alto_phi_recursive.1 = f32[8,16] custom-call(...)",
                   start, dur, "jit__mode_update")


def _trace(chips=1, call=True, traces=2):
    # Device busy [1000,3000], [4000,6000], [7000,9000]; idle [3000,4000],
    # [6000,7000] and [9000,11000].
    ops = [_op(0, 3000), _op(4000, 2000), _op(7000, 2000)]
    host = [WINDOW,
            T.Event("cpapr.dispatch", 500, 1000),     # busy: no idle
            T.Event("cpapr.sync", 2800, 400),         # idle [3000,3200]
            T.Event("cpapr.dispatch", 3200, 1000),    # idle [3200,4000]
            T.Event("cpapr.sync", 6000, 300),         # idle [6000,6300]
            T.Event("cpapr.dispatch", 6500, 200),     # idle [6500,6700]
            T.Event("cpapr.dispatch", 10800, 900)]    # idle [10800,11000]
    host += [T.Event("cpapr.trace", 3300 + 100 * i, 50)
             for i in range(traces)]
    host.append(T.Event("cpapr.trace", 200, 50))      # before the window
    if call:
        host.append(T.Event("cpapr.call", 900, 10200))
    return T.Trace({c: list(ops) for c in range(chips)}, host)


def _run(tr, driver="apr", steps=2):
    return types.SimpleNamespace(driver=driver, trace=tr, steps=steps)


def test_idle_is_split_by_span():
    tr = _trace()
    assert S.idle_in(tr, "cpapr.dispatch") == pytest.approx(1200e-9)
    assert S.idle_in(tr, "cpapr.sync") == pytest.approx(500e-9)
    assert S.idle_in(tr, "cpapr.nothing") is None
    run = _run(tr)
    assert _reader("idle_dispatch_s.apr").read(run) == pytest.approx(600e-9)
    assert _reader("idle_sync_s.apr").read(run) == pytest.approx(250e-9)


def test_two_chips_are_averaged():
    tr = _trace(chips=2)
    assert S.idle_in(tr, "cpapr.dispatch") == pytest.approx(1200e-9)
    # The second chip busy through [3000,4000] as well: no idle in the
    # sync span at [3000,3200] nor in the dispatch span at [3200,4000].
    tr.device_ops[1].append(_op(3000, 1000))
    assert S.idle_in(tr, "cpapr.dispatch") == pytest.approx(
        (1200e-9 + 400e-9) / 2)
    assert S.idle_in(tr, "cpapr.sync") == pytest.approx((500e-9 + 300e-9) / 2)
    # A chip that ran nothing is not averaged in.
    tr.device_ops[2] = []
    assert S.idle_in(tr, "cpapr.sync") == pytest.approx((500e-9 + 300e-9) / 2)


def test_retraces_count_trace_spans_in_the_window():
    read = _reader("retraces.apr").read
    assert read(_run(_trace(traces=3))) == 3
    assert read(_run(_trace(traces=0))) == 0
    assert read(_run(_trace(call=False))) is None


@pytest.mark.parametrize("name", METRICS)
def test_readers_need_the_driver_and_a_trace(name):
    read = _reader(name).read
    assert read(_run(_trace())) is not None
    assert read(_run(_trace(), driver="als")) is None
    assert read(_run(None)) is None


def test_readers_leave_a_program_without_spans_out():
    tr = T.Trace({0: [_op(0, 3000)]}, [WINDOW])
    for name in METRICS:
        assert _reader(name).read(_run(tr)) is None


def test_traced_cpu_run_reports_the_span_metrics(tmp_path):
    cell = "uber.apr.clustered"
    out = bench_run.run(cell, 2 ** 31 + 11, 0.5, True,
                        spec=small_spec(tmp_path), chip=False)
    assert out["correct"] is True
    assert set(METRICS) <= set(out["metrics"])
    assert out["metrics"]["retraces.apr"]["value"] >= 1
    assert all(out["metrics"][m]["value"] >= 0 for m in METRICS)
    assert {m["name"] for m in SPEC["per_layer"]} >= set(METRICS)
