"""The trace reduction on a synthetic trace: kernel time, busy union, idle
share, breakdown and idle-gap labels; and loading a recorded trace."""
import pytest

from bench import trace as T

WINDOW = T.Event(T.WINDOW_SPAN, 1000, 10000)


KERNEL = 'custom_call_target="tpu_custom_call"'


def _op(short, start, dur, module, kernel=False):
    text = f"{short} = f32[8,16] custom-call(...), " + (
        KERNEL if kernel else 'custom_call_target="EighTpu"')
    return T.Event(text, start, dur, module)


def _trace(chips=1):
    ops = [_op("%pre", 0, 1500, "jit_other"),               # clipped to 500
           _op("%run.1", 2000, 3000, "jit__sweep", kernel=True),
           _op("%fusion.3", 5000, 1000, "jit__sweep"),
           _op("%while.1", 6800, 2600, "jit__sweep"),       # encloses run.2
           _op("%run.2", 7000, 2000, "jit__sweep", kernel=True)]
    host = [WINDOW, T.Event("outer", 0, 20000),
            T.Event("_fit_host", 5900, 1200)]
    return T.Trace({c: list(ops) for c in range(chips)}, host)


def test_window_busy_and_idle():
    s = T.summarize(_trace())
    assert s.window_s == pytest.approx(10000e-9)
    # busy: [1000,1500] + [2000,6000] + [6800,9400] = 7100 ns
    assert s.busy_s == pytest.approx(7100e-9)
    assert 1 - s.busy_s / s.window_s == pytest.approx(0.29)


def test_kernel_time_is_union_of_matching_ops():
    assert T.kernel_s(_trace(), [KERNEL]) == pytest.approx(5000e-9)
    assert T.kernel_s(_trace(), [r"jit__sweep"]) == pytest.approx(6600e-9)
    assert T.kernel_s(_trace(), [r"^nothing$"]) == 0.0


def test_breakdown():
    s = T.summarize(_trace())
    ops = dict(s.device_ops)
    assert ops["%run.1 (jit__sweep)"] == pytest.approx(3000e-9)
    assert ops["%pre (jit_other)"] == pytest.approx(500e-9)
    # a loop's own time leaves out the body's operations
    assert ops["%while.1 (jit__sweep)"] == pytest.approx(600e-9)
    assert s.device_ops[0][0] == "%run.1 (jit__sweep)"
    # gaps [1500,2000] and [9400,11000] fall under "outer", [6000,6800]
    # under the innermost span that overlaps most of it, "_fit_host"
    assert s.idle_gaps == [("outer", pytest.approx(2100e-9)),
                           ("_fit_host", pytest.approx(800e-9))]


def test_chips_are_averaged():
    one, four = T.summarize(_trace(1)), T.summarize(_trace(4))
    assert four.busy_s == pytest.approx(one.busy_s)
    assert T.kernel_s(_trace(4), [KERNEL]) == pytest.approx(5000e-9)


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        T.Trace({0: []}, [T.Event("other", 0, 10)]).window()


def test_union_and_idle():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert T.idle([(2, 3), (5, 8)], 0, 10) == [(0, 2), (3, 5), (8, 10)]


def test_load_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((64,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
            f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    tr = T.load(str(tmp_path))
    lo, hi = tr.window()
    assert hi > lo
    assert any(e.name == T.WINDOW_SPAN for e in tr.host)
