"""Programs the window's `cp_apr` call traced: its `cpapr.trace` spans,
one per trace of the jitted mode update. 0 when the window holds the
call (`cpapr.call`) and no trace; None without the call."""
from bench import spans


def read(run):
    if run.driver != "apr" or run.trace is None:
        return None
    if not spans.named(run.trace, "cpapr.call"):
        return None
    return len(spans.named(run.trace, "cpapr.trace"))
