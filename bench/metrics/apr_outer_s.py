"""Seconds per CP-APR outer iteration: the window's `cp_apr` call, ended
by `block_until_ready`, over the outer iterations it ran (host clock)."""


def read(run):
    return run.step_s if run.driver == "apr" else None
