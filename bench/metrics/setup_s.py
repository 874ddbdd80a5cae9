"""Set-up: process start to the window's start, on the host clock —
device generation, ingest, plan, compile or cache load, and the check
steps."""


def read(run):
    return run.setup_s
