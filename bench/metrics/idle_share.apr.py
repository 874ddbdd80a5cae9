"""Share of the traced CP-APR window in which no operation ran on the
device: 1 - (union of device-busy intervals) / window."""
from bench import trace


def read(run):
    return trace.idle_share(run, "apr")
