"""Device idle seconds per CP-APR outer iteration inside the program's
`cpapr.sync` spans: the three host reads of each mode's update
(`int(n_inner)`, `bool(conv)`, `float(kkt)`)."""
from bench import spans


def read(run):
    return spans.idle_per_step(run, "apr", "cpapr.sync")
