"""Device idle seconds per CP-APR outer iteration inside the program's
`cpapr.dispatch` spans: the host issuing a mode update while the device
waits, any trace, lowering or compile of the update included."""
from bench import spans


def read(run):
    return spans.idle_per_step(run, "apr", "cpapr.dispatch")
