"""Device seconds per CP-APR outer iteration outside the Φ kernels: the
B updates, KKT values, λ and normalisation, and the XLA operations
around the kernels (the recursive traversal's pull reduction)."""
from bench import trace


def read(run):
    return trace.dense_s(run, "apr", run.metric("phi_roofline").KERNELS)
