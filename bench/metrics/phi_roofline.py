"""Share of the HBM roofline the Φ kernels reach in a CP-APR outer
iteration.

The least time for an outer iteration's Φ calls (every mode, the
traffic's inner iterations each) from their compulsory bytes and FLOPs
(`roofline`), over the device time of the kernel events of one outer
iteration in the traced window. Bound by bytes.
"""
from bench import roofline

# The Φ kernels' device events. The program gives its Pallas calls no
# names of their own: each is an HLO custom call ``%run.<n>`` with this
# target. In a traced CP-APR outer iteration on a TPU v5e they are
# exactly the Φ kernels, inner_iterations per mode.
KERNELS = (r'custom_call_target="tpu_custom_call"',)


def read(run):
    if run.driver != "apr":
        return None
    t = run.kernel_s(KERNELS)
    if t is None:
        return None
    c = run.config
    dims, nnz, words, rank = c["dims"], c["nnz"], c["index_words"], c["rank"]
    inner = run.cell.traffic["inner_iterations"]
    bound = sum(inner * roofline.bound_s(
        roofline.phi_flops(dims, nnz, rank),
        roofline.phi_bytes(dims, nnz, words, rank, mode), run.device_kind)
        for mode in range(len(dims)))
    return 100.0 * bound / t
