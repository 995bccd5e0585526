"""kernels: share of its roofline of the fused lm-head + CE backward kernels (``fused_ce_bwd_dh`` + ``fused_ce_bwd_dw``) in
the traced window; operations, bytes and conventions in lib/kernel_costs.py."""

from benchmarks.lib.kernel_costs import roofline_share


def read(run):
    return roofline_share(run, "fused_ce_bwd")
