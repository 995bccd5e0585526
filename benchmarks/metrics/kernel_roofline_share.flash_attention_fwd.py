"""kernels: share of its roofline of the flash attention forward kernel (``flash_attention_fwd``, all layers) in
the traced window; operations, bytes and conventions in lib/kernel_costs.py."""

from benchmarks.lib.kernel_costs import roofline_share


def read(run):
    return roofline_share(run, "flash_attention_fwd")
