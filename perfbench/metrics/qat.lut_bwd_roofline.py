"""qat.lut_bwd_roofline: least time of the window's approximate STE
gradient GEMMs over the device time of ``fused_lut_bwd``."""
from perfbench import readers


def read(ctx):
    return readers.kernel_roofline(ctx, "fused_lut_bwd_kernel",
                                   readers.train_bwd_work(ctx))
