"""qat.lut_dense_roofline: least time of the window's approximate forward
GEMMs (each counted once) over the device time of ``fused_lut_dense``."""
from perfbench import readers


def read(ctx):
    return readers.kernel_roofline(ctx, "fused_lut_dense_kernel",
                                   readers.train_fwd_work(ctx))
