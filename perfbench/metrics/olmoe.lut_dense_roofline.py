"""olmoe.lut_dense_roofline: least time of the window's attention
projections and output head over the device time of ``fused_lut_dense``."""
from perfbench import olmoe, readers


def read(ctx):
    cfg, mix = ctx["spec"]["config"], ctx["spec"]["mix"]
    one = olmoe.dense_work(cfg, mix["batch"] * mix["seq_len"],
                           readers.act_bytes(ctx))
    return readers.kernel_roofline(ctx, "fused_lut_dense_kernel",
                                   one * ctx["window"]["batches"])
