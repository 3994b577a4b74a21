"""olmoe.lut_grouped_roofline: least time of the window's expert GEMMs (the
T k routed rows of each batch spread over the experts, each expert's
weights read once) over the device time of ``fused_lut_grouped``."""
from perfbench import olmoe, readers


def read(ctx):
    cfg, mix = ctx["spec"]["config"], ctx["spec"]["mix"]
    one = olmoe.expert_work(cfg, mix["batch"] * mix["seq_len"],
                            readers.act_bytes(ctx))
    return readers.kernel_roofline(ctx, "fused_lut_grouped_kernel",
                                   one * ctx["window"]["batches"])
