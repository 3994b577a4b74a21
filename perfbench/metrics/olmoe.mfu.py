"""olmoe.mfu: 2 N operations per scored token (N: parameters that enter a
matrix product, the k routed experts of each layer among them) times the
window's tokens, over the window at the int8 peak."""
from perfbench import olmoe, readers


def read(ctx):
    n = olmoe.n_params(ctx["spec"]["config"])
    return readers.step_mfu(ctx, 2.0 * n, ctx["window"]["tokens"])
