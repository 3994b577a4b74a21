"""qat.mfu: 6 N operations per training token (N: parameters that enter a
matrix product) times the window's tokens, over the window at the int8
peak."""
from perfbench import model, readers


def read(ctx):
    n = model.n_params(ctx["spec"]["config"])
    return readers.step_mfu(ctx, 6.0 * n, ctx["window"]["tokens"])
