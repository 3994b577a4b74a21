"""Int8 error-feedback gradient compression."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.optim.compression import EFState, compress, compressed_psum, decompress, init_ef


def test_compress_roundtrip_bound(rng):
    g = jnp.asarray(rng.normal(size=(128,)) * 5, jnp.float32)
    q, scale = compress(g)
    back = decompress(q, scale)
    assert float(jnp.abs(back - g).max()) <= float(scale) / 2 + 1e-6
    assert q.dtype == jnp.int8


def test_psum_path_roundtrips_through_compress(rng):
    """Regression: the psum path must quantize through the same helper as
    standalone compress() — with the pmax'd amax passed in, its transmitted
    value is exactly decompress(compress(g, amax)) and the standalone
    round-trip bound holds inside the collective path too."""
    from repro.launch.mesh import make_mesh
    from jax.sharding import PartitionSpec as P
    mesh = make_mesh((1,), ("dp",))

    g = jnp.asarray(rng.normal(size=(64,)) * 3, jnp.float32)
    ef = init_ef({"w": g})

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P(), P()),
                       out_specs=(P(), P()), check_vma=False)
    def step(g, r):
        out, ef2 = compressed_psum({"w": g}, EFState(residual={"w": r}), "dp")
        return out["w"], ef2.residual["w"]

    sent, resid = step(g, ef.residual["w"])
    q, scale = compress(g)                      # 1 worker: pmax == local amax
    np.testing.assert_array_equal(np.asarray(sent),
                                  np.asarray(decompress(q, scale)))
    # residual is exactly what int8 dropped, bounded by half a code step
    np.testing.assert_array_equal(np.asarray(resid),
                                  np.asarray(g - decompress(q, scale)))
    assert float(jnp.abs(resid).max()) <= float(scale) / 2 + 1e-6


def test_compress_external_amax_roundtrip_bound(rng):
    """compress() with a caller-supplied (e.g. pmax'd) bound still
    round-trips within half a step of the *wider* grid."""
    g = jnp.asarray(rng.normal(size=(128,)), jnp.float32)
    amax = jnp.max(jnp.abs(g)) * 4.0            # another worker's larger amax
    q, scale = compress(g, amax)
    assert q.dtype == jnp.int8
    # multiply-form grid (bound * (1/127)): the division form was rewritten
    # inconsistently between eager and jitted code (see compress())
    assert float(scale) == float(jnp.maximum(amax, 1e-12) * (1.0 / 127.0))
    assert float(jnp.abs(decompress(q, scale) - g).max()) \
        <= float(scale) / 2 + 1e-6


def test_error_feedback_unbiased_over_steps(rng):
    """Sum of transmitted values + residual == sum of true gradients."""
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("dp",))
    from jax.sharding import PartitionSpec as P

    grads = {"w": jnp.asarray(rng.normal(size=(32,)), jnp.float32)}
    ef = init_ef(grads)
    sent_total = jnp.zeros(32)
    true_total = jnp.zeros(32)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P(), P()),
                       out_specs=(P(), P()), check_vma=False)
    def step(g, r):
        out, ef2 = compressed_psum({"w": g}, EFState(residual={"w": r}), "dp")
        return out["w"], ef2.residual["w"]

    r = ef.residual["w"]
    for i in range(5):
        g = grads["w"] * (i + 1)
        sent, r = step(g, r)
        sent_total = sent_total + sent
        true_total = true_total + g
    # transmitted + final residual == true sum (error feedback invariant)
    np.testing.assert_allclose(np.asarray(sent_total + r),
                               np.asarray(true_total), rtol=1e-4, atol=1e-4)


def test_ef_sgd_converges_like_exact(rng):
    """EF-compressed SGD reaches the same quadratic minimum."""
    target = jnp.asarray(rng.normal(size=(16,)), jnp.float32)
    x_ef = jnp.zeros(16)
    x_ex = jnp.zeros(16)
    resid = jnp.zeros(16)
    lr = 0.2
    for _ in range(60):
        g_ef = (x_ef - target) + resid
        q, s = compress(g_ef)
        sent = decompress(q, s)
        resid = g_ef - sent
        x_ef = x_ef - lr * sent
        x_ex = x_ex - lr * (x_ex - target)
    assert float(jnp.linalg.norm(x_ef - target)) < 0.05
    assert float(jnp.linalg.norm(x_ef - x_ex)) < 0.05
