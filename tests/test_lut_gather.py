"""The shared LUT-GEMM core (kernels/lut_gather.py) against a plain gather.

``lut_gemm`` splits the 2-D table lookup into lane gathers and one exact
one-hot matmul per signed base-256 digit plane. The reference is one
``jnp.take`` of the flattened table with an int32 sum; integer arithmetic
is exact (both wrap modulo 2^32), so the two must agree bit for bit for
every output width and every table the digit planes cover.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.lut_gather import lut_gemm, lut_planes, pad_lut


def _table(n_codes: int, planes: int, seed: int) -> np.ndarray:
    """Synthetic (n, n) int32 table that needs exactly ``planes`` digits:
    entries span the range that many digits reach (all of int32 for four),
    both extremes present."""
    rng = np.random.default_rng(seed)
    span = (256 ** planes - 1) // 255
    lo, hi = (-2 ** 31, 2 ** 31 - 1) if planes == 4 else (-128 * span,
                                                          127 * span)
    tab = rng.integers(lo, hi + 1, (n_codes, n_codes), dtype=np.int64)
    tab[0, -1], tab[-1, 0] = lo, hi
    return tab.astype(np.int32)


def _codes(m: int, k: int, n: int, n_codes: int, seed: int):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.integers(0, n_codes, (m, k)), jnp.int32),
            jnp.asarray(rng.integers(0, n_codes, (k, n)), jnp.int32))


def _take_ref(a, b, tab):
    n_codes = tab.shape[1]
    idx = a[:, :, None] * n_codes + b[None, :, :]
    return jnp.take(jnp.asarray(tab).reshape(-1), idx).sum(axis=1,
                                                           dtype=jnp.int32)


@pytest.mark.parametrize("planes", [1, 2, 3, 4])
@pytest.mark.parametrize("n", [64, 128, 256])
def test_lut_gemm_matches_take(n, planes):
    """N below 128 (the lane-padded branch) and multiples of 128, tables of
    1-4 digit planes with large negative and positive entries; K = 20 is
    not a multiple of the 8-index one-hot chunk of a 256-row table."""
    tab = _table(256, planes, seed=planes)
    assert lut_planes(tab) == planes
    lut, n_codes = pad_lut(tab)
    a, b = _codes(8, 20, n, n_codes, seed=n + planes)
    got = jax.jit(lambda a, b, t: lut_gemm(a, b, t, n_planes=planes))(
        a, b, lut)
    assert got.shape == (8, n) and got.dtype == jnp.int32
    assert jnp.array_equal(got, _take_ref(a, b, tab))


@pytest.mark.parametrize("m,k", [(200, 20), (136, 150)])
@pytest.mark.parametrize("planes", [2, 4])
def test_lut_gemm_row_blocks_match_take(m, k, planes):
    """More rows than one one-hot block (a short last block) against N =
    256 (two gathered column chunks): every (row block, column chunk)
    accumulator sums the same integers as the gather; K = 150 runs a full
    128-wide piece and a 22-index one with a 6-index tail chunk."""
    tab = _table(256, planes, seed=10 + planes)
    lut, n_codes = pad_lut(tab)
    a, b = _codes(m, k, 256, n_codes, seed=m + k)
    got = jax.jit(lambda a, b, t: lut_gemm(a, b, t, n_planes=planes))(
        a, b, lut)
    assert got.shape == (m, 256)
    assert jnp.array_equal(got, _take_ref(a, b, tab))


@pytest.mark.parametrize("n_codes,planes", [(256, 2), (256, 4), (16, 1)])
def test_lut_gemm_traced_table(n_codes, planes):
    """A table that arrives as a traced jit argument has no values to read:
    ``lut_planes`` falls back to all four digits, which is exact for any
    table (including a 16-code one padded to a 128x128 block)."""
    tab = _table(n_codes, planes, seed=n_codes + planes)
    lut, _ = pad_lut(tab)
    a, b = _codes(8, 24, 64, n_codes, seed=3)
    seen = []

    def f(a, b, t):
        seen.append(lut_planes(t))
        return lut_gemm(a, b, t, n_planes=seen[-1])

    got = jax.jit(f)(a, b, lut)
    assert seen == [4]
    assert jnp.array_equal(got, _take_ref(a, b, tab))
