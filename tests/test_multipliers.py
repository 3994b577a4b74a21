"""Multiplier zoo: exactness, bounds, and bit-level properties."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.core.multipliers import (REGISTRY, error_stats, get_multiplier,
                                    make_bam, make_drum, make_exact,
                                    make_mitchell, make_trunc)

i8 = st.integers(-128, 127)
i12 = st.integers(-2048, 2047)


def test_exact_is_exact():
    m = make_exact(8)
    a = np.arange(-128, 128)
    out = np.asarray(m(jnp.asarray(a[:, None]), jnp.asarray(a[None, :])))
    assert np.array_equal(out, a[:, None] * a[None, :])


@given(a=i8, w=i8, t=st.integers(1, 4))
def test_trunc_error_bound(a, w, t):
    """|trunc error| <= |a|*2^t + |w|*2^t (masked low bits of both operands)."""
    m = make_trunc(8, t)
    out = int(m(jnp.int32(a), jnp.int32(w)))
    err = abs(out - a * w)
    assert err <= (abs(a) + abs(w) + 2 ** t) * 2 ** t


@given(a=i8, w=i8)
def test_bam_underestimates_magnitude(a, w):
    """Perforation only drops positive partial products of |a|*|w|."""
    m = make_bam(8, 6)
    out = int(m(jnp.int32(a), jnp.int32(w)))
    assert abs(out) <= abs(a * w)
    assert np.sign(out) in (0, np.sign(a * w))


@given(a=i8, w=i8)
def test_bam_symmetry(a, w):
    m = make_bam(8, 6)
    assert int(m(jnp.int32(a), jnp.int32(w))) == int(m(jnp.int32(w), jnp.int32(a)))


@given(a=i8, w=i8)
def test_mitchell_relative_error(a, w):
    """Mitchell log multiplier: relative error < 11.2% (2 - 2^(x) bound)."""
    m = make_mitchell(8)
    out = int(m(jnp.int32(a), jnp.int32(w)))
    if a * w != 0:
        assert abs(out - a * w) / abs(a * w) <= 0.115 + 2.0 / abs(a * w)
    else:
        assert out == 0


@given(a=i12, w=i12)
def test_drum_relative_error(a, w):
    """DRUM k-bit windows: per-operand relative error <= 2^(1-k), so the
    product error is bounded by (1 + 2^-10)^2 - 1 = 2^-9 + 2^-20 for k=11
    (attained at exact powers of two, e.g. a = w = -2048)."""
    m = make_drum(12, 11)
    out = int(m(jnp.int32(a), jnp.int32(w)))
    if a * w != 0:
        assert abs(out - a * w) / abs(a * w) <= 2 ** -9 + 2 ** -20
    else:
        assert out == 0


@given(a=i8)
def test_zero_annihilates(a):
    """M[0, x] == M[x, 0] == 0 for every family (depthwise block-diag GEMMs
    rely on this — approx_ops.conv2d)."""
    for name, m in REGISTRY.items():
        if m.bits != 8:
            continue
        assert int(m(jnp.int32(0), jnp.int32(a))) == 0, name
        assert int(m(jnp.int32(a), jnp.int32(0))) == 0, name


def test_paper_role_stats():
    """The named stand-ins land in the paper's error regimes."""
    s8 = error_stats(get_multiplier("mul8s_1L2H"))
    assert 1.0 < s8["mre_pct"] < 10.0        # paper: 4.41%
    assert s8["mae_pct"] < 0.3               # paper: 0.081%
    s12 = error_stats(get_multiplier("mul12s_2KM"))
    assert s12["mre_pct"] < 1e-3             # paper: 4.7e-4%
    assert s12["mae_pct"] < 1e-4             # paper: 1.2e-6%


def test_registry_names():
    for name in ("mul8s_exact", "mul8s_1L2H", "mul12s_2KM", "mul8s_mitchell"):
        assert get_multiplier(name).name == name
    with pytest.raises(KeyError):
        get_multiplier("nope")
