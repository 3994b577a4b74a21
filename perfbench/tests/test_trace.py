"""The trace reduction against a small trace recorded on a TPU v5e: two
layers of smollm-135m at full width, one QAT step of 2 x 128 tokens, with
the host span ``bench.qat.step`` around it."""
import pathlib

import jax
import pytest

from perfbench import trace

DATA = pathlib.Path(__file__).parent / "data" / "qat_small.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return trace.reduce_file(str(DATA), host_prefix="bench.")


@pytest.fixture(scope="module")
def raw():
    pd = jax.profiler.ProfileData.from_file(str(DATA))
    for plane in pd.planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    return [(e.name, e.start_ns, e.duration_ns) for e in line.events]


def test_window_is_the_host_span(summary):
    assert summary.window_s == pytest.approx(0.361390418, abs=1e-9)
    assert summary.n_devices == 1


def test_busy_and_idle(summary):
    assert summary.busy_s == pytest.approx(0.358515468, abs=1e-9)
    assert 0 < summary.idle_share < 0.01


def test_self_times_add_up_to_busy(summary):
    # ops on the device run one at a time apart from loop nesting, so
    # self times partition the busy time
    assert sum(summary.op_s.values()) == pytest.approx(summary.busy_s, rel=1e-9)


def test_kernel_time_matches_a_plain_sum(summary, raw):
    # kernels contain no ops, so their self time is their duration
    for fam in ("fused_lut_dense_kernel", "fused_lut_bwd_kernel"):
        want = sum(d for n, _, d in raw if fam in n.split(" = ")[0]) / 1e9
        got = sum(s for k, s in summary.op_s.items() if fam in k)
        assert got == pytest.approx(want, rel=1e-12)
        assert got > 0


def test_modules(summary):
    assert set(summary.module_s) == {"jit_step_fn"}
    assert summary.module_s["jit_step_fn"] <= summary.window_s


def test_breakdown(summary):
    b = summary.breakdown()
    assert len(b["device_ops"]) == 10
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert b["device_ops"][0][0] == "transpose_jvp_jit_fused_lut_bwd_kernel___"
    assert sum(s for _, s in b["idle_gaps"]) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-6)


def test_op_family():
    assert trace.op_family("%fused_lut_dense_kernel.57 = f32[8,49152] custom-call(x)") \
        == "fused_lut_dense_kernel"
    assert trace.op_family("%while.12 = (s32[]) while(x)") == "while"
