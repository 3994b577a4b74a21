"""The scope attribution against two traces recorded on a TPU v5e.

``qat_small`` (see ``test_trace.py``) comes from a program that set no scope
and no ``repro.*`` span, so every op is ``(unscoped)``: its phases and the
partition of busy time are what it can check. ``qat_scoped``
(``record_trace.py``: two layers at full width, two QAT steps of 2 x 128
tokens) comes from a program with its scopes and trainer spans.
"""
import pathlib

import jax
import pytest

from perfbench import scope_report, scopes, trace

DATA = pathlib.Path(__file__).parent / "data" / "qat_small.xplane.pb"
SCOPED = pathlib.Path(__file__).parent / "data" / "qat_scoped.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return scopes.reduce_file(str(DATA), host_prefix="bench.")


@pytest.fixture(scope="module")
def ops():
    return scopes.tf_ops(str(DATA))


def test_wire_reader_reads_tf_op(ops):
    assert set(ops) == {"/device:TPU:0"}
    (name,) = [k for k in ops["/device:TPU:0"]
               if k.startswith("%fused_lut_dense_kernel.150 ")]
    assert ops["/device:TPU:0"][name] == (
        "jit(step_fn)/jvp()/while/body/closed_call/"
        "jit(fused_lut_dense_kernel)/pallas_call:")


def test_every_kernel_op_has_a_path(ops):
    paths = ops["/device:TPU:0"]
    kernels = [k for k in paths if "fused_lut_" in k.split(" = ")[0]]
    assert kernels and all("pallas_call" in paths[k] for k in kernels)


def test_recompute_is_under_forward(summary):
    assert 0 < summary.phase_s("recompute") < summary.phase_s("forward")


def test_buckets_partition_busy_time(summary):
    assert sum(summary.device_s.values()) == pytest.approx(summary.busy_s,
                                                           rel=1e-9)
    assert summary.busy_s == pytest.approx(
        trace.reduce_file(str(DATA), host_prefix="bench.").busy_s, rel=1e-12)


def test_unscoped_program(summary):
    assert {k.split("/")[0] for k in summary.device_s} == {scopes.UNSCOPED}
    assert summary.step_host_s == []


def test_gaps_are_the_idle_time(summary):
    assert sum(summary.gaps.values()) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-6)
    assert set(summary.gaps) == {"(none)"}


def test_as_dict(summary):
    d = summary.as_dict()
    assert set(d) == {"device_s", "idle_gaps", "step_host_s"}
    assert set(d["device_s"][scopes.UNSCOPED]) == set(scopes.PHASES)


@pytest.mark.parametrize("path,want", [
    ("jit(step_fn)/jvp()/while/body/closed_call/attn/"
     "jit(fused_lut_dense_kernel)/fused_lut_dense_kernel/pallas_call",
     "attn/forward"),
    ("jit(step_fn)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/jit(fused_lut_dense_kernel)/pallas_call",
     "mlp/recompute"),
    ("jit(step_fn)/transpose(jvp())/while/body/closed_call/checkpoint/mlp/"
     "jit(fused_lut_bwd_kernel)/pallas_call", "mlp/backward"),
    ("jit(step_fn)/transpose(jvp(lm_head))/jit(fused_lut_bwd_kernel)/"
     "pallas_call", "lm_head/backward"),
    ("jit(step_fn)/jvp(loss)/jit(take_along_axis)", "loss/forward"),
    ("jit(step_fn)/optimizer/mul", "optimizer/forward"),
    ("jit(score)/while/body/closed_call/checkpoint/attn/reshape;"
     "checkpoint/attn", "attn/forward"),
    ("jit(step_fn)/transpose(jvp())/while/body", "(unscoped)/backward"),
    ("", "(unscoped)/forward"),
    (None, "(unscoped)/forward"),
])
def test_label(path, want):
    assert scopes.label(path) == want


def _ctx(scope_s: dict, steps: int = 2, kind: str = "train"):
    s = scopes.ScopeSummary(window_s=1.0, busy_s=sum(scope_s.values()),
                            device_s=scope_s, gaps={}, step_host_s=[0.002])
    return {"scopes": s, "device": {"kind": "TPU v5 lite"},
            "window": {"steps": steps, "batches": steps},
            "spec": {"config": {"hidden_size": 576, "intermediate_size": 1536,
                                "vocab_size": 49152, "num_hidden_layers": 30,
                                "num_attention_heads": 9,
                                "num_key_value_heads": 3},
                     "mix": {"kind": kind, "batch": 2, "seq_len": 1024,
                             "dtype": "float32"}}}


def test_head_roofline_reads_the_head_scope():
    # forward + two gradient GEMMs of 2048 x 576 x 49152 in float32 take at
    # least 1.80 ms a step on a v5e (bandwidth-bound)
    ctx = _ctx({"lm_head/forward": 0.72, "lm_head/backward": 1.41,
                "attn/forward": 5.0})
    work = scope_report.head_work(ctx, 1, backward=True)
    assert len(work) == 3
    got = scope_report.scope_roofline(
        ctx, "lm_head", scope_report.head_work(ctx, 2, backward=True))
    assert got == pytest.approx(100 * 2 * 1.80e-3 / 2.13, rel=0.01)


def test_head_roofline_is_none_without_the_scope():
    ctx = _ctx({"(unscoped)/forward": 1.0})
    work = scope_report.head_work(ctx, 2, backward=False)
    assert scope_report.scope_roofline(ctx, "lm_head", work) is None
    assert scope_report.scope_roofline({"scopes": None}, "lm_head",
                                        work) is None


def test_training_readings():
    ctx = _ctx({"lm_head/forward": 0.72, "lm_head/backward": 1.41,
                "mlp/recompute": 1.0, "mlp/forward": 2.0,
                "(unscoped)/forward": 0.87})
    got = scope_report.readings(ctx)
    assert set(got) == {"qat.remat_share", "qat.lm_head_roofline",
                        "qat.step_host_ms"}
    assert got["qat.remat_share"] == pytest.approx(100 * 1.0 / 6.0)
    assert got["qat.lm_head_roofline"] == pytest.approx(
        100 * 2 * 1.80e-3 / 2.13, rel=0.01)
    assert got["qat.step_host_ms"] == pytest.approx(2.0)


def test_scoring_readings():
    # one head forward of 2048 x 576 x 49152 in bfloat16 a batch
    ctx = _ctx({"lm_head/forward": 1.0, "mlp/forward": 3.0}, steps=3,
               kind="score")
    ctx["spec"]["mix"]["dtype"] = "bfloat16"
    got = scope_report.readings(ctx)
    assert set(got) == {"eval.lm_head_roofline"}
    work = scope_report.head_work(ctx, 3, backward=False)
    assert got["eval.lm_head_roofline"] == pytest.approx(
        100 * scope_report.counts.total_least_s(
            work, scope_report.counts.peaks("TPU v5 lite")))


def test_unscoped_program_reads_only_the_remat_share():
    ctx = _ctx({"(unscoped)/forward": 3.0, "(unscoped)/recompute": 1.0})
    ctx["scopes"].step_host_s = []
    assert scope_report.readings(ctx) == {"qat.remat_share": 25.0}


# -- a program with scopes and spans ------------------------------------------

@pytest.fixture(scope="module")
def scoped():
    return scopes.reduce_file(str(SCOPED), host_prefix="bench.")


@pytest.fixture(scope="module")
def scoped_ops():
    """(op family, label, seconds) of every device op of the window."""
    paths = scopes.tf_ops(str(SCOPED))["/device:TPU:0"]
    pd = jax.profiler.ProfileData.from_file(str(SCOPED))
    (plane,) = [p for p in pd.planes if p.name == "/device:TPU:0"]
    (line,) = [x for x in plane.lines if x.name == "XLA Ops"]
    return [(trace.op_family(e.name), scopes.label(paths.get(e.name)),
             e.duration_ns / 1e9) for e in line.events]


@pytest.mark.parametrize("family", ["fused_lut_dense_kernel",
                                    "fused_lut_bwd_kernel"])
def test_every_kernel_op_is_in_a_layer_scope(scoped_ops, family):
    labels = {lab for fam, lab, _ in scoped_ops if fam == family}
    assert labels and {lab.split("/")[0] for lab in labels} == {
        "attn", "mlp", "lm_head"}


@pytest.mark.parametrize("scope,phases", [
    ("attn", {"forward", "recompute", "backward"}),
    ("mlp", {"forward", "recompute", "backward"}),
    ("lm_head", {"forward", "backward"}),
    ("optimizer", {"forward"}),
])
def test_scoped_phases(scoped, scope, phases):
    assert {k.split("/")[1] for k in scoped.device_s
            if k.split("/")[0] == scope} == phases


def test_scoped_buckets_partition_busy_time(scoped):
    assert sum(scoped.device_s.values()) == pytest.approx(scoped.busy_s,
                                                          rel=1e-9)
    assert scoped.scope_s(scopes.UNSCOPED) < 0.01 * scoped.busy_s


def test_step_host_time(scoped):
    # two steps inside the window, each shorter on the host than on the chip
    assert len(scoped.step_host_s) == 2
    assert all(0 < s < scoped.busy_s / 2 for s in scoped.step_host_s)


def test_gaps_are_named_by_trainer_spans(scoped):
    assert set(scoped.gaps) <= {"repro.train.step", "repro.train.draw",
                                "repro.train.wait"}
    assert sum(scoped.gaps.values()) == pytest.approx(
        scoped.window_s - scoped.busy_s, rel=1e-6)
