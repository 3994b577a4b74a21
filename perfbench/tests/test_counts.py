"""The operation and byte counts against hand counts at smollm-135m widths."""
from perfbench import counts, model
from perfbench.common import BENCH_DIR, load_json

CFG = load_json(BENCH_DIR / "configs" / "smollm-135m.json")
V5E = counts.peaks("TPU v5 lite")


def test_params_hand_count():
    # per layer: q, o 576x576; k, v 576x192; gate, up, down 576x1536
    layer = 2 * 576 * 576 + 2 * 576 * 192 + 3 * 576 * 1536
    assert layer == 3_538_944
    assert model.n_params(CFG) == 30 * layer + 576 * 49152 == 134_479_872


def test_gemm_shapes_hand_count():
    shapes = model.gemm_shapes(CFG, 2048)
    assert len(shapes) == 30 * 7 + 1
    assert shapes[:7] == [(2048, 576, 576), (2048, 576, 192), (2048, 576, 192),
                          (2048, 576, 576), (2048, 576, 1536),
                          (2048, 576, 1536), (2048, 1536, 576)]
    assert shapes[-1] == (2048, 576, 49152)
    macs = sum(m * k * n for m, k, n in shapes)
    assert macs == 2048 * 134_479_872


def test_head_gemm_least_time_is_bandwidth_bound():
    ops, nbytes = counts.gemm_fwd(2048, 576, 49152, 4)
    assert ops == 2 * 2048 * 576 * 49152 == 115_964_116_992
    # f32 activations in, 1-byte codes, f32 logits out
    assert nbytes == 2048 * 576 * 4 + 576 * 49152 + 2048 * 49152 * 4 == 435_683_328
    assert counts.least_s(ops, nbytes, V5E) == nbytes / 819e9
    small = counts.gemm_fwd(2048, 576, 576, 4)
    assert counts.least_s(*small, V5E) == small[1] / 819e9


def test_backward_gemms_hand_count():
    (o1, b1), (o2, b2) = counts.gemm_bwd(2048, 576, 1536, 4)
    assert o1 == o2 == 2 * 2048 * 576 * 1536
    # dX = dY (2048x1536) W^T (1536x576) -> 2048x576; dW = X^T dY -> 576x1536
    assert b1 == 4 * (2048 * 1536 + 1536 * 576 + 2048 * 576)
    assert b2 == 4 * (576 * 2048 + 2048 * 1536 + 576 * 1536)


def test_unknown_device_is_an_error():
    import pytest
    from perfbench.common import BenchError
    with pytest.raises(BenchError):
        counts.peaks("TPU v9 imaginary")
