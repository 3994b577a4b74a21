"""OLMoE's counts against hand counts at published widths, and the tiny
program and control against the cell's limit."""
import copy

import pytest

from perfbench import checks, counts, olmoe
from perfbench.common import BENCH_DIR, load_json
from perfbench.kinds import score_moe

CFG = load_json(BENCH_DIR / "configs" / "olmoe-1b-7b.json")
TINY = {"hidden_size": 64, "intermediate_size": 32, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
        "num_experts": 8, "num_experts_per_tok": 2, "vocab_size": 512}


def test_active_params_hand_count():
    # per layer: q, k, v, o 2048 x 2048; router 2048 x 64; 8 routed
    # experts of gate, up 2048 x 1024 and down 1024 x 2048
    layer = 4 * 2048 * 2048 + 2048 * 64 + 8 * 3 * 2048 * 1024
    assert layer == 16_777_216 + 131_072 + 50_331_648
    assert olmoe.n_params(CFG) == 4 * layer + 2048 * 50304 == 371_982_336


def test_gemm_lists_hand_count():
    dense = olmoe.dense_shapes(CFG, 4096)
    assert len(dense) == 4 * 4 + 1
    assert dense[:4] == [(4096, 2048, 2048)] * 4
    assert dense[-1] == (4096, 2048, 50304)
    experts = olmoe.expert_shapes(CFG, 4096)
    assert experts[:3] == [(64, 512, 2048, 1024), (64, 512, 2048, 1024),
                           (64, 512, 1024, 2048)]
    assert len(experts) == 4 * 3
    macs = sum(m * k * n for m, k, n in dense) + \
        sum(e * m * k * n for e, m, k, n in experts)
    # every matrix product but the exact router
    assert macs == 4096 * (371_982_336 - 4 * 2048 * 64)


def test_grouped_work_hand_count():
    work = olmoe.expert_work(CFG, 4096, 2)
    assert len(work) == 4 * 3 * 64
    ops, nbytes = work[0]
    # 512 routed rows of bf16 activations, the expert's 1-byte codes once,
    # float32 outputs
    assert ops == 2 * 512 * 2048 * 1024
    assert nbytes == 512 * 2048 * 2 + 2048 * 1024 + 512 * 1024 * 4
    assert sum(o for o, _ in work) == 2 * 4 * 3 * 4096 * 8 * 2048 * 1024
    v5e = counts.peaks("TPU v5 lite")
    assert counts.least_s(ops, nbytes, v5e) == nbytes / 819e9


@pytest.fixture(scope="module")
def tiny_spec():
    cfg = copy.deepcopy(CFG)
    cfg.update(TINY)
    mix = load_json(BENCH_DIR / "mixes" / "eval-1x4096.json")
    mix.update(seq_len=64, readings_seconds=1.0)
    return {"cell": {"name": "olmoe-eval", "config": "olmoe-1b-7b",
                     "traffic": "eval-1x4096", "chips": 1},
            "config": cfg, "mix": mix,
            "limits": load_json(BENCH_DIR / "limits" / "olmoe-eval.json")}


def test_program_agrees_with_reference(tiny_spec):
    numbers = score_moe.readings(tiny_spec, 2**33 + 5, ["program"])["program"]
    ok, compared = checks.judge(numbers, tiny_spec["limits"]["limits"])
    assert ok, compared
    assert numbers["expert_load_max"] >= 1.0


def test_control_fails(tiny_spec):
    numbers = score_moe.readings(tiny_spec, 2**33 + 6, ["control"])["control"]
    ok, compared = checks.judge(numbers, tiny_spec["limits"]["limits"])
    assert not ok, compared
