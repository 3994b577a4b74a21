"""The plain reference against the program at a CPU size, and the control
and planted faults against the cells' limits."""
import itertools

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import checks, model, reference, traffic
from perfbench.kinds import score, serve, train
from perfbench.tests.tiny import tiny_spec


def test_multiplier_matches_the_program_table():
    from repro.core.acu import make_acu
    spec = tiny_spec("smollm-135m", "qat-2x1024", "smollm-qat")
    mult = spec["config"]["multiplier"]
    ours = reference.multiplier_table(mult)
    acu = make_acu(mult["name"], mult["mode"])
    lut = np.asarray(acu.lut).reshape(256, 256)
    # the program's table is indexed by shifted codes a + offset
    assert acu.offset == 128
    np.testing.assert_array_equal(ours, lut)


def test_bit_plane_gemm_is_the_table_gemm():
    spec = tiny_spec("smollm-135m", "qat-2x1024", "smollm-qat")
    mult = spec["config"]["multiplier"]
    tab = reference.multiplier_table(mult)
    rng = np.random.default_rng(0)
    a = rng.integers(-128, 128, (9, 300))
    w = rng.integers(-128, 128, (300, 7))
    want = tab[a[:, :, None] + 128, w[None] + 128].sum(1)
    got = reference.multiplier_einsum(mult)(jnp.asarray(a, jnp.int32),
                                          jnp.asarray(w, jnp.int32))
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.fixture(scope="module")
def qat():
    return tiny_spec("smollm-135m", "qat-2x1024", "smollm-qat", seq_len=64)


def _ref_and(spec, seed, **kw):
    cfg, mix = spec["config"], spec["mix"]
    batches = list(itertools.islice(traffic.train_batches(mix, cfg["vocab_size"], seed), 3))
    p0 = model.init_weights(cfg, seed, jnp.float32)
    ref = reference.train_readings(cfg, mix["optimizer"], p0, batches, 3)
    other = reference.train_readings(cfg, mix["optimizer"], p0, batches, 3, **kw)
    return checks.train_numbers(other, ref)


def test_program_qat_agrees_with_reference(qat):
    ok, compared = checks.judge(train.readings(qat, 21, ["program"])["program"],
                                qat["limits"]["limits"])
    assert ok, compared


@pytest.mark.parametrize("kw", [{"dtype": jnp.bfloat16}, {"fault": "half_batch"}],
                         ids=["control", "half_batch"])
def test_qat_control_and_faults_fail(qat, kw):
    ok, compared = checks.judge(_ref_and(qat, 22, **kw), qat["limits"]["limits"])
    assert not ok, compared


def test_served_tokens_sit_closer_to_the_reference_than_the_control():
    """The serving path (no cell yet, see PERF.md): the program's greedy
    tokens lie closer to the reference's best than float8's do."""
    spec = tiny_spec("smollm-135m", "gen-decode", "smollm-eval",
                     slots=4, pool_tokens=1024, requests=40,
                     output={"kind": "loguniform", "lo": 4, "hi": 12})
    cell = serve.make(spec, 23)
    cell.setup()
    cell.window(8.0)
    cell.free()
    seqs = cell._sample()
    assert seqs
    prog = reference.served_gaps(cell.cfg, cell.params, 16, seqs, cell._padded(seqs))
    ctl = reference.served_gaps(cell.cfg, cell.params, 16, seqs, cell._padded(seqs),
                                other=spec["mix"]["control"])
    assert ctl.mean() > prog.mean()


def test_eval_control_fails():
    spec = tiny_spec("smollm-135m", "eval-2x1024", "smollm-eval", seq_len=64,
                     readings_seconds=1.0)
    numbers = score.readings(spec, 43, ["control"])["control"]
    ok, compared = checks.judge(numbers, spec["limits"]["limits"])
    assert not ok, compared
