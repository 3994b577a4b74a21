"""A whole run past the harness's look for a chip, with the timed path
broken underneath: ``correct`` has to come out false for every fault the
cell can have, and true when nothing is broken."""
import jax
import jax.numpy as jnp
import pytest

from perfbench import run
from perfbench.common import find_cell
from perfbench.tests.tiny import tiny_spec

DEV = {"platform": "cpu", "kind": "cpu", "count": 1}


def _spec(workload, traffic, **over):
    spec = tiny_spec("smollm-135m", traffic, workload, **over)
    full = find_cell(workload)
    spec["end_to_end"], spec["per_layer"] = full["end_to_end"], full["per_layer"]
    return spec


def _copy(tree):
    return jax.tree.map(jnp.copy, tree)


# -- training: the fault wraps the trainer the cell builds --------------------

def state_unchanged(trainer):
    step = trainer._run_step

    def broken(params, opt_state, batch, n_micro):
        _, _, loss, stats = step(_copy(params), _copy(opt_state), batch, n_micro)
        return params, opt_state, loss, stats
    trainer._run_step = broken


def half_batch(trainer):
    loss = trainer.loss_fn
    trainer.loss_fn = lambda p, b: loss(
        p, {k: v[: v.shape[0] // 2] for k, v in b.items()})


QAT = dict(workload="smollm-qat", traffic="qat-2x1024", seq_len=64)


def test_qat_run_is_correct():
    r = run.execute(_spec(**QAT), 31, 1.0, False, DEV)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"qat_tokens_per_s", "setup_s"}


# a training step produces no token or answer of its own, and one chip
# exchanges nothing, so these two are the faults this cell can have
@pytest.mark.parametrize("fault", [state_unchanged, half_batch],
                         ids=lambda f: f.__name__)
def test_qat_fault_is_caught(fault):
    r = run.execute(_spec(**QAT), 32, 1.0, False, DEV, fault=fault)
    assert not r["correct"], r["checks"]


# -- scoring: the fault wraps the cell's jitted scoring program -------------

def half_batch_scored(cell):
    score = cell.score

    def broken(params, tokens, labels):
        half = tokens.shape[0] // 2
        nll = score(params, tokens[:half], labels[:half])
        return jnp.concatenate([nll, nll], axis=0)
    cell.score = broken


def answer_altered(cell):
    score = cell.score

    def broken(params, tokens, labels):
        nll = score(params, tokens, labels)
        return nll.at[0].set(jnp.roll(nll[0], 1))
    cell.score = broken


EVAL = dict(workload="smollm-eval", traffic="eval-2x1024", seq_len=64)


def test_eval_run_is_correct():
    r = run.execute(_spec(**EVAL), 41, 1.0, False, DEV)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"eval_tokens_per_s", "setup_s"}


# scoring keeps no state and one chip exchanges nothing: these two are the
# faults this cell can have
@pytest.mark.parametrize("fault", [half_batch_scored, answer_altered],
                         ids=lambda f: f.__name__)
def test_eval_fault_is_caught(fault):
    r = run.execute(_spec(**EVAL), 42, 1.0, False, DEV, fault=fault)
    assert not r["correct"], r["checks"]
