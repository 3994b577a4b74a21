"""Checkpoint: roundtrip, retention, async, mesh-agnostic restore."""
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.train import checkpoint as C


def make_tree(key):
    return {"a": jax.random.normal(key, (4, 8)),
            "nested": {"b": jnp.arange(6).reshape(2, 3),
                       "c": (jnp.ones(3), jnp.zeros(()))}}


def test_roundtrip(tmp_path):
    tree = make_tree(jax.random.PRNGKey(0))
    C.save(str(tmp_path), 7, tree)
    assert C.latest_step(str(tmp_path)) == 7
    restored, man = C.restore(str(tmp_path), 7, tree)
    assert man["step"] == 7
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_retention(tmp_path):
    tree = {"x": jnp.ones(2)}
    for s in (1, 2, 3, 4, 5):
        C.save(str(tmp_path), s, tree, keep=2)
    dirs = sorted(os.listdir(tmp_path))
    assert dirs == ["step_00000004", "step_00000005"]


def test_async_saver(tmp_path):
    saver = C.AsyncSaver()
    tree = make_tree(jax.random.PRNGKey(1))
    saver.submit(str(tmp_path), 3, tree)
    saver.submit(str(tmp_path), 4, tree)   # supersedes queued older writes
    saver.wait()
    assert C.latest_step(str(tmp_path)) == 4


def test_async_saver_submit_drain_race(tmp_path):
    """Stress the submit/drain handoff: the drainer used to decide to exit
    (pending empty) while still reading as alive, so a submit landing in that
    window parked its snapshot in the pending slot with no thread to write it
    — ``wait()`` then returned with the newest step missing on disk. Many
    rapid submit/wait cycles make that window land reliably."""
    saver = C.AsyncSaver()
    tree = {"x": jnp.ones(2)}
    for step in range(1, 120):
        saver.submit(str(tmp_path), step, tree, keep=3)
        if step % 3 == 0:
            saver.wait()
            assert C.latest_step(str(tmp_path)) == step, step
    saver.wait()
    assert C.latest_step(str(tmp_path)) == 119
    assert saver.last_saved_step == 119


def test_restore_with_shardings(tmp_path):
    """Elastic restart: restore onto explicit (single-device) shardings."""
    tree = make_tree(jax.random.PRNGKey(2))
    C.save(str(tmp_path), 1, tree)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)
    restored, _ = C.restore(str(tmp_path), 1, tree, shardings=sh)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_atomicity_no_tmp_left(tmp_path):
    tree = {"x": jnp.ones(4)}
    C.save(str(tmp_path), 9, tree)
    assert not any(d.endswith(".tmp") for d in os.listdir(tmp_path))
