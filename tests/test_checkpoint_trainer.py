"""SEARS checkpointing + trainer fault-tolerance integration tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointError, SEARSCheckpointManager
from repro.configs.base import get_config
from repro.data.pipeline import DataConfig
from repro.train.optimizer import AdamWConfig
from repro.train.step import TrainStepConfig
from repro.train.trainer import Trainer, TrainerConfig


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    ks = jax.random.split(k, 3)
    return {
        "w": jax.random.normal(ks[0], (64, 128), jnp.float32),
        "emb": jax.random.normal(ks[1], (1000, 32)).astype(jnp.bfloat16),
        "nested": {"b": jax.random.normal(ks[2], (7,), jnp.float32),
                   "step": jnp.int32(3)},
    }


def _assert_tree_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


def test_checkpoint_roundtrip():
    mgr = SEARSCheckpointManager(node_capacity=1 << 26)
    tree = _tree()
    mgr.save(10, tree)
    out = mgr.restore(jax.eval_shape(lambda: tree))
    _assert_tree_equal(tree, out)


def test_checkpoint_dedup_across_steps():
    """Identical leaves between steps are stored once (incremental ckpt)."""
    mgr = SEARSCheckpointManager(node_capacity=1 << 26)
    tree = _tree()
    s1 = mgr.save(1, tree)
    s2 = mgr.save(2, tree)  # unchanged state
    assert s1["bytes_after_dedup"] > 0
    assert s2["bytes_after_dedup"] == 0  # fully deduped
    assert s2["dedup_saving"] == 1.0


def test_checkpoint_dedup_across_experiments():
    """A second run that shares a frozen leaf with the first stores that
    leaf's bytes once: only its own leaves upload."""
    mgr = SEARSCheckpointManager(node_capacity=1 << 26, run="a")
    tree = _tree()
    mgr.save(1, tree)
    mgr2 = SEARSCheckpointManager(store=mgr.store, run="b")
    tree2 = _tree(seed=1)
    tree2["emb"] = tree["emb"]  # shared frozen embedding
    s = mgr2.save(1, tree2)
    assert s["dedup_saving"] >= 0.1
    assert s["bytes_after_dedup"] <= s["bytes"] - tree["emb"].nbytes


def test_checkpoint_partial_change_partial_dedup():
    mgr = SEARSCheckpointManager(node_capacity=1 << 26)
    tree = _tree()
    mgr.save(1, tree)
    tree2 = dict(tree)
    tree2["nested"] = {"b": tree["nested"]["b"] + 1.0,
                       "step": jnp.int32(4)}
    s2 = mgr.save(2, tree2)
    # only the small changed leaves re-upload
    assert s2["bytes_after_dedup"] < 0.02 * s2["bytes"]


def test_checkpoint_survives_node_failures():
    mgr = SEARSCheckpointManager(node_capacity=1 << 26)
    tree = _tree()
    mgr.save(5, tree)
    for cluster in mgr.store.clusters:
        cluster.kill_nodes([0, 3, 5, 7, 9])  # n-k = 5 failures per cluster
    out = mgr.restore(jax.eval_shape(lambda: tree))
    _assert_tree_equal(tree, out)


def test_checkpoint_data_loss_detected():
    mgr = SEARSCheckpointManager(node_capacity=1 << 26)
    tree = _tree()
    mgr.save(5, tree)
    used = [c for c in mgr.store.clusters if c.used > 0]
    for cluster in used:
        cluster.kill_nodes(list(range(6)))  # > n-k failures
    with pytest.raises(CheckpointError):
        mgr.restore(jax.eval_shape(lambda: tree))


def test_checkpoint_gc_keeps_last():
    mgr = SEARSCheckpointManager(node_capacity=1 << 26, keep_last=2)
    tree = _tree()
    for s in (1, 2, 3, 4):
        mgr.save(s, tree)
    assert mgr.steps() == [3, 4]
    files = mgr.store.switching["trainer"].table
    assert not any("/00000001/" in f for f in files)


# ------------------------------------------------------------- trainer -----
def _trainer(manager=None, total=6, **kw):
    cfg = get_config("llama32_1b").reduced()
    dcfg = DataConfig(seq_len=32, global_batch=4, vocab_size=cfg.vocab_size)
    tcfg = TrainerConfig(
        total_steps=total, ckpt_every=3, seed=0,
        step_cfg=TrainStepConfig(
            microbatches=kw.pop("microbatches", 1), remat=False,
            adamw=AdamWConfig(lr=1e-3,
                              moment_dtype=kw.pop("moment_dtype", "fp32"))))
    return Trainer(cfg, dcfg, tcfg, manager=manager)


def test_trainer_runs_and_loss_decreases():
    tr = _trainer(total=6)
    metrics = tr.run()
    losses = [m["loss"] for m in metrics if "loss" in m]
    assert len(losses) == 6
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_trainer_crash_restart_is_deterministic():
    """Crash after step 3 + restore == uninterrupted run (bitwise-ish)."""
    mgr_a = SEARSCheckpointManager(node_capacity=1 << 28, run="a")
    tr_a = _trainer(manager=mgr_a, total=6)
    tr_a.run()
    ref_params = tr_a.final_state[0]

    mgr_b = SEARSCheckpointManager(node_capacity=1 << 28, run="b")
    tr_b1 = _trainer(manager=mgr_b, total=3)
    tr_b1.run()  # "crashes" after step 3 (checkpoint written there)
    del tr_b1
    # storage nodes fail between crash and restart
    for cluster in mgr_b.store.clusters:
        cluster.kill_nodes([1, 4, 6])
    tr_b2 = _trainer(manager=mgr_b, total=6)
    metrics = tr_b2.run()
    assert metrics[0]["step"] == 4  # resumed, not restarted
    got = tr_b2.final_state[0]
    for x, y in zip(jax.tree.leaves(ref_params), jax.tree.leaves(got)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   rtol=1e-5, atol=1e-5)


def test_trainer_microbatch_equivalence():
    """2 microbatches == 1 big batch (same grads up to accumulation fp)."""
    tr1 = _trainer(total=2, microbatches=1,
                   manager=SEARSCheckpointManager(node_capacity=1 << 28,
                                                  run="m1"))
    tr2 = _trainer(total=2, microbatches=2,
                   manager=SEARSCheckpointManager(node_capacity=1 << 28,
                                                  run="m2"))
    m1, m2 = tr1.run(), tr2.run()
    l1 = [m["loss"] for m in m1 if "loss" in m]
    l2 = [m["loss"] for m in m2 if "loss" in m]
    np.testing.assert_allclose(l1, l2, rtol=2e-2)


def test_trainer_int8_moments():
    tr = _trainer(total=4, moment_dtype="int8")
    metrics = tr.run()
    losses = [m["loss"] for m in metrics if "loss" in m]
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0] + 0.1


def test_elastic_restore_reshard():
    """Checkpoint written on 1x1 mesh restores under different shardings."""
    mgr = SEARSCheckpointManager(node_capacity=1 << 28, run="el")
    tr = _trainer(manager=mgr, total=3)
    tr.run()
    # new trainer, fresh mesh/rules (same devices; shardings rebuilt)
    tr2 = _trainer(manager=mgr, total=3)
    (params, opt_state), start = tr2.restore_or_init()
    assert start == 3
    for leaf in jax.tree.leaves(params):
        assert np.all(np.isfinite(np.asarray(leaf, np.float32)))
