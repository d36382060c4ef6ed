"""Checkpointing + fault-tolerance loop tests."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.data.pipeline import ShardedLoader
from repro.data import synthetic
from repro.dist import fault_tolerance as ft


def _tree(step):
    return {
        "a": jnp.arange(6, dtype=jnp.float32) + step,
        "nested": {"b": jnp.ones((3, 2)) * step, "c": jnp.asarray(step)},
    }


def test_save_restore_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_write=False)
    mgr.save(7, _tree(7))
    step, restored = mgr.restore(_tree(0))
    assert step == 7
    for a, b in zip(
        jax.tree_util.tree_leaves(restored), jax.tree_util.tree_leaves(_tree(7))
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_async_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_write=True)
    for s in (1, 2, 3):
        mgr.save(s, _tree(s))
    mgr.wait()
    assert mgr.latest_step() == 3


def test_gc_keeps_last_n(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_write=False)
    for s in range(5):
        mgr.save(s, _tree(s))
    assert mgr.all_steps() == [3, 4]


def test_tmp_debris_ignored(tmp_path):
    """A crashed (uncommitted) write must never be restored."""
    mgr = CheckpointManager(str(tmp_path), async_write=False)
    mgr.save(1, _tree(1))
    os.makedirs(tmp_path / "step_00000009.tmp")  # simulated crash debris
    assert mgr.latest_step() == 1
    step, _ = mgr.restore(_tree(0))
    assert step == 1


def test_tree_mismatch_rejected(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_write=False)
    mgr.save(1, _tree(1))
    with pytest.raises(AssertionError):
        mgr.restore({"different": jnp.zeros(3)})


# ------------------------------------------------------------ fault tolerance
def test_run_training_with_failures(tmp_path, capsys):
    """Injected crashes at steps 7 and 13 must not change the final result:
    restart from the last checkpoint reproduces the exact state (stateless
    data + deterministic step).  Each retried exception is printed."""
    mgr = CheckpointManager(str(tmp_path / "a"), async_write=False)

    def step_fn(state, batch):
        return {"x": state["x"] + batch["v"].sum(), "step": state["step"] + 1}

    def batch_at(step):
        return {"v": np.asarray([step, step], np.float32)}

    crashed = set()

    def injector(step):
        if step in (7, 13) and step not in crashed:
            crashed.add(step)
            raise RuntimeError(f"simulated node failure at {step}")

    init = {"x": jnp.zeros(()), "step": jnp.zeros((), jnp.int32)}
    cfg = ft.LoopConfig(checkpoint_every=5, max_restarts=5)
    final, stats = ft.run_training(
        step_fn, init, batch_at, mgr, num_steps=20, cfg=cfg,
        fail_injector=injector,
    )
    assert stats["restarts"] == 2
    err = capsys.readouterr().err
    assert "simulated node failure at 7" in err
    assert "simulated node failure at 13" in err
    # reference run without failures
    mgr2 = CheckpointManager(str(tmp_path / "b"), async_write=False)
    ref, _ = ft.run_training(step_fn, init, batch_at, mgr2, num_steps=20,
                             cfg=cfg)
    np.testing.assert_allclose(float(final["x"]), float(ref["x"]))
    assert int(final["step"]) == int(ref["step"]) == 20


def test_restart_budget_exceeded(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_write=False)

    def step_fn(state, batch):
        return state

    def injector(step):
        raise RuntimeError("always down")

    with pytest.raises(RuntimeError, match="restart budget"):
        ft.run_training(
            step_fn, {"x": jnp.zeros(())}, lambda s: {}, mgr, 5,
            ft.LoopConfig(max_restarts=2), fail_injector=injector,
        )


def test_straggler_monitor_remaps():
    cfg = ft.LoopConfig(straggler_factor=2.0, straggler_window=8)
    mon = ft.StragglerMonitor(num_shards=4, cfg=cfg)
    mon.spares = [99]
    for _ in range(8):
        for shard in range(4):
            mon.record(shard, 10.0 if shard == 2 else 1.0)
    assert mon.stragglers() == [2]
    remap = mon.mitigate()
    assert remap == {2: 99}


# ----------------------------------------------------------------------- data
def test_loader_deterministic_skip_ahead():
    """batch(step) must be derivable from (step, shard) alone -- the property
    the restart logic relies on."""
    mk = lambda step, shard, n: synthetic.token_batch(step, shard, n, 8, 100)
    a = ShardedLoader(mk, global_batch=8, num_shards=2, shard_id=0)
    b = ShardedLoader(mk, global_batch=8, num_shards=2, shard_id=0,
                      start_step=5)
    for _ in range(5):
        next(a)
    np.testing.assert_array_equal(next(a)["tokens"], next(b)["tokens"])


def test_loader_shards_differ():
    mk = lambda step, shard, n: synthetic.token_batch(step, shard, n, 8, 100)
    a = ShardedLoader(mk, 8, 2, 0)
    b = ShardedLoader(mk, 8, 2, 1)
    assert not np.array_equal(next(a)["tokens"], next(b)["tokens"])


def test_loader_prefetch():
    mk = lambda step, shard, n: synthetic.token_batch(step, shard, n, 4, 50)
    ld = ShardedLoader(mk, 4, 1, 0).start_prefetch()
    b0 = ld.next_prefetched()
    b1 = ld.next_prefetched()
    ld.stop()
    ref = synthetic.token_batch(0, 0, 4, 4, 50)
    np.testing.assert_array_equal(b0["tokens"], ref["tokens"])
    assert not np.array_equal(b0["tokens"], b1["tokens"])


def test_binary_dataset_shapes():
    for name, d in synthetic.TWENTY_DATASETS[:5]:
        x = synthetic.binary_dataset(name, 100)
        assert x.shape == (100, d)
        assert set(np.unique(x)) <= {0.0, 1.0}


def test_image_proxy_range():
    x = synthetic.gaussian_mixture_images(16, 8, 8, 3)
    assert x.shape == (16, 192)
    assert x.min() >= 0 and x.max() <= 1
