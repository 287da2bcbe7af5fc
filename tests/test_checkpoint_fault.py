"""Checkpointing (atomicity, roundtrip, GC) and fault-tolerant restart:
a run killed mid-training and restarted must reproduce the uninterrupted
run's loss trajectory exactly."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import configs
from repro.checkpoint import CheckpointManager, latest_step, restore, save
from repro.launch.mesh import make_local_mesh
from repro.launch.train import train_loop
from repro.runtime.fault import (FaultInjector, NodeFailure, RankDeath,
                                 run_with_restarts)

MESH = make_local_mesh(1, 1)


def _tree(seed=0):
    k = jax.random.key(seed)
    return {"a": jax.random.normal(k, (8, 16)),
            "b": {"c": jnp.arange(5, dtype=jnp.int32),
                  "d": jnp.float32(3.5)}}


def test_save_restore_roundtrip(tmp_path):
    tree = _tree()
    save(tmp_path, 7, tree)
    assert latest_step(tmp_path) == 7
    got = restore(tmp_path, 7, tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_atomic_no_partial_visible(tmp_path):
    """A tmp dir must never be picked up as a checkpoint."""
    (tmp_path / ".tmp_step_00000009").mkdir(parents=True)
    assert latest_step(tmp_path) is None
    save(tmp_path, 3, _tree())
    assert latest_step(tmp_path) == 3


def test_manager_gc_keeps_last(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2, async_write=False)
    for s in (1, 2, 3, 4):
        mgr.save(s, _tree(s))
    steps = sorted(int(p.name.split("_")[1]) for p in tmp_path.glob("step_*"))
    assert steps == [3, 4]
    restored, step = mgr.restore(_tree())
    assert step == 4


def test_structure_change_rejected(tmp_path):
    save(tmp_path, 1, _tree())
    bad = {"a": jnp.zeros((8, 16))}
    with pytest.raises(AssertionError):
        restore(tmp_path, 1, bad)


def test_latest_step_waits_for_interrupted_async_save(tmp_path, monkeypatch):
    """Regression: an async save still in flight when its manager is
    abandoned (the crash-restart path) must be visible to a FRESH reader —
    ``latest_step`` has to join the registered writer thread instead of
    returning None and silently replaying from step 0."""
    import time

    import repro.checkpoint.checkpoint as ckpt

    orig_save = ckpt.save

    def slow_save(*args, **kwargs):
        time.sleep(0.5)  # guarantee the reader races ahead of the rename
        return orig_save(*args, **kwargs)

    monkeypatch.setattr(ckpt, "save", slow_save)
    mgr = CheckpointManager(tmp_path, async_write=True)
    mgr.save(3, _tree())
    # simulate the crashed run: mgr is never wait()ed or used again
    assert latest_step(tmp_path) == 3
    fresh = CheckpointManager(tmp_path, async_write=True)
    assert fresh.latest() == 3
    restored, step = fresh.restore(_tree())
    assert step == 3


def test_restart_reproduces_uninterrupted_run(tmp_path):
    """Deterministic data + atomic checkpoints => restarted == straight run."""
    cfg = configs.get_smoke_config("tinyllama-1.1b")
    common = dict(steps=9, seq_len=32, global_batch=2, ckpt_every=3,
                  log_every=100, seed=0)

    # uninterrupted reference
    _, _, ref_losses = train_loop(cfg, MESH, ckpt_dir=str(tmp_path / "ref"),
                                  **common)

    # interrupted at step 5 (after the step-3 checkpoint), then restarted
    inj = FaultInjector(fail_at_steps=(5,))
    losses_parts = []

    def once():
        _, _, losses = train_loop(cfg, MESH, ckpt_dir=str(tmp_path / "ft"),
                                  fault=inj, **common)
        losses_parts.append(losses)

    stats = run_with_restarts(once)
    assert stats.completed and stats.restarts == 1
    # the restarted segment covers steps 3..8; compare overlap exactly
    restarted = losses_parts[-1]
    np.testing.assert_allclose(restarted, ref_losses[3:], rtol=1e-6)


def test_run_with_restarts_gives_up_after_max_restarts():
    """Satellite: the max_restarts-exceeded path — a driver that never
    stops failing must come back ``completed=False`` with the attempt
    count intact (max_restarts + 1 failures: the initial try plus one per
    allowed restart), not loop forever or raise out of the wrapper."""
    calls = []

    def always_fails():
        calls.append(1)
        raise NodeFailure("permanent")

    stats = run_with_restarts(always_fails, max_restarts=3)
    assert not stats.completed
    assert stats.restarts == 4          # gave up on the 4th failure
    assert len(calls) == 4              # initial attempt + 3 restarts
    assert stats.wall_s >= 0.0

    # max_restarts=0: one attempt, zero retries
    calls.clear()
    stats = run_with_restarts(always_fails, max_restarts=0)
    assert not stats.completed and len(calls) == 1 and stats.restarts == 1

    # RankDeath (async-harness total loss) rides the same policy
    def all_ranks_die():
        calls.append(1)
        raise RankDeath("every rank dead")

    calls.clear()
    stats = run_with_restarts(all_ranks_die, max_restarts=2)
    assert not stats.completed and len(calls) == 3


def test_run_with_restarts_honors_backoff(monkeypatch):
    """backoff_s sleeps between failures — but never after the final
    give-up failure, and never when backoff is zero."""
    import repro.runtime.fault as fault_mod

    naps = []
    monkeypatch.setattr(fault_mod.time, "sleep", lambda s: naps.append(s))

    attempts = []

    def fails_twice_then_succeeds():
        attempts.append(1)
        if len(attempts) < 3:
            raise NodeFailure("transient")

    stats = run_with_restarts(fails_twice_then_succeeds, max_restarts=5,
                              backoff_s=0.25)
    assert stats.completed and stats.restarts == 2
    assert naps == [0.25, 0.25]         # one nap per restart taken

    naps.clear()
    stats = run_with_restarts(lambda: (_ for _ in ()).throw(
        NodeFailure("permanent")), max_restarts=2, backoff_s=0.5)
    assert not stats.completed
    assert naps == [0.5, 0.5]           # no sleep after the give-up

    naps.clear()
    attempts.clear()
    run_with_restarts(fails_twice_then_succeeds, max_restarts=5)
    assert naps == []                   # backoff_s=0.0 never sleeps


def test_elastic_restore_other_mesh(tmp_path):
    """Same checkpoint restores under the shardings of another mesh's
    model (1x1 here)."""
    from repro.launch.steps import abstract_params
    from repro.models.layers import split_lp_tree
    from repro.models.model import build_model

    cfg = configs.get_smoke_config("smollm-360m")
    model = build_model(cfg, MESH)
    params, _ = split_lp_tree(model.init(jax.random.key(0)))
    save(tmp_path, 1, params)

    mesh2 = make_local_mesh(1, 1)
    model2 = build_model(cfg, mesh2)
    sds, sh = abstract_params(model2)
    got = restore(tmp_path, 1, sds, sh)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
