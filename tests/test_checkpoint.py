"""Checkpoint fault-tolerance: atomicity, corruption fallback, elasticity,
async writes, lossy-restore training continuity."""
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt as C
from repro.configs import get_arch
from repro.data.synthetic import DataConfig, batch_for_step
from repro.launch.train import (TrainConfig, init_state, jit_train_step,
                                make_plan_for)
from repro.runtime.sharding import ShardingPlan

PLAN = ShardingPlan(mesh=None)


@pytest.fixture()
def tmp_ckpt(tmp_path):
    return str(tmp_path / "ckpt")


def _state():
    cfg = get_arch("glm4-9b").reduced()
    return cfg, init_state(jax.random.key(0), cfg, TrainConfig(), PLAN)


def test_save_restore_within_bound(tmp_ckpt):
    cfg, state = _state()
    C.save_checkpoint(tmp_ckpt, state, step=5)
    restored, meta = C.restore_checkpoint(tmp_ckpt)
    assert meta["step"] == 5
    for a, b in zip(jax.tree.leaves(state["params"]),
                    jax.tree.leaves(restored["params"])):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        vr = max(a.max() - a.min(), 1e-9)
        assert np.abs(a - b).max() <= 5e-4 * vr * (1 + 1e-6)


def test_raw_mode_bit_exact(tmp_ckpt):
    cfg, state = _state()
    C.save_checkpoint(tmp_ckpt, state, step=1,
                      cfg=C.CheckpointConfig(mode="raw"))
    restored, _ = C.restore_checkpoint(tmp_ckpt,
                                       cfg=C.CheckpointConfig(mode="raw"))
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_corruption_falls_back(tmp_ckpt):
    cfg, state = _state()
    C.save_checkpoint(tmp_ckpt, state, step=1)
    C.save_checkpoint(tmp_ckpt, state, step=2)
    stream = os.path.join(tmp_ckpt, "step_00000002", C.LEAVES_STREAM)
    with open(stream, "r+b") as f:
        f.seek(os.path.getsize(stream) // 2)
        f.write(b"corrupted")                  # flips payload bytes mid-leaf
    restored, meta = C.restore_checkpoint(tmp_ckpt)
    assert meta["step"] == 1


def test_interrupted_write_invisible(tmp_ckpt):
    """A partial tmp dir must never be picked up."""
    cfg, state = _state()
    C.save_checkpoint(tmp_ckpt, state, step=1)
    os.makedirs(os.path.join(tmp_ckpt, ".tmp_step_9_partial"))
    steps = C.available_steps(tmp_ckpt)
    assert steps == [1]


def test_async_save(tmp_ckpt):
    cfg, state = _state()
    C.save_checkpoint(tmp_ckpt, state, step=7, background=True)
    C.wait_for_pending()
    restored, meta = C.restore_checkpoint(tmp_ckpt)
    assert meta["step"] == 7


def test_training_continues_after_lossy_restore(tmp_ckpt):
    """The restored (lossily compressed) state trains without blowup."""
    cfg, state = _state()
    dc = DataConfig(vocab_size=cfg.vocab_size, global_batch=4, seq_len=32)
    tc = TrainConfig()
    b0 = {k: jnp.asarray(v) for k, v in batch_for_step(dc, 0).items()}
    step = jit_train_step(cfg, tc, PLAN, state, b0)
    for i in range(3):
        b = {k: jnp.asarray(v) for k, v in batch_for_step(dc, i).items()}
        state, m = step(state, b)
    loss_before = float(m["loss"])
    C.save_checkpoint(tmp_ckpt, state, step=3)
    restored, _ = C.restore_checkpoint(tmp_ckpt)
    state2 = jax.tree.map(jnp.asarray, restored)
    for i in range(3, 6):
        b = {k: jnp.asarray(v) for k, v in batch_for_step(dc, i).items()}
        state2, m2 = step(state2, b)
    assert np.isfinite(float(m2["loss"]))
    assert float(m2["loss"]) < loss_before * 1.5


def test_bad_manifest_falls_back(tmp_ckpt):
    cfg, state = _state()
    C.save_checkpoint(tmp_ckpt, state, step=1)
    C.save_checkpoint(tmp_ckpt, state, step=2)
    with open(os.path.join(tmp_ckpt, "step_00000002", "manifest.json"),
              "w") as f:
        f.write("{not json")
    restored, meta = C.restore_checkpoint(tmp_ckpt)
    assert meta["step"] == 1


def test_non_corruption_error_propagates(tmp_ckpt, monkeypatch):
    """A decode failure that is not corruption (a device or compile
    error) must surface, not read as a bad checkpoint."""
    cfg, state = _state()
    C.save_checkpoint(tmp_ckpt, state, step=1)

    def broken(self, comps):
        raise RuntimeError("device program failed to compile")

    monkeypatch.setattr(C.CEAZ, "decompress_batch", broken)
    with pytest.raises(RuntimeError, match="failed to compile"):
        C.restore_checkpoint(tmp_ckpt)
