"""Checkpoint tests: atomicity, retention, restore-into-structure."""
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import CheckpointManager, restore_checkpoint, save_checkpoint


def _tree():
    return {"a": jnp.arange(6.0).reshape(2, 3),
            "n": {"b": jnp.ones((4,), jnp.int32)}}


def test_save_restore_roundtrip(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 3, t, {"note": "x"})
    out, step, meta = restore_checkpoint(str(tmp_path), t)
    assert step == 3 and meta["note"] == "x"
    np.testing.assert_array_equal(out["a"], t["a"])
    np.testing.assert_array_equal(out["n"]["b"], t["n"]["b"])


def test_retention_keeps_last_k(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in range(5):
        mgr.save(s, _tree())
    files = sorted(f for f in os.listdir(tmp_path) if f.startswith("ckpt"))
    assert len(files) == 2 and mgr.latest_step() == 4


def test_shape_mismatch_is_loud(tmp_path):
    save_checkpoint(str(tmp_path), 0, _tree())
    bad = {"a": jnp.zeros((3, 3)), "n": {"b": jnp.ones((4,), jnp.int32)}}
    with pytest.raises(ValueError):
        restore_checkpoint(str(tmp_path), bad)

