"""Checkpointer: atomic commit, GC, mesh-agnostic restore, corruption
resistance."""

import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import auto_mesh
from repro.train.checkpoint import Checkpointer


def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"params": {"w": jax.random.normal(k, (4, 4)),
                       "b": jnp.zeros((4,))},
            "opt": {"step": jnp.int32(7)}}


def test_roundtrip(tmp_path):
    ck = Checkpointer(tmp_path)
    s = _state()
    ck.save(10, s)
    step, restored = ck.restore(jax.eval_shape(lambda: s))
    assert step == 10
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.asarray(s["params"]["w"]))
    assert int(restored["opt"]["step"]) == 7


def test_latest_and_gc(tmp_path):
    ck = Checkpointer(tmp_path, keep=2)
    for step in (1, 2, 3, 4):
        ck.save(step, _state(step))
    assert ck.latest_step() == 4
    assert ck.all_steps() == [3, 4]       # GC keeps last 2


def test_tmp_dirs_ignored(tmp_path):
    """A crash mid-write leaves only a .tmp dir — restore must skip it."""
    ck = Checkpointer(tmp_path)
    ck.save(5, _state())
    crash = tmp_path / "step_0000000009.tmp"
    crash.mkdir()
    (crash / "junk.npy").write_bytes(b"garbage")
    assert ck.latest_step() == 5


def test_restore_shape_mismatch_raises(tmp_path):
    ck = Checkpointer(tmp_path)
    ck.save(1, {"w": jnp.zeros((4,))})
    with pytest.raises(ValueError):
        ck.restore({"w": jnp.zeros((8,))})


def test_restore_missing_leaf_raises(tmp_path):
    ck = Checkpointer(tmp_path)
    ck.save(1, {"w": jnp.zeros((4,))})
    with pytest.raises(KeyError):
        ck.restore({"w": jnp.zeros((4,)), "extra": jnp.zeros((2,))})


def test_mesh_agnostic_restore(tmp_path):
    """Arrays are stored unsharded: restoring into a differently-sharded
    (here: differently-replicated) target works — the elastic-rescale
    contract."""
    ck = Checkpointer(tmp_path)
    s = {"w": jnp.arange(16.0).reshape(4, 4)}
    ck.save(3, s)
    mesh = auto_mesh((1,), ("data",))
    from jax.sharding import NamedSharding, PartitionSpec as P
    target = jax.device_put(jnp.zeros((4, 4)),
                            NamedSharding(mesh, P("data", None)))
    _, restored = ck.restore({"w": target})
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(s["w"]))
