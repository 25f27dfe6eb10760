"""Checkpoint: roundtrip, atomicity (torn saves ignored), elastic remesh,
async saver, restore-into-different-dtype."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpoint as ckpt


def tree_example():
    return {
        "params": {"w": jnp.arange(12.0).reshape(3, 4),
                   "blocks": {"scale": jnp.ones((5,))}},
        "opt": {"step": jnp.asarray(7, jnp.int32),
                "m": {"w": jnp.zeros((3, 4))}},
    }


def test_roundtrip(tmp_path):
    t = tree_example()
    ckpt.save(t, tmp_path, 3)
    like = jax.tree.map(lambda a: jnp.zeros_like(a), t)
    restored, step = ckpt.restore(tmp_path, like=like)
    assert step == 3
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_latest_and_prune(tmp_path):
    t = tree_example()
    for s in (1, 2, 3, 4):
        ckpt.save(t, tmp_path, s)
    assert ckpt.latest_step(tmp_path) == 4
    ckpt.prune_old(tmp_path, keep=2)
    assert ckpt.latest_step(tmp_path) == 4
    assert not (tmp_path / "step_00000001").exists()


def test_torn_save_ignored(tmp_path):
    t = tree_example()
    ckpt.save(t, tmp_path, 1)
    # fake a torn save: directory without COMMIT
    torn = tmp_path / "step_00000002"
    torn.mkdir()
    (torn / "MANIFEST.json").write_text("{}")
    assert ckpt.latest_step(tmp_path) == 1


def test_async_saver(tmp_path):
    t = tree_example()
    s = ckpt.AsyncSaver()
    s.save(t, tmp_path, 5)
    s.wait()
    assert ckpt.latest_step(tmp_path) == 5


def test_elastic_remesh(tmp_path):
    """Save under mesh A (2 shards), restore under mesh B (1x... different
    spec) — on CPU we emulate with different PartitionSpecs on a 1-device
    mesh; the API path (shardings= tree) is identical on a pod."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh
    mesh_a = make_mesh((1,), ("data",))
    t = {"w": jnp.arange(16.0).reshape(4, 4)}
    sharded = jax.device_put(t["w"], NamedSharding(mesh_a, P("data", None)))
    ckpt.save({"w": sharded}, tmp_path, 1)

    mesh_b = make_mesh((1,), ("model",))
    like = {"w": jnp.zeros((4, 4))}
    shardings = {"w": NamedSharding(mesh_b, P(None, "model"))}
    restored, _ = ckpt.restore(tmp_path, like=like, shardings=shardings)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(t["w"]))
    assert restored["w"].sharding.spec == P(None, "model")


def test_dtype_cast_on_restore(tmp_path):
    t = {"w": jnp.arange(8.0, dtype=jnp.float32)}
    ckpt.save(t, tmp_path, 1)
    like = {"w": jnp.zeros((8,), jnp.bfloat16)}
    restored, _ = ckpt.restore(tmp_path, like=like)
    assert restored["w"].dtype == jnp.bfloat16


def test_missing_leaf_raises(tmp_path):
    ckpt.save({"a": jnp.ones(3)}, tmp_path, 1)
    with pytest.raises(KeyError):
        ckpt.restore(tmp_path, like={"b": jnp.ones(3)})
