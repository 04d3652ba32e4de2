"""Checkpoint/restart, atomicity, async, elastic reshard."""
import os
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.train.checkpoint import (save_checkpoint, restore_checkpoint,
                                    latest_step, prune_old,
                                    AsyncCheckpointer)
from repro.train.elastic import reshard_tree


def _tree():
    return {"params": {"w": jnp.arange(12.0).reshape(3, 4),
                       "b": jnp.ones(4, jnp.bfloat16)},
            "opt": {"m": jnp.zeros((3, 4))},
            "step": jnp.asarray(7, jnp.int32)}


def test_roundtrip(tmp_path):
    d = str(tmp_path)
    save_checkpoint(d, 7, _tree(), {"note": "x"})
    tree, meta = restore_checkpoint(d)
    assert meta["step"] == 7 and meta["metadata"]["note"] == "x"
    np.testing.assert_array_equal(tree["params"]["w"],
                                  np.arange(12.0).reshape(3, 4))
    assert tree["params"]["b"].dtype == jnp.bfloat16


def test_latest_and_prune(tmp_path):
    d = str(tmp_path)
    for s in (5, 10, 15, 20):
        save_checkpoint(d, s, _tree())
    assert latest_step(d) == 20
    prune_old(d, keep=2)
    assert latest_step(d) == 20
    with pytest.raises(FileNotFoundError):
        restore_checkpoint(d, 5)


def test_atomicity_no_partial_dir_visible(tmp_path):
    d = str(tmp_path)
    save_checkpoint(d, 3, _tree())
    # a leftover tmp dir (simulated crash) must not be picked up
    os.makedirs(os.path.join(d, "step_00000009.tmp"))
    assert latest_step(d) == 3


def test_async_checkpointer(tmp_path):
    d = str(tmp_path)
    ck = AsyncCheckpointer(d, keep=2)
    for s in (1, 2, 3):
        ck.save(s, _tree())
    ck.wait()
    assert latest_step(d) == 3


def test_elastic_reshard_roundtrip(tmp_path):
    """Save, then restore onto a (trivially different) mesh layout."""
    from jax.sharding import PartitionSpec as P
    d = str(tmp_path)
    save_checkpoint(d, 1, _tree())
    mesh = jax.make_mesh((1,), ("data",))
    tree, _ = restore_checkpoint(d)
    specs = jax.tree_util.tree_map(lambda _: P(), tree)
    out = reshard_tree(tree, mesh, specs)
    np.testing.assert_array_equal(np.asarray(out["params"]["w"]),
                                  np.arange(12.0).reshape(3, 4))
