"""Fault-tolerance substrate: atomic checkpoints, rotation, elastic restore."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, load_pytree, save_pytree


def tree(v=1.0):
    return {"w": jnp.full((4, 2), v, jnp.bfloat16),
            "o": {"mu": jnp.full((4, 2), v / 2, jnp.float32)}}


def test_save_load_roundtrip(tmp_path):
    p = tmp_path / "x.npz"
    save_pytree(tree(3.0), p)
    out = load_pytree(p, tree())
    np.testing.assert_allclose(np.asarray(out["w"], np.float32), 3.0)


def test_manager_roundtrip_and_manifest(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=3)
    mgr.save(10, params=tree(1.0), opt_state={"c": jnp.int32(7)},
             extra={"round": 4, "policy_T": 3.5})
    step, params, opt, extra = mgr.restore(
        params_like=tree(), opt_state_like={"c": jnp.int32(0)})
    assert step == 10 and extra["round"] == 4
    np.testing.assert_allclose(np.asarray(params["w"], np.float32), 1.0)
    assert int(opt["c"]) == 7


def test_rotation_keeps_newest(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, params=tree(float(s)))
    assert mgr.all_steps() == [3, 4]
    step, params, _, _ = mgr.restore(params_like=tree())
    assert step == 4
    np.testing.assert_allclose(np.asarray(params["w"], np.float32), 4.0)


def test_restore_specific_step(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=5)
    for s in (1, 2):
        mgr.save(s, params=tree(float(s)))
    step, params, _, _ = mgr.restore(params_like=tree(), step=1)
    assert step == 1
    np.testing.assert_allclose(np.asarray(params["w"], np.float32), 1.0)


def test_no_partial_checkpoint_on_disk(tmp_path):
    """Atomic publish: no .tmp dirs left behind after a successful save."""
    mgr = CheckpointManager(tmp_path, keep=2)
    mgr.save(5, params=tree())
    assert not [p for p in tmp_path.iterdir() if p.name.startswith(".tmp")]


def test_elastic_restore_new_sharding(tmp_path):
    """Restore with an explicit (single-device) sharding tree -- the elastic
    re-shard path: checkpoint saved without mesh info, loaded onto whatever
    mesh is live."""
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, params=tree(2.0))
    from repro.launch.mesh import make_mesh  # Auto axes
    mesh = make_mesh((1,), ("data",))
    sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    shardings = jax.tree.map(lambda _: sh, tree())
    step, params, _, _ = mgr.restore(params_like=tree(), shardings=shardings)
    assert params["w"].sharding == sh


def test_restore_missing_raises(tmp_path):
    mgr = CheckpointManager(tmp_path)
    with pytest.raises(FileNotFoundError):
        mgr.restore(params_like=tree())


def test_overwrite_same_step_keeps_newest(tmp_path):
    """Re-saving a step (crash-retry of the same round) replaces it and the
    overwrite has NO crash window: at every point a loadable copy of the
    step exists as step_X or .old_step_X."""
    mgr = CheckpointManager(tmp_path, keep=3)
    mgr.save(7, params=tree(1.0))
    mgr.save(7, params=tree(2.0))
    assert mgr.all_steps() == [7]
    _, params, _, _ = mgr.restore(params_like=tree())
    np.testing.assert_allclose(np.asarray(params["w"], np.float32), 2.0)
    # no rename-aside garbage after a clean overwrite
    assert not [p for p in tmp_path.iterdir() if p.name.startswith(".old")]


def test_recover_interrupted_overwrite(tmp_path):
    """Simulate a crash BETWEEN un-publish and re-publish: step_X has been
    renamed aside to .old_step_X and the new copy never landed.  A fresh
    manager must restore the old copy -- the previous rmtree-then-replace
    save() lost the checkpoint in exactly this window."""
    import os

    mgr = CheckpointManager(tmp_path, keep=3)
    ckpt = mgr.save(3, params=tree(5.0))
    os.replace(ckpt, tmp_path / ".old_step_0000000003")  # crash mid-overwrite
    assert CheckpointManager(tmp_path).all_steps() == [3]
    _, params, _, _ = CheckpointManager(tmp_path).restore(params_like=tree())
    np.testing.assert_allclose(np.asarray(params["w"], np.float32), 5.0)


def test_recover_discards_stale_leftovers(tmp_path):
    """A .old with a published sibling (crash after publish) and stale .tmp
    dirs are garbage: _recover deletes both, keeping the published copy."""
    import shutil

    mgr = CheckpointManager(tmp_path, keep=3)
    ckpt = mgr.save(3, params=tree(9.0))
    shutil.copytree(ckpt, tmp_path / ".old_step_0000000003")
    (tmp_path / ".tmp_step_0000000004").mkdir()
    mgr2 = CheckpointManager(tmp_path)
    assert mgr2.all_steps() == [3]
    leftovers = [p.name for p in tmp_path.iterdir()
                 if p.name.startswith((".old", ".tmp"))]
    assert leftovers == []
    _, params, _, _ = mgr2.restore(params_like=tree())
    np.testing.assert_allclose(np.asarray(params["w"], np.float32), 9.0)
