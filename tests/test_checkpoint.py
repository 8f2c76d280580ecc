"""Checkpointing: roundtrip, atomicity, keep-N, async, elastic reshard."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "a": jnp.asarray(rng.normal(size=(8, 16)), jnp.float32),
        "nested": {"b": jnp.asarray(rng.integers(0, 10, (4,)), jnp.int32)},
        "scalar": jnp.asarray(3, jnp.int32),
    }


def _assert_tree_equal(a, b):
    flat_a = jax.tree_util.tree_leaves(a)
    flat_b = jax.tree_util.tree_leaves(b)
    for x, y in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    t = _tree()
    mgr.save(10, t)
    restored, manifest = mgr.restore(target=t)
    _assert_tree_equal(t, restored)
    assert manifest["step"] == 10


def test_async_save_and_wait(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    t = _tree(1)
    mgr.save(5, t, blocking=False)
    mgr.wait()
    restored, _ = mgr.restore(5, target=t)
    _assert_tree_equal(t, restored)


def test_keep_n_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, _tree(s))
    assert mgr.steps() == [3, 4]


def test_atomicity_ignores_partial(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=5)
    mgr.save(1, _tree(1))
    # simulate a crashed writer: orphan tmp dir with garbage
    os.makedirs(tmp_path / "step_000000002.tmp")
    (tmp_path / "step_000000002.tmp" / "junk").write_text("x")
    assert mgr.latest_step() == 1  # tmp is invisible
    restored, _ = mgr.restore(target=_tree(1))
    _assert_tree_equal(_tree(1), restored)
    mgr.save(3, _tree(3))  # next save prunes orphans
    assert not any(n.endswith(".tmp") for n in os.listdir(tmp_path))


def test_restore_latest_and_missing(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    with pytest.raises(FileNotFoundError):
        mgr.restore()
    mgr.save(7, _tree(7))
    mgr.save(9, _tree(9))
    restored, m = mgr.restore(target=_tree(0))
    assert m["step"] == 9
    _assert_tree_equal(_tree(9), restored)


def test_elastic_reshard_subprocess(subproc):
    """Save under a (4,1) mesh, restore onto (2,2) — different topology."""
    subproc(
        """
        from repro.launch.mesh import make_mesh
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import CheckpointManager

        d = tempfile.mkdtemp()
        mesh1 = make_mesh((4, 1), ("data", "model"))
        x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
        xs = jax.device_put(x, NamedSharding(mesh1, P("data", None)))
        mgr = CheckpointManager(d)
        mgr.save(1, {"x": xs})

        mesh2 = make_mesh((2, 2), ("data", "model"))
        sh2 = {"x": NamedSharding(mesh2, P("data", "model"))}
        restored, _ = mgr.restore(target={"x": x}, shardings=sh2)
        np.testing.assert_array_equal(np.asarray(restored["x"]), np.asarray(x))
        assert restored["x"].sharding.mesh.shape["model"] == 2
        print("elastic reshard OK")
        """,
        n_devices=4,
    )
