"""Checkpointing: roundtrip, atomicity, integrity, keep-k, async."""

import json
import os
import shutil

import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (
    CheckpointManager,
    latest_step,
    restore_checkpoint,
    save_checkpoint,
)


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "a": jnp.asarray(rng.standard_normal((4, 8)), jnp.float32),
        "nested": {"b": jnp.asarray(rng.integers(0, 5, (3,)), jnp.int32)},
        "scalar": jnp.asarray(7, jnp.int32),
    }


def test_roundtrip(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 3, t)
    restored = restore_checkpoint(str(tmp_path), 3, t)
    for a, b in zip(jax.tree_util.tree_leaves(t), jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


import jax  # noqa: E402  (used above via jax.tree_leaves)


def test_latest_skips_uncommitted(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 1, t)
    save_checkpoint(str(tmp_path), 2, t)
    # simulate a crash mid-save: step 3 exists without COMMITTED
    d = tmp_path / "step_00000003"
    shutil.copytree(tmp_path / "step_00000002", d)
    os.remove(d / "COMMITTED")
    assert latest_step(str(tmp_path)) == 2


def test_checksum_detects_corruption_any_codec(tmp_path):
    """Codec-independent integrity check: flip one byte of a leaf payload
    (re-compressing when the codec is zstd) and expect a checksum error."""
    t = _tree()
    save_checkpoint(str(tmp_path), 1, t)
    d = tmp_path / "step_00000001"
    target = sorted(p for p in os.listdir(d) if p.startswith("leaf_"))[0]
    with open(d / target, "rb") as f:
        payload = f.read()
    if target.endswith(".zst"):
        import zstandard

        data = bytearray(zstandard.ZstdDecompressor().decompress(payload))
        data[0] ^= 0xFF
        payload = zstandard.ZstdCompressor().compress(bytes(data))
    else:
        data = bytearray(payload)
        data[0] ^= 0xFF
        payload = bytes(data)
    with open(d / target, "wb") as f:
        f.write(payload)
    with pytest.raises(IOError, match="checksum"):
        restore_checkpoint(str(tmp_path), 1, t)


def test_structure_mismatch_rejected(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 1, t)
    bad = {"a": t["a"]}
    with pytest.raises(ValueError, match="leaves"):
        restore_checkpoint(str(tmp_path), 1, bad)


def test_manager_keep_k_and_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=True)
    t = _tree()
    for s in (1, 2, 3, 4):
        mgr.save(s, t)
    mgr.wait()
    mgr._gc()
    steps = sorted(
        int(n.split("_")[1]) for n in os.listdir(tmp_path) if n.startswith("step_")
    )
    assert steps == [3, 4]
    restored = mgr.restore(t)
    np.testing.assert_array_equal(np.asarray(restored["a"]), np.asarray(t["a"]))


def test_elastic_restore_new_sharding(tmp_path):
    """Restore applies target shardings (single device: placement noop,
    structure exercised; the 8-device elastic path runs in
    test_distributed_8dev.py)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    t = _tree()
    save_checkpoint(str(tmp_path), 5, t)
    mesh = jax.make_mesh((1,), ("data",))
    sh = jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), t)
    restored = restore_checkpoint(str(tmp_path), 5, t, shardings=sh)
    assert restored["a"].sharding.is_equivalent_to(NamedSharding(mesh, P()), 2)
