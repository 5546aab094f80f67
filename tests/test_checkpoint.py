"""Checkpointing: atomicity, manifests, restore, resharding restore."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpoint import (CheckpointCorrupt, latest_step,
                                         list_steps, restore_checkpoint,
                                         save_checkpoint)


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {"params": {"w": jnp.asarray(rng.standard_normal((4, 4)),
                                        jnp.float32),
                       "b": jnp.asarray(rng.standard_normal(4), jnp.float32)},
            "opt": {"m": jnp.zeros((4, 4), jnp.float32)},
            "step": jnp.asarray(7, jnp.int32)}


def test_save_restore_roundtrip(tmp_path):
    tree = _tree()
    path = save_checkpoint(str(tmp_path), 7, tree, metadata={"note": "x"})
    assert os.path.isdir(path)
    restored, meta = restore_checkpoint(str(tmp_path), 7, tree)
    assert meta == {"note": "x"}
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_latest_step_and_list(tmp_path):
    for s in (3, 10, 5):
        save_checkpoint(str(tmp_path), s, _tree(s))
    assert list_steps(str(tmp_path)) == [3, 5, 10]
    assert latest_step(str(tmp_path)) == 10


def test_partial_write_is_invisible(tmp_path):
    """A directory without MANIFEST (crashed save) is ignored."""
    save_checkpoint(str(tmp_path), 1, _tree())
    bad = tmp_path / "step_0000000002"
    bad.mkdir()
    (bad / "arrays.npz").write_bytes(b"garbage")
    assert latest_step(str(tmp_path)) == 1


def test_overwrite_same_step(tmp_path):
    t1, t2 = _tree(1), _tree(2)
    save_checkpoint(str(tmp_path), 4, t1)
    save_checkpoint(str(tmp_path), 4, t2)
    restored, _ = restore_checkpoint(str(tmp_path), 4, t2)
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.asarray(t2["params"]["w"]))


def test_shape_mismatch_raises(tmp_path):
    save_checkpoint(str(tmp_path), 1, _tree())
    bad_template = _tree()
    bad_template["params"]["w"] = jnp.zeros((2, 2), jnp.float32)
    with pytest.raises(ValueError, match="shape mismatch"):
        restore_checkpoint(str(tmp_path), 1, bad_template)


def test_missing_leaf_raises(tmp_path):
    save_checkpoint(str(tmp_path), 1, {"a": jnp.zeros(2)})
    with pytest.raises(KeyError):
        restore_checkpoint(str(tmp_path), 1, {"a": jnp.zeros(2),
                                              "b": jnp.zeros(2)})


def test_restore_with_sharding_placement(tmp_path):
    """Restore accepts NamedSharding for the current (here 1-device) mesh —
    the elastic-resize path."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import AxisType
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    tree = {"w": jnp.ones((8, 2), jnp.float32)}
    save_checkpoint(str(tmp_path), 2, tree)
    sh = {"w": NamedSharding(mesh, P("data", None))}
    restored, _ = restore_checkpoint(str(tmp_path), 2, tree, shardings=sh)
    assert restored["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))


def test_manifest_contents(tmp_path):
    save_checkpoint(str(tmp_path), 9, _tree(), metadata={"cfg": "smollm"})
    with open(tmp_path / "step_0000000009" / "MANIFEST.json") as f:
        man = json.load(f)
    assert man["step"] == 9
    assert man["metadata"]["cfg"] == "smollm"
    assert man["keys"]["params/w"]["shape"] == [4, 4]
    assert isinstance(man["keys"]["params/w"]["crc32"], int)


# ---------------------------------------------------------------------------
# Self-verification: per-array checksums, named CheckpointCorrupt
# ---------------------------------------------------------------------------

def test_flipped_payload_bytes_raise_checkpoint_corrupt(tmp_path):
    """Silent bit-rot in arrays.npz is caught by the manifest crc32 —
    restore raises the named ``CheckpointCorrupt``, never returns a
    garbage tree."""
    tree = _tree()
    path = save_checkpoint(str(tmp_path), 1, tree)
    npz = os.path.join(path, "arrays.npz")
    data = bytearray(open(npz, "rb").read())
    # flip bytes deep in the compressed payload, leaving the zip
    # container parseable (the interesting failure mode: npz loads,
    # values are wrong)
    for off in range(len(data) // 2, len(data) // 2 + 8):
        data[off] ^= 0xFF
    with open(npz, "wb") as f:
        f.write(bytes(data))
    with pytest.raises(CheckpointCorrupt):
        restore_checkpoint(str(tmp_path), 1, tree)


def test_truncated_payload_raises_checkpoint_corrupt(tmp_path):
    tree = _tree()
    path = save_checkpoint(str(tmp_path), 1, tree)
    npz = os.path.join(path, "arrays.npz")
    data = open(npz, "rb").read()
    with open(npz, "wb") as f:
        f.write(data[: len(data) // 3])
    with pytest.raises(CheckpointCorrupt, match="unreadable|crc32"):
        restore_checkpoint(str(tmp_path), 1, tree)


def test_manifest_listed_array_missing_from_payload(tmp_path):
    tree = {"a": jnp.zeros(3), "b": jnp.ones(3)}
    path = save_checkpoint(str(tmp_path), 2, tree)
    man_path = os.path.join(path, "MANIFEST.json")
    with open(man_path) as f:
        man = json.load(f)
    man["keys"]["ghost"] = {"shape": [3], "dtype": "float64", "crc32": 0}
    with open(man_path, "w") as f:
        json.dump(man, f)
    with pytest.raises(CheckpointCorrupt, match="ghost"):
        restore_checkpoint(str(tmp_path), 2, tree)


def test_pre_checksum_manifest_restores_unverified(tmp_path):
    """Manifests written before per-array checksums (no ``crc32`` key)
    still restore — verification is skipped, not failed."""
    tree = _tree()
    path = save_checkpoint(str(tmp_path), 3, tree)
    man_path = os.path.join(path, "MANIFEST.json")
    with open(man_path) as f:
        man = json.load(f)
    for info in man["keys"].values():
        del info["crc32"]
    with open(man_path, "w") as f:
        json.dump(man, f)
    restored, _ = restore_checkpoint(str(tmp_path), 3, tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
