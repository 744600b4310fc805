"""Checkpoint protocol: atomicity, integrity, retention, elastic restore."""
from __future__ import annotations

import json
import os
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.train import checkpoint as ckpt

jax.config.update("jax_platform_name", "cpu")


def small_state(seed=0):
    k = jax.random.key(seed)
    return {
        "params": {"w": jax.random.normal(k, (8, 16)),
                   "b": jnp.zeros((16,))},
        "opt": {"m": jnp.ones((8, 16)) * 0.5},
        "step": jnp.asarray(7, jnp.int32),
    }


def test_save_restore_roundtrip(tmp_path):
    state = small_state()
    ckpt.save(tmp_path, 7, state)
    step, restored = ckpt.restore(tmp_path)
    assert step == 7
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        state, restored)


def test_latest_step_and_retention(tmp_path):
    state = small_state()
    for s in (1, 2, 3, 4, 5):
        ckpt.save(tmp_path, s, state, keep_n=2)
    assert ckpt.latest_step(tmp_path) == 5
    kept = sorted(d.name for d in Path(tmp_path).iterdir())
    assert kept == ["step_0000000004", "step_0000000005"]


def test_atomicity_orphan_tmp_ignored(tmp_path):
    """A crashed writer leaves step_N.tmp; restore must ignore it."""
    state = small_state()
    ckpt.save(tmp_path, 3, state)
    # simulate a crash mid-write of step 4
    orphan = Path(tmp_path) / "step_0000000004.tmp"
    orphan.mkdir()
    (orphan / "garbage").write_text("crash")
    assert ckpt.latest_step(tmp_path) == 3
    step, _ = ckpt.restore(tmp_path)
    assert step == 3


def test_crc_detects_corruption(tmp_path):
    """The SEU-in-storage threat model: a flipped bit must be caught."""
    state = small_state()
    d = ckpt.save(tmp_path, 1, state)
    shards = d / "shards.npz"
    raw = bytearray(shards.read_bytes())
    raw[len(raw) // 2] ^= 0x40          # flip one bit mid-file
    shards.write_bytes(bytes(raw))
    with pytest.raises((IOError, ValueError, Exception)):
        ckpt.restore(tmp_path, 1)


def test_elastic_restore_new_mesh(tmp_path):
    """Save under a (2,1) mesh layout, restore onto (1,2) — elastic restart."""
    from jax.sharding import PartitionSpec as P
    state = small_state()
    specs = {"params": {"w": P("data", "model"), "b": P("model")},
             "opt": {"m": P("data", "model")}, "step": P()}
    mesh_a = make_mesh((1, 1), ("data", "model"))
    ckpt.save(tmp_path, 5, state, specs=specs)
    mesh_b = make_mesh((1, 1), ("data", "model"))
    step, restored = ckpt.restore(tmp_path, 5, mesh=mesh_b, specs=specs)
    assert step == 5
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.asarray(state["params"]["w"]))


def test_restore_missing_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        ckpt.restore(tmp_path / "nope")


# ---------------------------- property tests --------------------------------

from hypothesis import given, settings, strategies as st


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 5), st.integers(1, 8), st.integers(0, 2**31 - 1))
def test_checkpoint_roundtrip_property(depth, width, seed):
    """Arbitrary nested pytrees of arbitrary-shape arrays survive
    save→restore bit-exactly (crc verified on the way back in)."""
    import numpy as _np
    import tempfile
    rng = _np.random.default_rng(seed)

    def make(d):
        if d == 0:
            shape = tuple(int(x) for x in rng.integers(1, 5, rng.integers(0, 3)))
            dt = rng.choice([_np.float32, _np.int32, _np.float64])
            return (rng.standard_normal(shape) * 10).astype(dt)
        return {f"k{i}": make(d - 1) for i in range(min(width, 3))}

    state = {"tree": make(depth % 3), "step": _np.int64(seed)}
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 1, state)
        _, restored = ckpt.restore(d)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)), state, restored)


# ------------------- incremental + async checkpointing ----------------------


def _mutate(state, r=-1.0):
    out = jax.tree_util.tree_map(lambda x: x, state)
    out["params"]["w"] = state["params"]["w"].at[0, 0].set(r)
    return out


def test_incremental_restore_bit_identical_to_full(tmp_path):
    """Acceptance: a chained incremental checkpoint restores bit-identically
    to a full checkpoint of the same state."""
    state = small_state()
    state2 = _mutate(state)
    inc_dir, full_dir = tmp_path / "inc", tmp_path / "full"
    with ckpt.IncrementalCheckpointer(inc_dir, async_write=False) as c:
        c.save(1, state)
        c.save(2, state2)
    ckpt.save(full_dir, 2, state2)
    s_inc, r_inc = ckpt.restore(inc_dir)
    s_full, r_full = ckpt.restore(full_dir)
    assert s_inc == s_full == 2
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), r_inc, r_full)


def test_incremental_writes_only_dirty_chunks(tmp_path):
    state = small_state()
    with ckpt.IncrementalCheckpointer(tmp_path, async_write=False,
                                      chunk_bytes=128) as c:
        c.save(1, state)
        first = c.stats["chunks_written"]
        c.save(2, _mutate(state))              # one element changed
        assert c.stats["chunks_written"] == first + 1
        c.save(3, _mutate(state))              # nothing changed since step 2
        assert c.stats["chunks_written"] == first + 1
        assert c.dirty_fraction() < 1.0


def test_async_writer_bounded_staleness_and_durability(tmp_path):
    state = small_state()
    with ckpt.IncrementalCheckpointer(tmp_path, async_write=True,
                                      max_pending=2) as c:
        for s in range(1, 6):
            c.save(s, _mutate(state, float(s)))
        c.wait()
        assert ckpt.latest_step(tmp_path) == 5
    _, restored = ckpt.restore(tmp_path)
    assert float(np.asarray(restored["params"]["w"])[0, 0]) == 5.0


def test_crash_mid_write_restores_last_durable_manifest(tmp_path, monkeypatch):
    """Kill the writer between the data write and the manifest publish: the
    half-written step must be invisible and the previous chain bit-exact."""
    state = small_state()
    state2 = _mutate(state)
    c = ckpt.IncrementalCheckpointer(tmp_path, async_write=False)
    c.save(1, state)

    real_rename = os.rename

    def crash_rename(src, dst):
        raise OSError("simulated power loss before publish")

    monkeypatch.setattr(os, "rename", crash_rename)
    with pytest.raises(OSError):
        c.save(2, state2)
    monkeypatch.setattr(os, "rename", real_rename)

    # the torn write left a .tmp dir at most — never a manifest
    assert ckpt.latest_step(tmp_path) == 1
    step, restored = ckpt.restore(tmp_path)
    assert step == 1
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), state, restored)

    # the writer retries cleanly after the crash (baseline uncorrupted) and
    # the orphaned tmp dir is swept by the successful publish
    c.save(2, state2)
    assert ckpt.latest_step(tmp_path) == 2
    assert not list(Path(tmp_path).glob("*.tmp"))
    _, r2 = ckpt.restore(tmp_path)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), state2, r2)


def test_restore_leaves_partial_matches_full(tmp_path):
    state = small_state()
    ckpt.save(tmp_path / "full", 1, state)              # format 1
    with ckpt.IncrementalCheckpointer(tmp_path / "inc",
                                      async_write=False) as c:
        c.save(1, state)
        c.save(2, _mutate(state))                       # format 2, chained
    for d, ref in ((tmp_path / "full", state),
                   (tmp_path / "inc", _mutate(state))):
        leaves = ckpt.restore_leaves(d, ["params/w", "opt/m"])
        assert set(leaves) == {"params/w", "opt/m"}
        np.testing.assert_array_equal(leaves["params/w"],
                                      np.asarray(ref["params"]["w"]))
        np.testing.assert_array_equal(leaves["opt/m"],
                                      np.asarray(ref["opt"]["m"]))
    # unknown paths are absent, not an error (caller falls back)
    assert ckpt.restore_leaves(tmp_path / "inc", ["no/such"]) == {}


def test_incremental_chunk_crc_detects_storage_seu(tmp_path):
    """Same SEU-in-storage refusal as full checkpoints, per chunk."""
    state = small_state()
    with ckpt.IncrementalCheckpointer(tmp_path, async_write=False) as c:
        c.save(1, state)
    shards = Path(tmp_path) / "step_0000000001" / "chunks.npz"
    raw = bytearray(shards.read_bytes())
    raw[len(raw) // 2] ^= 0x40
    shards.write_bytes(bytes(raw))
    with pytest.raises((IOError, ValueError, Exception)):
        ckpt.restore(tmp_path, 1)


def test_retention_keeps_chain_referenced_dirs(tmp_path):
    """keep_n pruning must never delete a step dir an alive manifest still
    references for clean chunks."""
    state = small_state()
    with ckpt.IncrementalCheckpointer(tmp_path, async_write=False,
                                      keep_n=2) as c:
        for s in range(1, 7):
            c.save(s, _mutate(state, float(s)))
    # steps 5 and 6 are kept; both reference step 1 (the only writer of the
    # never-dirtied leaves), so step 1 must survive
    names = sorted(d.name for d in Path(tmp_path).iterdir())
    assert "step_0000000006" in names and "step_0000000005" in names
    assert "step_0000000001" in names
    _, restored = ckpt.restore(tmp_path)
    assert float(np.asarray(restored["params"]["w"])[0, 0]) == 6.0


def test_async_save_snapshots_before_caller_mutates(tmp_path):
    """save() must capture the state at call time: a numpy leaf mutated by
    the caller after save() returns must not leak into the durable bytes."""
    w = np.zeros((64, 64), np.float32)
    with ckpt.IncrementalCheckpointer(tmp_path, async_write=True) as c:
        c.save(1, {"w": w})
        w[:] = 7.0                       # caller keeps training/serving
        c.wait()
    _, restored = ckpt.restore(tmp_path)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.zeros((64, 64), np.float32))


def test_failed_write_does_not_corrupt_stats_or_rebase(tmp_path, monkeypatch):
    state = small_state()
    c = ckpt.IncrementalCheckpointer(tmp_path, async_write=False,
                                     full_every=2)
    c.save(1, state)
    before = dict(c.stats)
    real_rename = os.rename
    monkeypatch.setattr(os, "rename",
                        lambda s, d: (_ for _ in ()).throw(OSError("torn")))
    with pytest.raises(OSError):
        c.save(2, _mutate(state))
    monkeypatch.setattr(os, "rename", real_rename)
    assert c.stats == before             # nothing counted for the torn write
    c.save(2, _mutate(state))            # durable save #2 → the rebase
    assert c.stats["saves"] == 2
    man = json.loads((Path(tmp_path) / "step_0000000002" /
                      "manifest.json").read_text())
    assert man["rebase"] is True
