"""hvd-pipeline checkpoint half: the background rank-0 writer
(utils/checkpoint.py) — overlap, atomicity under a mid-write kill,
ordering, the elastic commit() integration — plus the persistent
compile cache (core/state.compile_cache_dir: megakernel manifest +
warm start across a simulated elastic relaunch)."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu
from horovod_tpu import elastic
from horovod_tpu.utils import checkpoint as ck


def _tree():
    return {"w": jnp.arange(8.0), "b": np.arange(4.0, dtype="float32")}


def _slow_write(seconds):
    real = ck._write_bytes

    def write(path, blob):
        time.sleep(seconds)
        real(path, blob)

    return write


# ---------------------------------------------------------------------------
# Background writes
# ---------------------------------------------------------------------------

def test_save_checkpoint_async_roundtrip(hvd, tmp_path):
    path = str(tmp_path / "ckpt.msgpack")
    h = ck.save_checkpoint(path, _tree(), step=7)
    assert bool(h)  # the historical truthy-on-rank-0 contract
    assert h.wait(10.0)
    restored = ck.restore_checkpoint(
        path, {"w": jnp.zeros(8), "b": np.zeros(4, "float32")},
        broadcast=False)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.arange(8.0))
    np.testing.assert_array_equal(restored["b"], np.arange(4.0))
    assert ck.resume_epoch(path) == 7


def test_save_latency_excludes_disk(hvd, tmp_path, monkeypatch):
    """The acceptance gate: with a deliberately slow filesystem the
    training loop's save latency is the device→host snapshot, not the
    write — the disk time lands on the writer thread."""
    monkeypatch.setattr(ck, "_write_bytes", _slow_write(0.5))
    path = str(tmp_path / "slow.msgpack")
    t0 = time.perf_counter()
    h = ck.save_checkpoint(path, _tree())
    call_latency = time.perf_counter() - t0
    assert call_latency < 0.25, (
        f"save_checkpoint blocked {call_latency:.3f}s on a 0.5s disk")
    assert not h.done
    assert h.wait(10.0)
    assert os.path.exists(path)
    snap = horovod_tpu.metrics()
    assert snap["checkpoint.write_seconds"]["count"] >= 1
    assert snap["checkpoint.write_seconds"]["sum"] >= 0.5


def test_block_true_restores_sync_semantics(hvd, tmp_path):
    path = str(tmp_path / "sync.msgpack")
    h = ck.save_checkpoint(path, _tree(), block=True)
    assert h.done and os.path.exists(path)


def test_writer_killed_mid_write_previous_checkpoint_intact(
        hvd, tmp_path, monkeypatch):
    """A write that dies midway (partial tmp, no rename) must leave the
    previous checkpoint bytes untouched — restore_checkpoint can never
    see a torn file — and surface the failure at wait()."""
    path = str(tmp_path / "atomic.msgpack")
    ck.save_checkpoint(path, {"v": jnp.asarray(1.0)}).wait(10.0)
    good = open(path, "rb").read()

    def dying_write(p, blob):
        with open(f"{p}.tmp.partial", "wb") as f:
            f.write(blob[: len(blob) // 2])  # torn tmp left behind
        raise OSError("disk died mid-write")

    errors_before = horovod_tpu.metrics().get(
        "checkpoint.errors", {}).get("value", 0)
    monkeypatch.setattr(ck, "_write_bytes", dying_write)
    h = ck.save_checkpoint(path, {"v": jnp.asarray(2.0)})
    with pytest.raises(ck.CheckpointError, match="disk died"):
        h.wait(10.0)
    monkeypatch.undo()
    # The published path still holds the previous checkpoint, bit for bit.
    assert open(path, "rb").read() == good
    restored = ck.restore_checkpoint(path, {"v": jnp.zeros(())},
                                     broadcast=False)
    assert float(restored["v"]) == 1.0
    assert horovod_tpu.metrics()["checkpoint.errors"]["value"] \
        == errors_before + 1


def test_writes_apply_in_submission_order(hvd, tmp_path):
    path = str(tmp_path / "ordered.msgpack")
    handles = [ck.save_checkpoint(path, {"v": jnp.asarray(float(i))})
               for i in range(5)]
    for h in handles:
        h.wait(10.0)
    restored = ck.restore_checkpoint(path, {"v": jnp.zeros(())},
                                     broadcast=False)
    assert float(restored["v"]) == 4.0


def test_restore_fences_pending_writes(hvd, tmp_path, monkeypatch):
    """restore right after an async save sees the new bytes (wait_for_
    writes inside restore_checkpoint), even on a slow filesystem."""
    monkeypatch.setattr(ck, "_write_bytes", _slow_write(0.3))
    path = str(tmp_path / "fence.msgpack")
    ck.save_checkpoint(path, {"v": jnp.asarray(3.0)})
    restored = ck.restore_checkpoint(path, {"v": jnp.zeros(())},
                                     broadcast=False)
    assert float(restored["v"]) == 3.0


def test_numpy_leaves_snapshot_at_call_time(hvd, tmp_path):
    """In-place mutation after save_checkpoint returns must not leak
    into the written bytes (the writer serializes a snapshot)."""
    arr = np.arange(4.0, dtype="float32")
    path = str(tmp_path / "snap.msgpack")
    h = ck.save_checkpoint(path, {"a": arr})
    arr[:] = -1.0
    h.wait(10.0)
    restored = ck.restore_checkpoint(path, {"a": np.zeros(4, "float32")},
                                     broadcast=False)
    np.testing.assert_array_equal(restored["a"], np.arange(4.0))


def test_pending_gauge_and_wait_for_writes(hvd, tmp_path, monkeypatch):
    monkeypatch.setattr(ck, "_write_bytes", _slow_write(0.2))
    path = str(tmp_path / "pending.msgpack")
    ck.save_checkpoint(path, _tree())
    assert ck.pending_writes() >= 1
    assert ck.wait_for_writes(10.0)
    assert ck.pending_writes() == 0
    assert horovod_tpu.metrics()["checkpoint.pending"]["value"] == 0


# ---------------------------------------------------------------------------
# Elastic commit() rides the background writer
# ---------------------------------------------------------------------------

def test_elastic_commit_overlaps_disk(hvd, tmp_path, monkeypatch):
    monkeypatch.setenv("HVD_TPU_ELASTIC_DIR", str(tmp_path))
    monkeypatch.setattr(ck, "_write_bytes", _slow_write(0.4))
    state = elastic.State(w=jnp.arange(4.0), step=3)
    t0 = time.perf_counter()
    state.commit()
    commit_latency = time.perf_counter() - t0
    assert commit_latency < 0.2, (
        f"commit blocked {commit_latency:.3f}s on a 0.4s disk")
    assert state.wait_committed(10.0)
    assert os.path.exists(str(tmp_path / elastic._STATE_FILE))


def test_elastic_relaunch_resumes_from_async_commit(hvd, tmp_path,
                                                    monkeypatch):
    """Commit asynchronously, then a fresh State (the relaunched
    incarnation) sync()s: it must converge on the committed values —
    sync fences the in-flight publish first."""
    monkeypatch.setenv("HVD_TPU_ELASTIC_DIR", str(tmp_path))
    monkeypatch.setattr(ck, "_write_bytes", _slow_write(0.3))
    first = elastic.State(w=jnp.arange(4.0) * 2.0, step=9)
    first.commit()  # returns before the 0.3s write lands

    relaunched = elastic.State(w=jnp.zeros(4), step=0)
    relaunched.sync()
    assert relaunched.step == 9
    np.testing.assert_array_equal(np.asarray(relaunched.w),
                                  np.arange(4.0) * 2.0)


# ---------------------------------------------------------------------------
# Persistent compile cache (core/state.compile_cache_dir: one rule)
# ---------------------------------------------------------------------------

def _fused_cycle(hvd, tag):
    xs = [hvd.shard(np.arange(8 * 4, dtype=np.float32).reshape(8, 4) + i)
          for i in range(3)]
    hs = [hvd.allreduce_async(x, average=True, name=f"{tag}.{i}")
          for i, x in enumerate(xs)]
    return [np.asarray(hvd.synchronize(h)) for h in hs]


def test_compile_cache_rule(tmp_path, monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` placed from outside wins and the
    package never writes ``jax_compilation_cache_dir``; unset, an
    accelerator gets ``<checkout>/.jax_cache`` and a CPU run nothing."""
    from horovod_tpu.core import state as st

    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda name, value: (updates.append(name),
                             real_update(name, value))[1])
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert st.compile_cache_dir() == str(tmp_path)
    assert st.configure_compile_cache() == str(tmp_path)
    assert updates and "jax_compilation_cache_dir" not in updates

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert st.compile_cache_dir() is None  # CPU backend, nothing placed
    assert st.configure_compile_cache() is None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert st.compile_cache_dir() == os.path.join(checkout, ".jax_cache")


def test_compile_cache_from_outside_fills_and_is_never_set(tmp_path):
    """A fresh process with ``JAX_COMPILATION_CACHE_DIR`` set: jax reads
    it, ``hvd.init()`` writes no directory, and the directory fills."""
    import subprocess
    import sys

    cache = tmp_path / "outside"
    code = (
        "import jax, jax.numpy as jnp\n"
        "writes = []\n"
        "real = jax.config.update\n"
        "jax.config.update = lambda n, v: (writes.append(n), "
        "real(n, v))[1]\n"
        "import horovod_tpu as hvd\n"
        "hvd.init()\n"
        "jax.block_until_ready(jax.jit(lambda x: x * 2 + 1)"
        "(jnp.arange(8.0)))\n"
        "hvd.shutdown()\n"
        "assert 'jax_compilation_cache_dir' not in writes, writes\n"
        "print('CACHE_DIR', jax.config.jax_compilation_cache_dir)\n")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    assert f"CACHE_DIR {cache}" in out.stdout
    assert any(cache.iterdir())


def test_compile_cache_reuse_across_simulated_relaunch(tmp_path,
                                                       monkeypatch):
    """First incarnation: a fused allreduce builds a megakernel and
    records it in the manifest of the resolved directory.  Simulated
    relaunch (executables flushed, re-init): warm_start AOT-rebuilds the
    executable at init — before any collective runs — and it serves the
    replayed cycle with identical results."""
    from horovod_tpu.ops import megakernel as mk

    cache_dir = str(tmp_path / "compile-cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache_dir)
    import horovod_tpu as hvd

    hvd.init(devices=jax.devices())
    try:
        res1 = _fused_cycle(hvd, "cc")
        manifest = mk.load_manifest(cache_dir)
        assert len(manifest) >= 1
        assert manifest[0]["variant"] in ("sp_pr", "sp_rep")
    finally:
        hvd.shutdown()

    mk.flush("test: simulated relaunch")
    assert mk.cache_size() == 0
    warm_before = mk.stats.warm_starts
    hvd.init(devices=jax.devices())
    try:
        # Warmed at init: executables exist BEFORE the first collective.
        assert mk.cache_size() >= 1
        assert mk.stats.warm_starts > warm_before
        res2 = _fused_cycle(hvd, "cc")
        assert all(a.tobytes() == b.tobytes()
                   for a, b in zip(res1, res2))
        assert horovod_tpu.metrics()[
            "megakernel.warm_starts"]["value"] > 0
    finally:
        hvd.shutdown()


def test_compile_cache_manifest_ignores_foreign_mesh(tmp_path,
                                                     monkeypatch):
    """Entries recorded for a different mesh fingerprint are skipped,
    not compiled against the wrong topology."""
    from horovod_tpu.ops import megakernel as mk

    cache_dir = str(tmp_path / "foreign")
    os.makedirs(cache_dir)
    import json

    with open(os.path.join(cache_dir, mk.MANIFEST_NAME), "w") as f:
        json.dump({"format": "hvd-megakernel-manifest-v1",
                   "entries": [{
                       "variant": "sp_pr", "op": "psum", "average": True,
                       "denom": 4096, "dtype": "float32",
                       "shapes": [[4]], "donate": [True], "hier": False,
                       "digest": None,
                       "mesh": {"platform": "tpu", "device_kind": "v9",
                                "count": 4096}}]}, f)
    import horovod_tpu as hvd

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache_dir)
    hvd.init(devices=jax.devices())
    try:
        assert mk.warm_start(horovod_tpu.mesh(), cache_dir) == 0
    finally:
        hvd.shutdown()


def test_compression_state_rides_checkpoints(hvd, tmp_path, monkeypatch):
    """Quantized-allreduce error-feedback residuals are
    checkpoint-restorable: hvd.compression_state() serializes through
    the normal save/restore path, and after load_compression_state()
    the resumed step replays BITWISE (the EF chain continues instead of
    restarting)."""
    from horovod_tpu.ops import megakernel as mk

    monkeypatch.setenv("HVD_TPU_COMPRESSION", "int8")
    n = horovod_tpu.size()
    rng = np.random.default_rng(21)
    x = horovod_tpu.shard(rng.standard_normal((n, 48)).astype("float32"))
    np.asarray(horovod_tpu.allreduce(x, average=False, name="ckq"))
    snap = horovod_tpu.compression_state()
    assert snap["residuals"]
    path = str(tmp_path / "q.msgpack")
    ck.save_checkpoint(path, {"params": _tree(), "quant": snap},
                       block=True)
    out_next = np.asarray(horovod_tpu.allreduce(x, average=False,
                                                name="ckq"))

    # Simulated relaunch: executor state gone, checkpoint restores it
    # (flax restores by target structure — a snapshot with the same
    # groups serves as the template, exactly as a resumed trainer's
    # would).
    mk.flush("test: relaunch")
    restored = ck.restore_checkpoint(
        path, {"params": _tree(), "quant": snap}, broadcast=False)
    horovod_tpu.load_compression_state(restored["quant"])
    out_resumed = np.asarray(horovod_tpu.allreduce(x, average=False,
                                                   name="ckq"))
    assert out_next.tobytes() == out_resumed.tobytes()


# ---------------------------------------------------------------------------
# Sharded distributed checkpointing (docs/performance.md "Scale-out
# control plane")
# ---------------------------------------------------------------------------

def _big_tree():
    rng = np.random.default_rng(11)
    return {
        "layers": [
            {"w": rng.standard_normal((16, 16)).astype("float32"),
             "b": rng.standard_normal((16,)).astype("float32")}
            for _ in range(3)
        ],
        "head": rng.standard_normal((16, 4)).astype("float64"),
        "meta": {"epoch": 9, "name": "m"},
    }


def _zeros_like_big():
    return {
        "layers": [
            {"w": np.zeros((16, 16), "float32"),
             "b": np.zeros((16,), "float32")}
            for _ in range(3)
        ],
        "head": np.zeros((16, 4), "float64"),
        "meta": {"epoch": 0, "name": ""},
    }


def _assert_trees_bitwise(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        if isinstance(x, np.ndarray) or isinstance(y, np.ndarray):
            assert np.asarray(x).tobytes() == np.asarray(y).tobytes()
        else:
            assert x == y


def test_shard_assignment_deterministic_and_balanced():
    sizes = [100, 90, 80, 10, 10, 10, 5, 0]
    a1 = ck.shard_assignment(sizes, 3)
    a2 = ck.shard_assignment(sizes, 3)
    assert a1 == a2
    load = [0, 0, 0]
    for i, w in enumerate(a1):
        load[w] += sizes[i]
    assert max(load) - min(load) <= max(sizes)
    # every writer gets used when there is enough work
    assert set(a1) == {0, 1, 2}


def test_sharded_save_restore_reshards_across_world_sizes(tmp_path):
    """The tentpole gate: save under one world size, restore under
    different ones, parameters bitwise-equal — no broadcast, no rank-0
    byte funnel."""
    d = str(tmp_path / "sharded")
    tree_in = _big_tree()
    h = ck.save_checkpoint_sharded(d, tree_in, step=2, world=2,
                                   block=True)
    assert bool(h) and h.done
    man = ck.load_sharded_manifest(d)
    assert man["world"] == 2 and man["format"] == ck.SHARDED_FORMAT
    # shard files exist for both writer ranks of the declared layout
    sd = os.path.join(d, man["save_dir"])
    assert sorted(f for f in os.listdir(sd) if f.endswith(".msgpack")) \
        == ["shard-00000-of-00002.msgpack", "shard-00001-of-00002.msgpack"]
    # restore "at np=1" and "at np=4" (the layout is irrelevant at
    # restore: every process reads what it needs from shared storage)
    out1 = ck.restore_checkpoint_sharded(d, _zeros_like_big())
    _assert_trees_bitwise(out1, tree_in)
    ck.save_checkpoint_sharded(d, tree_in, step=3, world=4, block=True)
    out4 = ck.restore_checkpoint_sharded(d, _zeros_like_big())
    _assert_trees_bitwise(out4, tree_in)


def test_sharded_torn_fleet_keeps_previous_checkpoint(tmp_path,
                                                      monkeypatch):
    """Mid-write kill of any single host: the manifest commit waits for
    every shard sidecar, times out, and the MANIFEST pointer still
    names the previous COMPLETE save — a partial save can never shadow
    it."""
    d = str(tmp_path / "torn")
    good = _big_tree()
    ck.save_checkpoint_sharded(d, good, step=1, world=2, block=True)
    # Second save at world=2, but only "rank 0" of the fleet survives
    # (virtual=False: strict per-rank shard writing; rank 1 never runs)
    monkeypatch.setenv("HVD_TPU_CKPT_MANIFEST_TIMEOUT", "0.4")
    bad = jax.tree_util.tree_map(
        lambda x: x * 2 if isinstance(x, np.ndarray) else x, good)
    h = ck.save_checkpoint_sharded(d, bad, step=2, world=2, rank=0,
                                   virtual=False)
    with pytest.raises(ck.CheckpointError, match="never became durable"):
        h.wait(30.0)
    man = ck.load_sharded_manifest(d)
    assert man["step"] == 1  # pointer still the previous complete save
    out = ck.restore_checkpoint_sharded(d, _zeros_like_big())
    _assert_trees_bitwise(out, good)


def test_sharded_two_rank_fleet_commit_order(tmp_path):
    """np=2-style save driven rank by rank (strict mode): rank 0's
    manifest commit only lands after rank 1's shard is durable — the
    rank-0-committed-manifest contract without any collective."""
    d = str(tmp_path / "fleet2")
    tree_in = _big_tree()
    # rank 1 writes its shard first, then rank 0 commits
    h1 = ck.save_checkpoint_sharded(d, tree_in, step=5, world=2, rank=1,
                                    virtual=False, block=True)
    assert bool(h1)
    assert not os.path.exists(os.path.join(d, "MANIFEST"))
    h0 = ck.save_checkpoint_sharded(d, tree_in, step=5, world=2, rank=0,
                                    virtual=False, block=True)
    assert bool(h0)
    out = ck.restore_checkpoint_sharded(d, _zeros_like_big())
    _assert_trees_bitwise(out, tree_in)
    assert ck.load_sharded_manifest(d)["shard_digests"].keys() == {"0",
                                                                   "1"}


def test_sharded_restore_rejects_corrupt_shard(tmp_path):
    d = str(tmp_path / "corrupt")
    ck.save_checkpoint_sharded(d, _big_tree(), step=1, world=2,
                               block=True)
    man = ck.load_sharded_manifest(d)
    victim = os.path.join(d, man["save_dir"],
                          "shard-00001-of-00002.msgpack")
    with open(victim, "r+b") as f:
        f.seek(0)
        f.write(b"\xff\xff\xff\xff")
    with pytest.raises(ck.CheckpointError, match="digest mismatch"):
        ck.restore_checkpoint_sharded(d, _zeros_like_big())


def test_restore_broadcast_skip_decision(monkeypatch):
    """The broadcast-elision rule: skip only when EVERY rank gathered
    the same non-None digest (checkpoint.broadcast_skipped counts it);
    any missing or divergent local file falls back to the classic
    rank-0 broadcast."""
    calls = {}

    def fake_allgather(obj, name=None):
        calls["digest"] = obj
        return calls["fleet"]

    monkeypatch.setattr("horovod_tpu.ops.objects.allgather_object",
                        fake_allgather)
    calls["fleet"] = ["d1", "d1", "d1"]
    assert ck._broadcast_skippable("d1")
    calls["fleet"] = ["d1", "d2", "d1"]
    assert not ck._broadcast_skippable("d1")
    calls["fleet"] = ["d1", None, "d1"]
    assert not ck._broadcast_skippable("d1")
    calls["fleet"] = []
    assert not ck._broadcast_skippable(None)


def test_sharded_untagged_save_requires_step_in_mp(tmp_path):
    """The tag must be fleet-agreed: an untagged save in strict
    multi-rank mode is a contract error (a process-local counter
    diverges across elastic restarts)."""
    with pytest.raises(ValueError, match="requires step="):
        ck.save_checkpoint_sharded(str(tmp_path / "x"), _big_tree(),
                                   world=2, rank=0, virtual=False)


def test_sharded_retry_ignores_stale_sidecars_from_torn_attempt(
        tmp_path, monkeypatch):
    """Torn-retry freshness: a save-<tag>/ left by a torn attempt (no
    committed manifest) holds self-consistent shard+.ok pairs; a retry
    under the same tag must NOT let the commit consume them until the
    owning rank republishes — otherwise the manifest could mix
    attempts (or record a digest mid-rewrite)."""
    d = str(tmp_path / "retry")
    tree_a = _big_tree()
    # attempt 1, torn: rank 1 published, rank 0 (the committer) died
    ck.save_checkpoint_sharded(d, tree_a, step=7, world=2, rank=1,
                               virtual=False, block=True)
    assert not os.path.exists(os.path.join(d, "MANIFEST"))
    # age the leftover sidecar past the staleness margin (a real torn
    # retry happens after a job restart, minutes later)
    stale_ok = os.path.join(d, "save-s7",
                            "shard-00001-of-00002.msgpack.ok")
    past = time.time() - 3600
    os.utime(stale_ok, (past, past))
    # attempt 2 with DIFFERENT bytes: rank 0 runs, rank 1 never
    # republishes -> the stale sidecar must not satisfy the commit
    monkeypatch.setenv("HVD_TPU_CKPT_MANIFEST_TIMEOUT", "0.6")
    tree_b = jax.tree_util.tree_map(
        lambda x: x + 1 if isinstance(x, np.ndarray) else x, tree_a)
    h = ck.save_checkpoint_sharded(d, tree_b, step=7, world=2, rank=0,
                                   virtual=False)
    with pytest.raises(ck.CheckpointError, match="never became durable"):
        h.wait(30.0)
    assert not os.path.exists(os.path.join(d, "MANIFEST"))
