"""Checkpoint/resume with the reference's rank-0 + broadcast conventions,
overlapped with training (hvd-pipeline).

The reference delegates serialization to the frameworks but fixes two
conventions (SURVEY.md §5): save on rank 0 only (README.md:102-104,
examples/keras_imagenet_resnet50.py:126-127) and, on resume, load on rank 0
then broadcast — including the scalar ``resume_from_epoch``
(examples/keras_imagenet_resnet50.py:47-56, :130-133).

Serialization uses flax msgpack (``flax.serialization``) — a single
self-contained file, atomic-renamed into place.

Background writes (PR 5)
------------------------
``save_checkpoint`` no longer blocks the training loop on disk: the
caller pays only the device→host snapshot, then a dedicated rank-0
writer thread serializes and publishes the file (tmp + ``os.replace``,
so a reader NEVER sees a torn checkpoint — a write killed midway leaves
the previous checkpoint intact and at most an orphaned ``*.tmp.*``).
The returned :class:`CheckpointWrite` handle is truthy exactly when
this process performs the save (the historical bool contract) and has
``wait()`` for an explicit durability point; writes to one path apply
in submission order (single FIFO writer).  ``restore_checkpoint`` and
``resume_epoch`` fence pending writes to their path first, so
read-after-write inside one process stays coherent.  Pending writes
flush at interpreter exit (``atexit``); a writer failure re-raises at
``wait()`` AND is flight-recorded (``checkpoint_error``) so
fire-and-forget savers still see it.

Telemetry (docs/metrics.md): ``checkpoint.write_seconds`` histogram
(disk time per write, off the training loop), ``checkpoint.pending``
gauge (queued+in-flight writes), ``checkpoint.errors`` counter.
"""

from __future__ import annotations

import atexit
import os
import queue
import sys
import threading
import time
from typing import Any, Optional

import jax
import numpy as np

from .. import chaos as _chaos
from .. import telemetry as _telemetry
from .. import trace as _trace
from ..analysis import lockorder as _lockorder
from ..analysis import threads as _athreads
from ..core import state as _state
from ..memory import ledger as _mem
from ..parallel.data import broadcast_parameters
from ..telemetry import flight as _flight
from .retry import BackoffPolicy, retry_call

_M_WRITE_SECONDS = _telemetry.histogram(
    "checkpoint.write_seconds", "seconds",
    "disk seconds per background checkpoint write")
_R_WRITE = _trace.region("checkpoint.write", "checkpoint", timed=True)
_M_PENDING = _telemetry.gauge(
    "checkpoint.pending", "checkpoint writes queued or in flight")
_M_RETRIES = _telemetry.counter(
    "checkpoint.retries", "transient write failures retried with "
    "backoff before surfacing CheckpointError (hvd-chaos hardening)")
_M_SHARDS = _telemetry.counter(
    "checkpoint.shards_written", "parameter shard files published by "
    "this process (sharded distributed checkpointing)")
_M_MANIFESTS = _telemetry.counter(
    "checkpoint.manifest_commits", "sharded-checkpoint manifests "
    "committed (rank 0; the save's durability point)")
_M_BCAST_SKIPPED = _telemetry.counter(
    "checkpoint.broadcast_skipped", "restore broadcasts skipped "
    "because a digest allgather proved every rank read identical "
    "bytes locally")


def _write_retries() -> int:
    """Attempts per checkpoint publish (1 = the pre-chaos no-retry
    behavior).  A transient OSError — flaky NFS, a momentary ENOSPC —
    should not permanently fail a CheckpointWrite that a retry 50 ms
    later would land."""
    return max(1, int(os.environ.get("HVD_TPU_CKPT_RETRIES", "3")))


class CheckpointError(RuntimeError):
    """A background checkpoint write failed (surfaced at ``wait()``)."""


class CheckpointWrite:
    """Handle for one (possibly still in-flight) checkpoint write.

    Truthiness keeps the historical ``save_checkpoint`` bool contract:
    truthy iff THIS process performs the save (rank 0), whether or not
    the bytes hit disk yet.  ``wait()`` is the durability point."""

    def __init__(self, path: Optional[str], performed: bool) -> None:
        self.path = path
        self._performed = performed
        self._done = threading.Event()
        self.error: Optional[BaseException] = None
        if not performed:
            self._done.set()  # nothing to wait for on non-saving ranks

    def __bool__(self) -> bool:
        return self._performed

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until the write is durably published (atomic rename
        complete).  Returns False on timeout; raises
        :class:`CheckpointError` if the write failed."""
        if not self._done.wait(timeout):
            return False
        if self.error is not None:
            raise CheckpointError(
                f"background checkpoint write to {self.path!r} failed: "
                f"{type(self.error).__name__}: {self.error}"
            ) from self.error
        return True


def _write_bytes_once(path: str, blob: bytes) -> None:
    """One atomic publish attempt: full write to a private tmp, then
    rename.  A crash at ANY point leaves either the previous file or
    the new one — never a torn read (tests kill this midway to prove
    it).  The hvd-chaos ``ckpt.oserror`` site injects its transient
    OSError here — inside the retried region, before the rename — so
    an injected fault can never publish partial bytes either."""
    tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
    try:
        with open(tmp, "wb") as f:
            fault = _chaos.fire("ckpt.oserror") if _chaos.active() \
                else None
            if fault is not None:
                raise OSError(28, "hvd-chaos: ckpt.oserror (injected "
                              "transient ENOSPC)", tmp)
            f.write(blob)
        os.replace(tmp, path)
    except BaseException:
        # A failed attempt must not strand its tmp: the NEXT attempt
        # re-creates it, and the atomicity story stays "previous file
        # or new file, never torn, at most one orphaned tmp".
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def _write_bytes(path: str, blob: bytes) -> None:
    """Atomic publish with transient-fault retries (hvd-chaos
    hardening): up to ``HVD_TPU_CKPT_RETRIES`` attempts with the shared
    jittered exponential backoff (utils/retry.py); each retried failure
    is counted, flight-recorded and logged.  Only OSError retries —
    serialization bugs fail immediately.  The final failure re-raises
    unchanged, keeping the CheckpointError contract at ``wait()``."""

    def on_retry(attempt: int, exc: BaseException, delay: float) -> None:
        _M_RETRIES.inc()
        _flight.record("ckpt_retry", path, attempt,
                       f"{type(exc).__name__}: {exc}")
        print(f"WARNING: checkpoint write to {path!r} failed "
              f"(attempt {attempt + 1}/{_write_retries()}: "
              f"{type(exc).__name__}: {exc}); retrying in "
              f"{delay * 1e3:.0f}ms", file=sys.stderr)

    retry_call(lambda: _write_bytes_once(path, blob),
               attempts=_write_retries(),
               policy=BackoffPolicy(base=0.02, cap=0.5),
               retry_on=(OSError,), on_retry=on_retry)


class _Writer:
    """The rank-0 background checkpoint writer: one FIFO thread, so
    writes to the same path apply in submission order."""

    def __init__(self) -> None:
        self._q: queue.Queue = queue.Queue()
        self._pending = 0
        self._lock = _lockorder.make_lock("checkpoint._Writer._lock")
        self._thread = threading.Thread(
            target=self._run, name="hvd-ckpt-writer", daemon=True)
        self._thread.start()

    def submit(self, handle: CheckpointWrite, host_tree: Any,
               step: Optional[int]) -> None:
        # hvd-mem: the host snapshot is framework-held memory until the
        # background write publishes it — charged per handle, released
        # in the writer's finally (success or failure alike).
        if _mem.enabled():
            handle._mem_bytes = _mem.tree_nbytes(host_tree)
            if handle._mem_bytes:
                _mem.ledger.alloc("checkpoint.snapshots",
                                  handle._mem_bytes)

        def publish() -> None:
            from flax import serialization

            blob = serialization.to_bytes(host_tree)
            _write_bytes(handle.path, blob)
            if step is not None:
                _write_bytes(f"{handle.path}.step", str(step).encode())

        self.submit_task(handle, publish)

    def submit_task(self, handle: CheckpointWrite, publish) -> None:
        """Queue an arbitrary publish thunk on the FIFO writer thread
        (the sharded-checkpoint path submits shard writes and the
        manifest commit through here, so ordering and the
        CheckpointError-at-wait() contract stay uniform)."""
        with self._lock:
            self._pending += 1
            _M_PENDING.set(self._pending)
        self._q.put((handle, publish))

    def pending(self) -> int:
        with self._lock:
            return self._pending

    def _run(self) -> None:  # thread: writer
        _athreads.set_role("writer")
        while True:
            item = self._q.get()
            if item is None:  # drain sentinel (wait_all)
                continue
            handle, publish = item
            try:
                # hvd-trace: a write that stole the cycle shows up in
                # the fleet trace as a checkpoint-leg span; the
                # histogram reads the region's own pair of clock reads.
                with _R_WRITE(path=os.path.basename(handle.path)) as r:
                    try:
                        publish()
                    except BaseException as e:  # noqa: BLE001 — carried
                        handle.error = e        # to wait()
                        _telemetry.checkpoint_error_event(
                            handle.path, f"{type(e).__name__}: {e}")
                _M_WRITE_SECONDS.observe(r.seconds)
            finally:
                nb = getattr(handle, "_mem_bytes", 0)
                if nb:
                    _mem.ledger.free("checkpoint.snapshots", nb)
                with self._lock:
                    self._pending -= 1
                    _M_PENDING.set(self._pending)
                handle._done.set()

    def wait_all(self, timeout: Optional[float] = None) -> bool:
        """Block until every submitted write has finished (the atexit
        flush; returns False on timeout)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while self.pending() > 0:
            if deadline is not None and time.monotonic() > deadline:
                return False
            time.sleep(0.005)
        return True


_writer: Optional[_Writer] = None
_writer_lock = _lockorder.make_lock("checkpoint._writer_lock")


def _get_writer() -> _Writer:
    global _writer
    with _writer_lock:
        if _writer is None or not _writer._thread.is_alive():
            _writer = _Writer()
            # Pending writes must survive a normal interpreter exit
            # (the thread is a daemon — without this flush a short job
            # could lose its final checkpoint).
            atexit.register(_writer.wait_all, 30.0)
        return _writer


def pending_writes() -> int:
    """Number of checkpoint writes queued or in flight on this process."""
    with _writer_lock:
        w = _writer
    return w.pending() if w is not None else 0


def wait_for_writes(timeout: Optional[float] = None) -> bool:
    """Flush every pending background write (all paths)."""
    with _writer_lock:
        w = _writer
    return w.wait_all(timeout) if w is not None else True


def _is_saving_process() -> bool:
    return _state.process_index() == 0


def _host_snapshot(tree: Any) -> Any:
    """Device→host snapshot the writer thread can serialize later.

    jax Arrays are immutable — ``np.asarray`` (the fetch) is safe to
    alias.  Raw numpy leaves are COPIED: the caller may mutate them
    in place after ``save_checkpoint`` returns, and the writer must
    capture the value at call time (same rationale as
    ``elastic._host_copy``)."""
    def snap(x):
        if isinstance(x, (int, float, bool, bytes, str)):
            return x
        if isinstance(x, np.ndarray):
            return np.array(x)
        return np.asarray(x)

    return jax.tree_util.tree_map(snap, tree)


def write_tree_async(path: str, host_tree: Any,
                     step: Optional[int] = None) -> CheckpointWrite:
    """Queue one already-host-resident tree for the background writer
    (the low-level half of :func:`save_checkpoint`; ``elastic.commit``
    feeds its snapshot through here so the commit barrier excludes disk
    latency).  Caller must guarantee ``host_tree`` is not mutated
    afterwards — :func:`_host_snapshot` produces such a tree."""
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    handle = CheckpointWrite(path, performed=True)
    _get_writer().submit(handle, host_tree, step)
    return handle


def save_checkpoint(path: str, tree: Any, step: Optional[int] = None,
                    block: bool = False) -> CheckpointWrite:
    """Save ``tree`` at ``path`` from the coordinating process only
    (≙ the rank-0 guard in every reference example).

    The call returns after the device→host snapshot; serialization and
    the atomic tmp+rename publish happen on the background writer
    thread, overlapped with training.  Returns a
    :class:`CheckpointWrite` — truthy iff this process performs the
    save (the historical bool contract: ``if save_checkpoint(...)``),
    with ``wait()`` as the explicit durability point.  ``block=True``
    restores fully synchronous semantics."""
    if not _is_saving_process():
        return CheckpointWrite(path, performed=False)
    handle = write_tree_async(path, _host_snapshot(tree), step=step)
    if block:
        handle.wait()
    return handle


def restore_checkpoint(path: str, target: Any, broadcast: bool = True) -> Any:
    """Load ``path`` into the structure of ``target`` and (by default)
    broadcast from root so all replicas resume identically
    (≙ load-on-rank-0-then-broadcast, keras_imagenet_resnet50.py:130-133).

    Only the coordinating process reads the file — non-root processes keep
    ``target`` and receive root's values through the broadcast, so a
    checkpoint that exists only on the coordinator's disk restores
    everywhere (the reference's save-on-rank-0 convention implies exactly
    this asymmetry).  Pending background writes are fenced first, so a
    restore right after an async save sees the new bytes (and the atomic
    rename means it can never see torn ones).

    Broadcast elision: on a shared filesystem every rank reads the SAME
    file, so broadcasting every parameter byte through rank 0 is pure
    waste.  When all ranks can read ``path`` locally, a 64-byte digest
    allgather over the control plane proves the reads are identical and
    the full-tree broadcast is skipped (``checkpoint.broadcast_skipped``
    counts it); any rank missing the file — the rank-0-local-disk
    deployment — falls back to the classic broadcast."""
    from flax import serialization

    st = _state.global_state()
    if broadcast and _state.is_initialized() and st.multiprocess:
        wait_for_writes()
        digest = _file_digest(path) if os.path.exists(path) else None
        if _broadcast_skippable(digest):
            _M_BCAST_SKIPPED.inc()
            with open(path, "rb") as f:
                return serialization.from_bytes(target, f.read())
    if not _state.is_initialized() or _is_saving_process():
        wait_for_writes()
        with open(path, "rb") as f:
            blob = f.read()
        tree = serialization.from_bytes(target, blob)
    else:
        tree = target
    if broadcast and _state.is_initialized():
        tree = broadcast_parameters(tree, root_rank=0)
    return tree


def _file_digest(path: str) -> str:
    """Chunked sha256 — the digest pass must not hold a multi-GB
    checkpoint resident on every rank just to decide whether the
    broadcast can be skipped (the bytes are only read in full on the
    branch that actually deserializes them)."""
    import hashlib

    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 26), b""):
            h.update(chunk)
    return h.hexdigest()


def _broadcast_skippable(digest: Optional[str]) -> bool:
    """True when every rank holds identical local checkpoint bytes —
    proved by an allgather of content digests (a control-plane object
    collective: 64 bytes per rank instead of every parameter byte
    through rank 0).  Deterministic fleet-wide: the gathered list is
    identical everywhere, so every rank takes the same branch."""
    from ..ops.objects import allgather_object

    digests = allgather_object(digest, name="checkpoint.restore.digest")
    return bool(digests) and all(
        d is not None and d == digests[0] for d in digests)


# -- sharded distributed checkpointing (docs/performance.md "Scale-out
# -- control plane") --------------------------------------------------------
#
# ``save_checkpoint`` funnels every parameter byte through rank 0 — the
# last O(world x bytes) cost in the runtime.  The sharded format splits
# the tree's leaves across the fleet: each host serializes and publishes
# ONLY its assigned shards through the background writer, and rank 0
# commits a manifest LAST — after every shard's digest sidecar proves it
# durable.  The ``MANIFEST`` pointer file is atomically renamed onto the
# new manifest only at commit, so a torn fleet (any host killed mid-
# write, rank 0 included) leaves the PREVIOUS complete checkpoint
# loadable and never shadows it with a partial one.  Restore reads the
# shards directly from shared storage — no broadcast, and the save-time
# world size is irrelevant: a checkpoint saved at np=8 reshards onto
# np=2 or np=32 by reassigning which process reads what (elastic resize
# stops round-tripping every byte through rank 0).
#
# Layout under ``directory``:
#   MANIFEST                      -> "manifest-<tag>.json" (atomic ptr)
#   manifest-<tag>.json           committed by rank 0, LAST
#   save-<tag>/shard-NNNNN-of-WWWWW.msgpack   (+ .ok digest sidecars)

MANIFEST_POINTER = "MANIFEST"
SHARDED_FORMAT = "hvd-sharded-checkpoint-v1"

_save_seq: dict = {}
_save_seq_lock = _lockorder.make_lock("checkpoint._save_seq_lock")


def _manifest_timeout() -> float:
    """How long rank 0 waits for the fleet's shard sidecars before
    failing the manifest commit (the torn-fleet bound)."""
    return float(os.environ.get("HVD_TPU_CKPT_MANIFEST_TIMEOUT", "120"))


def shard_assignment(nbytes: list, world: int) -> list:
    """Deterministic leaf -> writer-rank map: greedy largest-first onto
    the least-loaded writer, ties by rank then leaf index, so every
    rank derives the identical assignment with no agreement round."""
    order = sorted(range(len(nbytes)), key=lambda i: (-nbytes[i], i))
    load = [0] * max(1, world)
    assign = [0] * len(nbytes)
    for i in order:
        w = min(range(len(load)), key=lambda r: (load[r], r))
        assign[i] = w
        load[w] += nbytes[i]
    return assign


def _shard_name(rank: int, world: int) -> str:
    return f"shard-{rank:05d}-of-{world:05d}.msgpack"


def _sharded_leaf_specs(leaves: list) -> list:
    import json as _json

    specs = []
    for leaf in leaves:
        if isinstance(leaf, np.ndarray):
            specs.append({"kind": "array", "dtype": str(leaf.dtype),
                          "shape": list(leaf.shape),
                          "nbytes": int(leaf.nbytes)})
        else:
            # Python scalars/strings ride the manifest inline — they
            # are negotiation metadata, not parameter bytes.
            specs.append({"kind": "inline",
                          "value": _json.loads(_json.dumps(leaf)),
                          "nbytes": 0})
    return specs


def save_checkpoint_sharded(directory: str, tree: Any,
                            step: Optional[int] = None,
                            block: bool = False,
                            rank: Optional[int] = None,
                            world: Optional[int] = None,
                            virtual: Optional[bool] = None
                            ) -> CheckpointWrite:
    """Sharded distributed save: THIS process publishes the shards the
    deterministic assignment gives its rank; rank 0 additionally
    commits the manifest once every shard is durable.

    ``rank``/``world`` default to the live fleet.  Passing a ``world``
    different from the live process count is the dryrun/virtual mode:
    this one process writes EVERY shard of the declared layout (how the
    CI reshard gate saves an np=2-layout checkpoint from np=1).
    ``virtual=False`` forces the strict one-rank's-shards behavior even
    when the declared world differs from the live one — the torn-fleet
    tests drive each simulated rank through it separately.

    Multi-process fleets must pass ``step`` — the save tag has to be
    agreed across ranks, and only caller state (the training step) is
    shared by construction.

    Returns a :class:`CheckpointWrite`; on rank 0 ``wait()`` is the
    manifest commit — the save's durability point."""
    import hashlib
    import json as _json

    import jax

    live_world = (_state.global_state().process_count
                  if _state.is_initialized() else 1)
    if rank is None:
        rank = _state.process_index() if _state.is_initialized() else 0
    if world is None:
        world = live_world
    if virtual is None:
        virtual = world != live_world
    host = _host_snapshot(tree)
    leaves, _treedef = jax.tree_util.tree_flatten(host)
    specs = _sharded_leaf_specs(leaves)
    assign = shard_assignment([s["nbytes"] for s in specs], world)
    for i, s in enumerate(specs):
        if s["kind"] == "array":
            s["shard"] = assign[i]
    if step is not None:
        tag = f"s{step}"
    else:
        # The tag must be IDENTICAL on every rank — a per-process
        # counter diverges the moment one worker restarts (elastic
        # rejoin: its counter resets while the fleet's advanced, and
        # every later untagged save times out waiting for a shard in
        # the wrong save-<tag> dir).  Multi-rank fleets must pass
        # ``step`` (shared state by construction); the counter is the
        # single-process / virtual-dryrun convenience only.
        if not virtual and world > 1:
            raise ValueError(
                "save_checkpoint_sharded requires step= in "
                "multi-process mode: the save tag must be agreed "
                "across ranks, and a process-local counter diverges "
                "across elastic restarts")
        with _save_seq_lock:
            _save_seq[directory] = _save_seq.get(directory, 0) + 1
            tag = f"c{_save_seq[directory]}"
    save_dir = os.path.join(directory, f"save-{tag}")
    manifest_path = os.path.join(directory, f"manifest-{tag}.json")
    # Torn-retry detection (committing rank): a save-<tag>/ dir with no
    # committed manifest means a PREVIOUS attempt tore mid-fleet.  Its
    # leftover sidecars must not satisfy this attempt's commit while
    # the owning rank is still rewriting the shard — snapshot the ones
    # OLDER than the staleness margin (a torn attempt being retried is
    # minutes old; a same-attempt fast rank's sidecar is seconds old,
    # and must keep working — ranks complete in any order) and require
    # each to CHANGE (unlink+rewrite) before the commit accepts it.  A
    # rank that never republishes then times the commit out (pointer
    # preserved) instead of silently committing mixed-attempt bytes.
    prior_ok: dict = {}
    if rank == 0 and os.path.isdir(save_dir) \
            and not os.path.exists(manifest_path):
        margin = float(os.environ.get(
            "HVD_TPU_CKPT_STALE_OK_SECONDS", "60"))
        cutoff = time.time() - margin
        for w in range(world):
            ok = os.path.join(save_dir, _shard_name(w, world) + ".ok")
            try:
                st_ = os.stat(ok)
                if st_.st_mtime >= cutoff:
                    continue  # fresh: a same-attempt early completer
                with open(ok) as f:
                    prior_ok[w] = (f.read().strip(), st_.st_mtime)
            except OSError:
                pass
    os.makedirs(save_dir, exist_ok=True)
    writer_ranks = list(range(world)) if virtual else [rank]
    writer = _get_writer()
    handle = CheckpointWrite(manifest_path, performed=True)

    def shard_task(wr: int):
        my = {str(i): leaves[i] for i in range(len(leaves))
              if specs[i]["kind"] == "array" and assign[i] == wr}
        path = os.path.join(save_dir, _shard_name(wr, world))

        def publish() -> None:
            from flax import serialization

            # Invalidate a PREVIOUS attempt's sidecar FIRST: a torn
            # save retried under the same tag must never let the
            # manifest commit observe a stale shard+.ok pair while the
            # fresh shard is still being written.  (Belt: the commit
            # side ALSO snapshots pre-existing sidecars of an
            # uncommitted save dir and accepts each only once it has
            # changed — see ``prior_ok`` in save_checkpoint_sharded.)
            try:
                os.unlink(path + ".ok")
            except OSError:
                pass
            blob = serialization.to_bytes(my)
            _write_bytes(path, blob)
            _write_bytes(path + ".ok",
                         hashlib.sha256(blob).hexdigest().encode())
            _M_SHARDS.inc()

        return publish

    for wr in writer_ranks:
        writer.submit_task(CheckpointWrite(
            os.path.join(save_dir, _shard_name(wr, world)),
            performed=True), shard_task(wr))

    def commit_manifest() -> None:
        deadline = time.monotonic() + _manifest_timeout()
        digests: dict = {}
        while True:
            missing = [w for w in range(world) if str(w) not in digests]
            for w in list(missing):
                ok = os.path.join(save_dir,
                                  _shard_name(w, world) + ".ok")
                try:
                    st_ = os.stat(ok)
                    with open(ok) as f:
                        got = f.read().strip()
                except OSError:
                    continue
                if w in prior_ok and (got, st_.st_mtime) == prior_ok[w]:
                    continue  # previous torn attempt's sidecar,
                    # unchanged — the owning rank has not republished
                digests[str(w)] = got
            if len(digests) == world:
                break
            if time.monotonic() > deadline:
                raise CheckpointError(
                    f"sharded save {tag!r}: shards from writer rank(s) "
                    f"{[w for w in range(world) if str(w) not in digests]} "
                    f"never became durable within "
                    f"{_manifest_timeout():.0f}s; the previous complete "
                    f"checkpoint (MANIFEST pointer) is untouched")
            time.sleep(0.05)
        manifest = {
            "format": SHARDED_FORMAT, "tag": tag, "step": step,
            "world": world, "save_dir": f"save-{tag}",
            "leaves": specs, "shard_digests": digests,
        }
        _write_bytes(handle.path,
                     _json.dumps(manifest, indent=1).encode())
        # The durability point: only a COMPLETE save ever moves the
        # pointer (atomic rename), so a torn fleet can't shadow the
        # previous checkpoint.
        _write_bytes(os.path.join(directory, MANIFEST_POINTER),
                     f"manifest-{tag}.json".encode())
        _M_MANIFESTS.inc()

    if rank == 0:
        writer.submit_task(handle, commit_manifest)
    else:
        # Non-committing ranks: their durability point is their own
        # shard; ride a sentinel task so wait() fences the FIFO.
        writer.submit_task(handle, lambda: None)
    if block:
        handle.wait()
    return handle


def load_sharded_manifest(directory: str) -> dict:
    """The manifest the ``MANIFEST`` pointer names — always the latest
    COMPLETE save (the pointer moves only at commit)."""
    import json as _json

    with open(os.path.join(directory, MANIFEST_POINTER)) as f:
        name = f.read().strip()
    with open(os.path.join(directory, name)) as f:
        return _json.load(f)


def restore_checkpoint_sharded(directory: str, target: Any) -> Any:
    """Restore the latest complete sharded save into ``target``'s
    structure — at ANY world size.  Every process reads the shards it
    needs straight from shared storage (for replicated parameters:
    all of them), verifying each shard against the manifest digest; no
    byte crosses the control plane, so elastic resize restores at disk
    bandwidth instead of rank-0 uplink bandwidth."""
    import hashlib

    import jax
    from flax import serialization

    wait_for_writes()
    manifest = load_sharded_manifest(directory)
    if manifest.get("format") != SHARDED_FORMAT:
        raise CheckpointError(
            f"{directory!r} is not a sharded checkpoint "
            f"(format {manifest.get('format')!r})")
    leaves, treedef = jax.tree_util.tree_flatten(target)
    specs = manifest["leaves"]
    if len(leaves) != len(specs):
        raise CheckpointError(
            f"target structure has {len(leaves)} leaves but the "
            f"checkpoint holds {len(specs)} — the model changed since "
            f"the save")
    out = list(leaves)
    world = int(manifest["world"])
    save_dir = os.path.join(directory, manifest["save_dir"])
    by_shard: dict = {}
    for i, s in enumerate(specs):
        if s["kind"] == "inline":
            out[i] = type(leaves[i])(s["value"]) \
                if leaves[i] is not None else s["value"]
        else:
            by_shard.setdefault(int(s["shard"]), []).append(i)
    for wr, idxs in sorted(by_shard.items()):
        path = os.path.join(save_dir, _shard_name(wr, world))
        with open(path, "rb") as f:
            blob = f.read()
        want = manifest["shard_digests"].get(str(wr))
        got = hashlib.sha256(blob).hexdigest()
        if want != got:
            raise CheckpointError(
                f"shard {os.path.basename(path)} digest mismatch "
                f"({got[:12]} != manifest {str(want)[:12]}) — torn or "
                f"foreign file")
        template = {str(i): np.zeros(tuple(specs[i]["shape"]),
                                     np.dtype(specs[i]["dtype"]))
                    for i in idxs}
        data = serialization.from_bytes(template, blob)
        for i in idxs:
            out[i] = data[str(i)]
    return jax.tree_util.tree_unflatten(treedef, out)


# -- serving checkpoints (hvd-serve, docs/inference.md) --------------------

SERVING_PARAMS_FILE = "params.msgpack"
SERVING_META_FILE = "serving.json"


def save_serving_checkpoint(directory: str, params: Any, cfg: Any,
                            tokenizer: str = "byte",
                            extra: Optional[dict] = None,
                            block: bool = False) -> CheckpointWrite:
    """Export a serving-ready checkpoint: the parameter pytree (flax
    msgpack, via the background writer) plus a ``serving.json`` carrying
    the model config and tokenizer metadata, so
    ``examples/serve_lm.py`` / :func:`load_serving_checkpoint` can
    build an :class:`~horovod_tpu.serving.engine.InferenceEngine` with
    no knowledge of the training script.  Rank-0 only, like every save
    (``examples/transformer_lm.py --export`` rides this)."""
    import json

    import jax.numpy as jnp

    if _state.is_initialized() and not _is_saving_process():
        return CheckpointWrite(None, performed=False)
    os.makedirs(directory, exist_ok=True)
    handle = write_tree_async(
        os.path.join(directory, SERVING_PARAMS_FILE),
        _host_snapshot(params))
    meta = {
        "format": "hvd-serving-checkpoint-v1",
        "model": {
            "vocab_size": cfg.vocab_size, "d_model": cfg.d_model,
            "n_heads": cfg.n_heads, "n_layers": cfg.n_layers,
            "d_ff": cfg.d_ff, "max_seq_len": cfg.max_seq_len,
            "num_experts": cfg.num_experts,
            "dtype": jnp.dtype(cfg.dtype).name,
        },
        "tokenizer": {"kind": tokenizer},
        "extra": extra or {},
    }
    _write_bytes(os.path.join(directory, SERVING_META_FILE),
                 json.dumps(meta, indent=1).encode())
    if block:
        handle.wait()
    return handle


def load_serving_checkpoint(directory: str):
    """Load a :func:`save_serving_checkpoint` export.  Returns
    ``(params, cfg, meta)`` — ``cfg`` a reconstructed
    :class:`~horovod_tpu.models.transformer.TransformerConfig`, ``meta``
    the raw ``serving.json`` dict (tokenizer kind, extras)."""
    import json

    import jax
    import jax.numpy as jnp
    from flax import serialization

    from ..models.transformer import TransformerConfig, init_transformer

    with open(os.path.join(directory, SERVING_META_FILE)) as f:
        meta = json.load(f)
    m = meta["model"]
    cfg = TransformerConfig(
        vocab_size=int(m["vocab_size"]), d_model=int(m["d_model"]),
        n_heads=int(m["n_heads"]), n_layers=int(m["n_layers"]),
        d_ff=int(m["d_ff"]), max_seq_len=int(m["max_seq_len"]),
        num_experts=int(m.get("num_experts", 0)),
        dtype=jnp.dtype(m.get("dtype", "float32")))
    template = init_transformer(jax.random.PRNGKey(0), cfg)
    with open(os.path.join(directory, SERVING_PARAMS_FILE), "rb") as f:
        params = serialization.from_bytes(template, f.read())
    return params, cfg, meta


def resume_epoch(path: str) -> int:
    """Determine the epoch to resume from and agree on it across replicas —
    the reference broadcasts this scalar explicitly
    (keras_imagenet_resnet50.py:47-56)."""
    epoch = 0
    if not _state.is_initialized() or _is_saving_process():
        wait_for_writes()
    step_file = f"{path}.step"
    if os.path.exists(step_file):
        with open(step_file) as f:
            epoch = int(f.read().strip())
    if _state.is_initialized():
        from ..ops import collective as C

        epoch = int(np.asarray(C.broadcast(
            np.asarray(epoch, np.int32), root_rank=0,
            name="resume_from_epoch")))
    return epoch
