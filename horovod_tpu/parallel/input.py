"""Double-buffered device prefetch — the input half of host-stall
elimination (hvd-pipeline).

PR 2 deleted the per-step control-plane cost and PR 3 the data-plane
dispatch cost, which leaves the *host* as the steady-state bound: a
train loop that calls ``shard_batch(next(loader))`` serializes three
things that could overlap — the loader producing batch N+1, the
host→device transfer of batch N+1, and the device computing step N.
That is exactly the input-pipeline stall the original Horovod paper's
throughput methodology assumes away with synthetic data
(arXiv:1802.05799 §5) and that production input pipelines hide with
prefetch queues.

:func:`prefetch_to_device` wraps any host batch iterator in a
background stager: while step N computes, the stager pulls batch N+1
from the loader and places it on the mesh with ONE batched
``jax.device_put`` over the whole pytree (correct ``NamedSharding`` per
leaf), parking the device-resident batch in a bounded queue.  The
consuming loop's ``next()`` then returns arrays that are already on
device — combined with the async-dispatch loop (deferred metric
fetches, ``hvd.barrier_fence()`` for explicit completion points) the
TPU never waits for the host in steady state.

Contract:

* **Ordering** — batches come out in exactly the loader's order.
* **Bounded** — at most ``depth`` staged batches exist at once (plus
  the one the loader is currently producing); depth 2 is classic
  double buffering.
* **Exceptions** — a loader exception is captured on the stager thread
  and re-raised at the consuming step WITH the original traceback; the
  flight recorder logs it (``prefetch_error``) so a crashed input
  pipeline is forensically visible.
* **Clean shutdown** — ``close()`` (also via context manager / ``for``
  loop exhaustion / garbage collection) stops the stager, closes a
  generator loader, and joins the thread, even mid-epoch with a full
  queue.

Telemetry (docs/metrics.md): ``host.stall_seconds`` (histogram — time
the consumer blocked waiting on the queue, i.e. the stall the prefetch
failed to hide), ``input.batches_staged`` (counter) and
``input.prefetch_queue_depth`` (gauge).
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Iterable, Iterator, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import chaos as _chaos
from .. import telemetry as _telemetry
from .. import trace as _trace
from ..analysis import threads as _analysis_threads
from ..core import state as _state
from ..core.state import REPLICA_AXIS
from ..memory import ledger as _mem
from ..telemetry import flight as _flight

_M_STALL = _telemetry.histogram(
    "host.stall_seconds", "seconds",
    "time the training loop blocked waiting on the input queue")
_R_WAIT = _trace.region("prefetch.wait", "host", timed=True)
_M_STAGED = _telemetry.counter(
    "input.batches_staged", "batches staged host->device by prefetchers")
_M_DEPTH = _telemetry.gauge(
    "input.prefetch_queue_depth", "device-resident batches currently staged")

# Queue sentinels (identity-compared).
_END = object()


class _Staged:
    """One staged device batch plus its ledger charge.  A wrapper
    class, not a tuple — user batches may themselves be tuples, and the
    consumer must distinguish them from the bookkeeping by type."""

    __slots__ = ("batch", "nbytes")

    def __init__(self, batch, nbytes: int) -> None:
        self.batch = batch
        self.nbytes = nbytes


def _shardings_for(batch: Any, mesh, sharding) -> Any:
    """Resolve the per-leaf shardings for one batch pytree.

    ``sharding`` may be None (split the leading axis over the replica
    axis — the data-parallel default), a single ``NamedSharding`` /
    ``PartitionSpec`` applied to every leaf, or a pytree of either
    matching the batch structure."""
    if sharding is None:
        sharding = NamedSharding(mesh, P(REPLICA_AXIS))
    def to_sharding(s):
        return NamedSharding(mesh, s) if isinstance(s, P) else s
    if isinstance(sharding, (NamedSharding, P)):
        s = to_sharding(sharding)
        return jax.tree_util.tree_map(lambda _: s, batch)
    return jax.tree_util.tree_map(lambda _x, s: to_sharding(s),
                                  batch, sharding)


def device_put_batch(batch: Any, mesh=None, sharding=None) -> Any:
    """Place one host batch onto the mesh with a single batched
    ``jax.device_put`` call over the whole pytree (one transfer program,
    not one dispatch per leaf — the satellite fix PR 5 applies to
    ``shard_batch``/``replicate``/``shard_parallel_batch`` too)."""
    mesh = mesh or _state.mesh()
    return jax.device_put(batch, _shardings_for(batch, mesh, sharding))


class PrefetchIterator:
    """Iterator returned by :func:`prefetch_to_device`.

    Iterates device-resident batches; supports ``len()`` pass-through
    is intentionally absent (the loader's length is unknowable in
    general).  Use as a context manager — or just break/close — for
    deterministic mid-epoch shutdown."""

    def __init__(self, iterable: Iterable, mesh, depth: int,
                 sharding) -> None:
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self._mesh = mesh
        self._depth = depth
        self._sharding = sharding
        self._source = iterable
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._stage_loop, name="hvd-prefetch", daemon=True)
        self._thread.start()

    # -- stager thread -----------------------------------------------------
    def _stage_loop(self) -> None:  # thread: stager
        _analysis_threads.set_role("stager")
        it = iter(self._source)
        try:
            while not self._stop.is_set():
                try:
                    host_batch = next(it)
                except StopIteration:
                    self._put(_END)
                    return
                # hvd-chaos input.stall: a loader/filesystem hiccup on
                # the stager thread.  The contract under injection: the
                # consumer sees added latency (host.stall_seconds), the
                # batch ORDER and VALUES never change — training stays
                # bitwise-identical to the fault-free run.
                if _chaos.active():
                    _chaos.sleep_site("input.stall")
                staged = device_put_batch(host_batch, self._mesh,
                                          self._sharding)
                _M_STAGED.inc()
                # hvd-mem: a staged batch is framework-held HBM until
                # the consumer takes it — charge the ledger for its
                # queue residency (released at __next__/close).
                nb = _mem.tree_nbytes(staged) if _mem.enabled() else 0
                if nb:
                    _mem.ledger.alloc("input.prefetch", nb)
                if not self._put(_Staged(staged, nb)):
                    _mem.ledger.free("input.prefetch", nb)
                    return
        except BaseException as e:  # noqa: BLE001 — carried to consumer
            _telemetry.prefetch_error_event(
                f"{type(e).__name__}: {e}")
            self._put(e)
        finally:
            close = getattr(it, "close", None)
            if close is not None:
                try:
                    close()
                except Exception:  # noqa: BLE001 — shutdown best-effort
                    pass

    def _put(self, item) -> bool:
        """Bounded put that stays responsive to close(); returns False
        when the iterator shut down before the item was accepted."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                _M_DEPTH.set(self._q.qsize())
                return True
            except queue.Full:
                continue
        return False

    # -- consumer side -----------------------------------------------------
    def __iter__(self) -> "PrefetchIterator":
        return self

    def __next__(self):
        if self._stop.is_set():
            raise StopIteration
        try:
            item = self._q.get_nowait()
        except queue.Empty:
            # The stall the prefetch could not hide: the loader (or the
            # transfer) is slower than the step.  One pair of clock
            # reads, blocked path only: the prefetch.wait region's,
            # which host.stall_seconds reads too — the analyzer's
            # "this rank was input-bound" signal, the blame category a
            # seeded slow loader must surface under (docs/tracing.md).
            # Timed gets so a close() from another thread (which
            # enqueues nothing) wakes this consumer too.
            stopped = False
            with _R_WAIT() as r:
                while True:
                    try:
                        item = self._q.get(timeout=0.05)
                        break
                    except queue.Empty:
                        if self._stop.is_set():
                            stopped = True
                            r.cancel()
                            break
            _M_STALL.observe(r.seconds)
            if stopped:
                raise StopIteration from None
        _M_DEPTH.set(self._q.qsize())
        if item is _END:
            self._stop.set()
            raise StopIteration
        if isinstance(item, BaseException):
            self._stop.set()
            # Re-raise ON the consumer thread with the stager-side
            # traceback intact (the exception object carries it).
            raise item
        if item.nbytes:
            _mem.ledger.free("input.prefetch", item.nbytes)
        return item.batch

    def close(self) -> None:
        """Stop the stager and join it.  Safe mid-epoch with a full
        queue (the stager's bounded put polls the stop flag), safe to
        call twice, safe from ``__del__``."""
        self._stop.set()
        # Unblock a stager parked in put() by draining; it re-checks the
        # stop flag within its put timeout either way.  Drained staged
        # batches release their ledger charge — a mid-epoch shutdown
        # must not read as a prefetch leak.
        try:
            while True:
                item = self._q.get_nowait()
                if isinstance(item, _Staged) and item.nbytes:
                    _mem.ledger.free("input.prefetch", item.nbytes)
        except queue.Empty:
            pass
        if self._thread.is_alive():
            self._thread.join(timeout=5.0)
        # Drain AGAIN after the join: a stager parked inside its bounded
        # put() can land one final charged batch in the window between
        # the drain above emptying the queue and the stop-flag re-check
        # — the put succeeds, the stager exits without freeing, and the
        # charge would leak into whichever test asserts the
        # "input.prefetch" category drains to zero.
        try:
            while True:
                item = self._q.get_nowait()
                if isinstance(item, _Staged) and item.nbytes:
                    _mem.ledger.free("input.prefetch", item.nbytes)
        except queue.Empty:
            pass
        _M_DEPTH.set(0)

    def __enter__(self) -> "PrefetchIterator":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self) -> None:  # pragma: no cover - gc timing
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass


def prefetch_to_device(iterable: Iterable, mesh=None, depth: int = 2,
                       sharding=None) -> PrefetchIterator:
    """Stage host batches onto the mesh ahead of consumption.

    Args:
      iterable: host batch source — any iterable/iterator/generator
        yielding pytrees of arrays (one GLOBAL batch per item, leading
        axis divisible by the replica count under the default
        sharding).
      mesh: target mesh; defaults to the global replica mesh.
      depth: bound on staged batches (2 = double buffering: batch N+1
        transfers while step N computes).
      sharding: per-leaf placement — None for the data-parallel default
        (leading axis split over ``"hvd"``), or a ``PartitionSpec`` /
        ``NamedSharding`` / pytree of either (the multi-axis
        ``shard_parallel_batch`` layouts).

    Returns a :class:`PrefetchIterator` yielding device-resident
    batches in loader order.  Loader exceptions re-raise at the
    consuming ``next()`` with the original traceback.
    """
    mesh = mesh or _state.mesh()
    return PrefetchIterator(iterable, mesh, depth, sharding)


__all__ = [
    "PrefetchIterator",
    "device_put_batch",
    "prefetch_to_device",
]
