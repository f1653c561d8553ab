"""Multi-process cluster bootstrap.

TPU-native equivalent of the reference's MPI bootstrap
(``MPI_Init_thread`` + ``MPI_Comm_rank/size`` + the SHARED-memory
communicator split — reference: horovod/common/operations.cc:1173-1196).
The launcher (``python -m horovod_tpu.run``, ≙ ``mpirun -np N``) exports
the ``HVD_TPU_*`` variables below; ``maybe_initialize()`` turns them into
a ``jax.distributed`` cluster, after which every process sees the global
device topology and jitted collectives run SPMD across processes.

Environment contract (set by the launcher, overridable by schedulers):

  HVD_TPU_COORDINATOR      host:port of the jax.distributed rendezvous
  HVD_TPU_NUM_PROCESSES    world size
  HVD_TPU_PROCESS_ID       this process's rank
  HVD_TPU_CONTROLLER_PORT  TCP port of the rank-0 eager-op controller
                           (defaults to rendezvous port + 1)
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional


@dataclass(frozen=True)
class ClusterSpec:
    coordinator: str          # host:port for jax.distributed
    num_processes: int
    process_id: int

    @property
    def controller_host(self) -> str:
        return self.coordinator.rsplit(":", 1)[0]

    @property
    def controller_port(self) -> int:
        port = os.environ.get("HVD_TPU_CONTROLLER_PORT")
        if port:
            return int(port)
        if ":" in self.coordinator:
            return int(self.coordinator.rsplit(":", 1)[1]) + 1
        return 29521


def cluster_spec_from_env() -> Optional[ClusterSpec]:
    """Read the launcher contract; None when running single-process."""
    addr = (os.environ.get("HVD_TPU_COORDINATOR")
            or os.environ.get("JAX_COORDINATOR_ADDRESS"))
    n = (os.environ.get("HVD_TPU_NUM_PROCESSES")
         or os.environ.get("JAX_NUM_PROCESSES"))
    pid = (os.environ.get("HVD_TPU_PROCESS_ID")
           or os.environ.get("JAX_PROCESS_ID"))
    if not (addr and n and pid):
        return None
    return ClusterSpec(coordinator=addr, num_processes=int(n),
                       process_id=int(pid))


# Set by disarm_distributed_shutdown: a peer died, the jax.distributed
# client was abandoned, and this process can only exit.
_disarmed = False


def maybe_initialize() -> Optional[ClusterSpec]:
    """Initialize ``jax.distributed`` when a cluster env is present.

    Idempotent: if the user already called ``jax.distributed.initialize``
    (or a previous ``hvd.init()`` did), this is a no-op that still reports
    the spec.  Returns None in single-process mode.
    """
    import jax

    if _disarmed:
        raise RuntimeError(
            "horovod_tpu cannot re-initialize: a peer process died and "
            "the jax.distributed cluster was abandoned. Restart the job "
            "(e.g. relaunch via `python -m horovod_tpu.run`).")
    spec = cluster_spec_from_env()
    if spec is None:
        # The user may have initialized jax.distributed directly; honor it.
        # (is_initialized() does not touch the XLA backend.)
        if jax.distributed.is_initialized() and jax.process_count() > 1:
            return ClusterSpec(
                coordinator=os.environ.get("JAX_COORDINATOR_ADDRESS", ""),
                num_processes=jax.process_count(),
                process_id=jax.process_index())
        return None
    if spec.num_processes > 1 and not jax.distributed.is_initialized():
        jax.distributed.initialize(
            coordinator_address=spec.coordinator,
            num_processes=spec.num_processes,
            process_id=spec.process_id,
            heartbeat_timeout_seconds=int(
                os.environ.get("HVD_TPU_HEARTBEAT_TIMEOUT", "100")),
            shutdown_timeout_seconds=int(
                os.environ.get("HVD_TPU_SHUTDOWN_TIMEOUT", "300")))
    return spec


def disarm_distributed_shutdown() -> None:
    """Skip ``jax.distributed``'s exit-time shutdown barrier.

    JAX registers an atexit hook (jax/_src/api.py ``clean_up``) that calls
    ``jax.distributed.shutdown()``, which enters a coordination-service
    barrier waiting for EVERY process.  Once we know a peer died without
    reaching that barrier, it can only fail — after blocking the survivor
    for ``heartbeat_timeout_seconds`` (100 s default) and then fatally
    aborting the process (client.h LOG(FATAL)), which also discards
    buffered output.  The reference's equivalent failure mode is an MPI
    job hanging in MPI_Finalize until the scheduler kills it.

    Dropping the client reference makes that atexit hook a no-op so the
    survivor can exit promptly with its diagnosis.  The coordination
    *service* (rank 0 hosts it) is left in place — its shutdown does not
    block on peers.

    After this, the process is expected to exit: the cluster is missing a
    member and cannot be re-formed from within (``jax.distributed`` does
    not support re-initialization), so ``maybe_initialize`` refuses with
    a diagnosis instead of letting jax raise an opaque error.
    """
    global _disarmed
    _disarmed = True
    try:
        from jax._src import distributed as _jd

        state = _jd.global_state
        if getattr(state, "preemption_sync_manager", None) is not None:
            state.preemption_sync_manager.shutdown()
            state.preemption_sync_manager = None
        state.client = None  # leaked deliberately; the process is exiting
    except Exception:  # noqa: BLE001 — the process is exiting either way
        pass
