"""Process launcher: ``python -m horovod_tpu.run -np N script.py [args...]``.

TPU-native stand-in for the reference's ``mpirun -np N python train.py``
launch recipe (reference: README.md:148-177 and the Travis CI legs,
.travis.yml:96-123).  Spawns N local worker processes wired together via
``jax.distributed`` (the ``HVD_TPU_*`` env contract in core/cluster.py);
each worker's stdout/stderr is prefixed with its rank, mpirun-style.

For multi-node jobs, run one ``python script.py`` per node under your
scheduler with HVD_TPU_COORDINATOR / HVD_TPU_NUM_PROCESSES /
HVD_TPU_PROCESS_ID exported — the same contract this launcher uses.

On a TPU host one process drives every chip (``-np 1``, or no launcher
at all): that is the usual deployment.  ``-np N`` with N > 1 gives each
worker ONE chip through libtpu's per-process visibility and bounds
variables, and therefore needs N to equal the host's chip count; any
other N refuses at launch, because without a binding every worker opens
every chip and all but the first die on the device lock.

``--elastic`` adds fault tolerance (≙ the post-v0.13 ``horovodrun``
elastic mode): the launcher supervises the workers and, when the job
fails — a worker crash, or a survivor exiting EX_TEMPFAIL(75) after
diagnosing a dead peer — tears the job down and relaunches it, up to
``--max-restarts`` times.  ``HVD_TPU_ELASTIC_DIR`` (exported to the
workers) carries the committed ``horovod_tpu.elastic.State`` across
incarnations, so training resumes from the last ``state.commit()``
rather than from scratch.
"""

from __future__ import annotations

import argparse
import glob
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time


def _free_ports(n: int) -> list:
    # Hold all sockets open while allocating so the kernel can't hand the
    # same ephemeral port out twice.
    socks = [socket.socket() for _ in range(n)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


# libtpu's process grid for one-chip-per-worker jobs, by the host's chip
# count.  Only the host shape this was run on (the 2x2 v5e host) is
# listed; another count refuses rather than guessing a topology.
_TPU_PROCESS_BOUNDS = {4: "2,2,1"}

# What a TPU VM image exports for the one-process-per-host layout; the
# per-worker binding below replaces them.
_TPU_HOST_LAYOUT_VARS = ("TPU_CHIPS_PER_HOST_BOUNDS", "TPU_HOST_BOUNDS",
                         "TPU_WORKER_ID", "TPU_WORKER_HOSTNAMES")


def _local_tpu_chips() -> int:
    """Chips on this host, counted from the device files (v5e:
    ``/dev/vfio/<n>``; earlier generations: ``/dev/accel<n>``).  The
    launcher must not ask jax: a process that has initialized the
    backend holds every chip it was about to hand out."""
    vfio = [p for p in glob.glob("/dev/vfio/*")
            if os.path.basename(p).isdigit()]
    return len(vfio) or len(glob.glob("/dev/accel*"))


def _tpu_worker_env(rank: int, ports: list) -> dict:
    """The libtpu variables that bind local worker ``rank`` to chip
    ``rank`` and join the workers into one slice over ICI.  The runtime
    then numbers the processes by the position of their chip in the
    slice, which ``/dev/vfio`` order need not follow: a worker's
    ``jax.process_index()`` may differ from its launch ``rank``."""
    return {
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": _TPU_PROCESS_BOUNDS[len(ports)],
        "TPU_PROCESS_ADDRESSES": ",".join(f"localhost:{p}"
                                          for p in ports),
        "TPU_PROCESS_PORT": str(ports[rank]),
        "TPU_VISIBLE_CHIPS": str(rank),
        "CLOUD_TPU_TASK_ID": str(rank),
    }


def _pump(stream, rank: int, out) -> None:
    for line in iter(stream.readline, b""):
        out.buffer.write(f"[{rank}] ".encode() + line)
        out.flush()


def _launch_once(args, extra_env=None) -> int:
    """One job incarnation: spawn N workers, forward output, wait.

    Returns the first nonzero worker exit code (0 when all succeed).
    A failed worker's surviving peers diagnose the death themselves and
    exit (ops/transport.py failure detection); ``--grace`` bounds how
    long the launcher waits for that before terminating stragglers.
    """
    # Reserve a distinct port for the eager-op controller up front; the
    # rendezvous-port+1 default could land on an in-use port.
    coord_port, controller_port, *tpu_ports = _free_ports(
        2 + (args.num_proc if args.bind_tpu else 0))
    procs = []
    pumps = []
    for rank in range(args.num_proc):
        env = dict(os.environ)
        env.update(extra_env or {})
        env["HVD_TPU_COORDINATOR"] = f"127.0.0.1:{coord_port}"
        env["HVD_TPU_CONTROLLER_PORT"] = str(controller_port)
        env["HVD_TPU_NUM_PROCESSES"] = str(args.num_proc)
        env["HVD_TPU_PROCESS_ID"] = str(rank)
        if args.platform:
            env["JAX_PLATFORMS"] = args.platform
        if args.bind_tpu:
            for name in _TPU_HOST_LAYOUT_VARS:
                env.pop(name, None)
            env.update(_tpu_worker_env(rank, tpu_ports))
        # -u: a worker that dies abruptly (or is torn down by the JAX
        # coordination service) must not lose block-buffered output —
        # mpirun's stdout forwarding has the same property.
        p = subprocess.Popen([sys.executable, "-u"] + args.command, env=env,
                             stdout=subprocess.PIPE,
                             stderr=subprocess.STDOUT)
        procs.append(p)
        t = threading.Thread(target=_pump, args=(p.stdout, rank, sys.stdout),
                             daemon=True)
        t.start()
        pumps.append(t)

    rc = 0
    try:
        deadline = None
        # Poll EVERY worker each tick: any(...) would short-circuit at
        # the first live process and never set returncode on the ranks
        # behind it, so a crash behind a blocked rank 0 would go
        # undetected and the grace window would never arm.
        while None in [p.poll() for p in procs]:
            if rc == 0:
                rc = next((p.returncode for p in procs
                           if p.returncode not in (None, 0)), 0)
                if rc and args.grace > 0:
                    deadline = time.monotonic() + args.grace
            if deadline is not None and time.monotonic() > deadline:
                for p in procs:
                    if p.poll() is None:
                        p.terminate()
                for p in procs:
                    try:
                        p.wait(timeout=10.0)
                    except subprocess.TimeoutExpired:
                        p.kill()
                        p.wait()
                break
            time.sleep(0.2)
        for p in procs:
            if p.returncode is None:
                p.wait()
            rc = rc or (p.returncode or 0)
    except KeyboardInterrupt:
        for p in procs:
            p.send_signal(signal.SIGTERM)
        rc = 130
    for t in pumps:
        t.join(timeout=2.0)
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m horovod_tpu.run",
        description="Launch N cooperating horovod_tpu processes locally.")
    ap.add_argument("-np", "--num-proc", type=int, required=True)
    ap.add_argument("--platform", default=None,
                    help="force a JAX platform for workers (e.g. cpu)")
    ap.add_argument("--elastic", action="store_true",
                    help="relaunch the job on worker failure, resuming "
                         "from the last horovod_tpu.elastic.State commit")
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="elastic mode: maximum relaunches before giving "
                         "up (default 3)")
    ap.add_argument("--elastic-dir", default=None,
                    help="directory carrying committed elastic state "
                         "across incarnations (default: a fresh temp dir)")
    ap.add_argument("--grace", type=float, default=60.0,
                    help="seconds to let surviving workers diagnose a "
                         "peer failure and exit before the launcher "
                         "terminates them (0 disables)")
    ap.add_argument("command", nargs=argparse.REMAINDER,
                    help="script (and args) to run in each process")
    args = ap.parse_args(argv)
    if not args.command:
        ap.error("missing script to launch")

    platform = (args.platform
                or os.environ.get("JAX_PLATFORMS", "")).split(",")[0]
    chips = _local_tpu_chips() if platform in ("", "tpu") else 0
    args.bind_tpu = args.num_proc > 1 and chips > 0
    if args.bind_tpu and (args.num_proc != chips
                          or chips not in _TPU_PROCESS_BOUNDS):
        ap.error(
            f"-np {args.num_proc} on a host with {chips} TPU chip(s): "
            f"workers are bound one chip each, which needs -np equal to "
            f"the chip count (supported: "
            f"{sorted(_TPU_PROCESS_BOUNDS)}); unbound, every worker "
            f"opens every chip and all but the first fail on the device "
            f"lock. Use -np 1 (one process drives all chips), or "
            f"--platform cpu.")

    if not args.elastic:
        return _launch_once(args)

    elastic_dir = args.elastic_dir or tempfile.mkdtemp(
        prefix="hvd_tpu_elastic_")
    extra = {"HVD_TPU_ELASTIC": "1", "HVD_TPU_ELASTIC_DIR": elastic_dir}
    for attempt in range(args.max_restarts + 1):
        rc = _launch_once(args, extra)
        if rc == 0:
            return 0
        if rc == 130:  # Ctrl+C is the user stopping the job, not a failure
            return rc
        if attempt == args.max_restarts:
            print(f"[elastic] giving up after {attempt} restart(s): "
                  f"rc={rc}", file=sys.stderr)
            return rc
        print(f"[elastic] job failed (rc={rc}); relaunching from the "
              f"last commit in {elastic_dir} "
              f"(restart {attempt + 1}/{args.max_restarts})",
              file=sys.stderr, flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
