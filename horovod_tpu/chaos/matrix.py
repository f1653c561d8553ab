"""The hvd-chaos scenario matrix: the fleet-wide no-hang contract.

``python -m horovod_tpu.chaos --matrix`` runs every scenario below
under a hard per-scenario wall-clock cap and enforces, for each:

* **recover** — the faulted run exits 0 and its ``CHAOS_RESULT``
  digests are IDENTICAL to a fault-free run of the same scenario
  (full recovery, bitwise);
* **diagnostic** — the faulted run ends (within the cap) with a
  nonzero exit AND its output names the injected fault
  (``needle``) — a bounded, diagnosable failure;
* **complete** — a single pass that must simply finish cleanly under
  load (no fault spec; e.g. the request storm).

A run that is still alive at the cap is killed and reported as HANG —
the contract violation this matrix exists to catch.  Every scenario's
fault sequence is deterministic (chaos/spec.py), so a failure
reproduces from the scenario's spec line alone.

Scenario kinds:

* ``cp`` — an np=2/np=3 REAL-process control-plane fleet: one
  controller + workers driving the actual ControllerTransport /
  WorkerTransport / Coordinator / ResponseCache over TCP loopback
  with a drain loop mirroring ops/collective._drain's transport
  sequencing.  This exercises the reconnect protocol, replay rings,
  grace windows, frame deadlines and cache-replica alignment with
  real sockets and real processes — no XLA, so it runs in any
  container (np>1 CPU data-plane collectives need a current jax; the
  CI-gated ``scenario_chaos`` mp leg covers the full-stack training
  variant).  The digest covers every completed negotiation
  ``(step, tensor, response type)`` per rank.
* ``local`` — a single-process scenario with the real jax stack
  (checkpoint writer, prefetch training loop, serving front door);
  digests cover real bytes (checkpoint content, trained parameters,
  generated tokens).
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import socket
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

# ---------------------------------------------------------------------------
# The matrix
# ---------------------------------------------------------------------------


@dataclass
class Scenario:
    name: str
    kind: str                  # "cp" | "local"
    expect: str                # "recover" | "diagnostic" | "complete"
    spec: str = ""             # HVD_TPU_FAULTS for the faulted pass
    needle: str = ""           # substring the faulted output must show
    np: int = 2                # cp: process count
    cap: float = 90.0          # wall-clock cap per pass (seconds)
    env: Dict[str, str] = field(default_factory=dict)
    doc: str = ""


SCENARIOS: List[Scenario] = [
    # -- transport (ops/transport.py): the reconnect protocol ------------
    Scenario(
        "transport_reset_worker", "cp", "recover",
        spec="transport.reset:count=1:after=25:rank=1@11",
        needle="session resumed",
        doc="worker's control-plane connection reset mid-run; "
            "reconnect + ring replay; results identical"),
    Scenario(
        "transport_reset_np3", "cp", "recover", np=3,
        spec="transport.reset:count=1:after=25:rank=2@12",
        needle="session resumed", cap=120.0,
        doc="np=3: one of two workers resets; the other is "
            "undisturbed; results identical on all three"),
    Scenario(
        "transport_reset_controller", "cp", "recover",
        spec="transport.reset:count=1:after=25:rank=0@13",
        needle="session resumed",
        doc="controller-side reset of a worker's socket (send path); "
            "grace + reconnect; results identical"),
    Scenario(
        "transport_trunc", "cp", "recover",
        spec="transport.trunc:count=1:after=20:rank=1@14",
        needle="session resumed",
        doc="frame truncated mid-wire then connection reset; the "
            "replay ring re-sends the full frame"),
    Scenario(
        "transport_dup_delay", "cp", "recover",
        spec="transport.dup:count=3:after=10:rank=1;"
             "transport.delay:count=5:after=12:delay=0.05:rank=1@15",
        doc="duplicated + delayed frames; the stream survives "
            "(duplicate REQUEST_BATCH submits are idempotent)"),
    Scenario(
        "transport_drop", "cp", "diagnostic",
        spec="transport.drop:count=1:after=20:rank=1@16",
        needle="was abandoned",
        doc="a silently dropped frame (no reset, so no reconnect): "
            "bounded failure via the withdraw path, naming the op"),
    Scenario(
        "transport_stall", "cp", "recover",
        spec="transport.stall:count=1:after=20:delay=3:rank=1@17",
        needle="frame deadline exceeded",
        env={"HVD_TPU_FRAME_TIMEOUT": "1"},
        doc="slow peer stalls mid-frame past HVD_TPU_FRAME_TIMEOUT: "
            "the deadline names peer+frame, then reconnect recovers"),
    Scenario(
        "grace_expiry", "cp", "diagnostic",
        needle="no reconnect within",
        env={"HVD_TPU_CHAOS_KILL_STEP": "12",
             "HVD_TPU_RECONNECT_GRACE": "1.5"},
        doc="worker dies hard (no reconnect ever comes): the grace "
            "window expires into a diagnostic naming the fault"),
    # -- tree overlay (ops/tree.py): interior-node death + re-parent -----
    Scenario(
        "tree_interior_down", "cp", "recover", np=3, cap=150.0,
        spec="tree.relay_reset:count=1:after=40:rank=1;"
             "transport.reset:count=1:after=30:rank=1@31",
        needle="re-parent",
        env={"HVD_TPU_TREE": "on", "HVD_TPU_TREE_FANOUT": "1",
             "HVD_TPU_RECONNECT_GRACE": "15",
             "HVD_TPU_RECONNECT_DEADLINE": "15"},
        doc="np=3 chain 0<-1<-2: BOTH of the interior's links die "
            "(uplink reset + child-link relay reset); rank 1 resumes "
            "its uplink, rank 2 re-parents to the root via the "
            "session-resume listener; results (and the mid-run fleet "
            "metrics pull) identical to the fault-free tree run"),
    Scenario(
        "tree_leaf_reset", "cp", "recover", np=3, cap=150.0,
        spec="transport.reset:count=1:after=25:rank=2@32",
        needle="session resumed",
        env={"HVD_TPU_TREE": "on", "HVD_TPU_TREE_FANOUT": "1",
             "HVD_TPU_RECONNECT_GRACE": "15",
             "HVD_TPU_RECONNECT_DEADLINE": "15"},
        doc="np=3 chain: the LEAF's uplink to its interior parent is "
            "reset; it re-parents to the root and the stream replay "
            "keeps every cache replica aligned"),
    # -- hvd-tune actuation (tuning/actuation.py) ------------------------
    Scenario(
        "retune_midfault", "cp", "recover",
        spec="transport.reset:count=1:after=26:rank=1@33",
        needle="session resumed",
        env={"HVD_TPU_CHAOS_RETUNE_STEPS": "10,25"},
        doc="hvd-tune RETUNE markers ride the response stream at steps "
            "10 and 25; the worker's connection resets in the window "
            "between a marker's broadcast and its apply boundary — the "
            "session-resume replay must deliver the marker exactly "
            "once (records identical to the fault-free pass: never "
            "lost, never double-applied, fleet-coherent)"),
    # -- coordinator drain loop (ops/collective.py) ----------------------
    Scenario(
        "coord_tick_delay", "cp", "recover", cap=120.0,
        spec="coord.tick_delay:p=0.4:count=20:delay=0.03@18",
        doc="randomly starved drain ticks; slower, never different"),
    Scenario(
        "coord_reorder", "cp", "recover",
        spec="coord.reorder:p=0.5:count=50@19",
        doc="freshly negotiated responses permuted within their tick; "
            "completion set identical"),
    # -- checkpoint writer (utils/checkpoint.py) -------------------------
    Scenario(
        "ckpt_flaky", "local", "recover", cap=240.0,
        spec="ckpt.oserror:count=2@20",
        needle="retrying",
        doc="two transient ENOSPC during the tmp write; the retry "
            "loop lands the identical bytes"),
    Scenario(
        "ckpt_exhaustion", "local", "diagnostic", cap=240.0,
        spec="ckpt.oserror:count=9@21",
        needle="ckpt.oserror",
        env={"HVD_TPU_CKPT_RETRIES": "3"},
        doc="persistent write failure exhausts the retries: "
            "CheckpointError at wait() names the injected fault"),
    # -- prefetch stager (parallel/input.py) -----------------------------
    Scenario(
        "input_stall", "local", "recover", cap=240.0,
        spec="input.stall:count=3:after=2:delay=0.2@22",
        doc="loader stalls on the stager thread; training result "
            "bitwise-identical (prefetch hides latency, never "
            "reorders)"),
    # -- serving front door (serving/server.py) --------------------------
    Scenario(
        "serving_disconnect", "local", "recover", cap=300.0,
        spec="serving.disconnect:count=1@23",
        needle="disconnected mid-generation",
        doc="client vanishes mid-generate: slot released via the "
            "abort path; the NEXT request's completion is identical "
            "to the fault-free run's"),
    Scenario(
        "serving_storm", "local", "complete", cap=300.0,
        doc="a burst of concurrent /generate requests: every one "
            "completes or fails explicitly — the front door never "
            "hangs"),
    Scenario(
        "serving_spec_disconnect", "local", "recover", cap=300.0,
        spec="serving.disconnect:count=1@27",
        needle="disconnected mid-generation",
        doc="client vanishes mid-SPECULATION (draft model + prefix "
            "cache live): the iteration-boundary abort releases the "
            "target AND draft KV slots and decrements the prefix "
            "refcounts; the follow-up request (a prefix-cache hit) "
            "completes identically to the fault-free run"),
    # -- hvd-route fleet router (routing/router.py) ----------------------
    Scenario(
        "router_replica_death", "local", "recover", cap=300.0,
        spec="router.replica_kill:count=1@40",
        needle="failed over",
        doc="two real replicas behind the real Router over real HTTP; "
            "the one that served the first request is drained and "
            "then killed hard — dispatch fails over to the survivor "
            "and every completion is identical to the fault-free "
            "fleet's"),
    Scenario(
        "router_restart", "local", "recover", cap=300.0,
        spec="router.kill:count=1@41",
        needle="severed router connection",
        doc="the real RouterServer runs as a separate PROCESS and is "
            "SIGKILLed mid-generation; the replicas abort the severed "
            "sockets via the client probe (no slot leak), a fresh "
            "router over the same fleet serves the resubmitted "
            "request, and completions are identical to the never-"
            "killed run (the bitwise contract makes the retry the "
            "same answer)"),
]


def find(name: str) -> Scenario:
    for s in SCENARIOS:
        if s.name == name:
            return s
    raise SystemExit(f"unknown chaos scenario {name!r}; "
                     f"--list shows the matrix")


# ---------------------------------------------------------------------------
# Shared helpers
# ---------------------------------------------------------------------------

def _digest(records) -> str:
    """Order-insensitive digest of a run's completion records; the
    recover contract compares it between the faulted and fault-free
    passes."""
    blob = json.dumps(sorted(map(list, records))).encode()
    return hashlib.sha256(blob).hexdigest()[:24]


def _result(rank: int, records) -> None:
    print(f"CHAOS_RESULT rank={rank} n={len(records)} "
          f"digest={_digest(records)}", flush=True)


def _diag(rank: int, message: str) -> None:
    print(f"CHAOS_DIAG rank={rank}: {message}", file=sys.stderr,
          flush=True)
    sys.stdout.flush()
    raise SystemExit(1)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ---------------------------------------------------------------------------
# cp nodes: a real-process control-plane fleet (no XLA)
# ---------------------------------------------------------------------------

CP_TENSORS = 4
CP_STEP_DEADLINE = 8.0
_THRESHOLD = 1 << 20


def _cp_steps() -> int:
    """Steps per cp pass (env-overridable so the tier-1 tree leg can
    run a short fleet; the matrix default stays 40)."""
    return int(os.environ.get("HVD_TPU_CHAOS_CP_STEPS", "40"))


def _cp_layout(np_: int):
    """The tree layout the cp fleet runs under, or None for the flat
    star — the SAME decision rule production init applies
    (ops/tree.tree_active), so HVD_TPU_TREE=on in a scenario's env
    turns the whole fleet into tree mode."""
    from ..ops import tree as _tree

    return _tree.build_layout(np_) if _tree.tree_active(np_) else None


def _cp_req(rank: int, name: str):
    from ..ops import wire
    from ..ops.wire import Request

    return Request(rank, wire.RequestType.ALLREDUCE,
                   wire.DataType.FLOAT32, name, -1, -1, (8,),
                   wire.ReduceOp.SUM, 0, ())


def _cp_names() -> List[str]:
    return [f"t{k}" for k in range(CP_TENSORS)]


# hvd-mem: every cp rank seeds a rank-keyed ledger entry at fleet
# start, so the mid-run FRAME_METRICS / FRAME_METRICS_TREE pull can
# assert the memory gauge family aggregates EXACTLY — per-rank values
# from every rank, fleet min/max/mean bit-for-bit (tests/test_tree.py
# extends its metrics-pull leg over this).
MEM_PROBE_GAUGE = "memory.bytes.chaos.probe"


def _seed_mem_probe(rank: int) -> None:
    from ..memory import ledger as _mem

    _mem.ledger.set("chaos.probe", (rank + 1) << 20)


def _check_mem_gauges(snaps, np_: int) -> None:
    """Controller-side exactness assertion over one completed pull:
    the seeded probe gauge must arrive from EVERY rank with its exact
    per-rank value, and the fleet min/max/mean must be exact integers
    of the seeded arithmetic — any drop or mangling through the tree
    merge is a loud _diag, not a silent coverage gap."""
    from .. import telemetry as _telemetry

    agg = _telemetry.aggregate(snaps).get(MEM_PROBE_GAUGE)
    if agg is None:
        _diag(0, f"metrics pull carried no {MEM_PROBE_GAUGE} gauge "
                 f"(snapshot keys: "
                 f"{sorted(next(iter(snaps.values())))[:8]}...)")
    expect = {r: (r + 1) << 20 for r in range(np_)}
    got = {int(r): int(v) for r, v in agg.get("per_rank", {}).items()}
    if got != expect:
        _diag(0, f"{MEM_PROBE_GAUGE} per-rank mismatch: got {got}, "
                 f"expected {expect}")
    exact = {"min": 1 << 20, "max": np_ << 20,
             "mean": ((np_ + 1) << 20) / 2.0,
             "sum": (np_ * (np_ + 1) // 2) << 20}
    for key, want in exact.items():
        if agg.get(key) != want:
            _diag(0, f"{MEM_PROBE_GAUGE} {key} inexact: "
                     f"{agg.get(key)} != {want}")
    print(f"CHAOS_MEMGAUGES ranks={np_} ok", flush=True)


def run_cp_controller(np_: int, port: int) -> None:
    """Rank 0 of the cp fleet: the real ControllerTransport +
    Coordinator + ResponseCache, driven by a drain loop mirroring
    ops/collective._drain's transport sequencing (expire_grace →
    lost_ranks → flush_unrouted → marker/replay/negotiated →
    broadcast → observe)."""
    from .. import chaos as _chaos
    from ..ops import cache as _cache_mod
    from ..ops import transport as T
    from ..ops.coordinator import Coordinator
    from ..ops.wire import Response, ResponseType

    _seed_mem_probe(0)
    cache = (_cache_mod.ResponseCache(rank=0)
             if _cache_mod.cache_enabled() else None)
    coord = Coordinator(size=np_, fusion_threshold=_THRESHOLD,
                        cache=cache)
    ctrl = T.ControllerTransport(coord, np_, port, tree=_cp_layout(np_))
    ctrl.cache = cache
    records = []
    # hvd-tune: the retune_midfault scenario injects RETUNE markers at
    # fixed steps (HVD_TPU_CHAOS_RETUNE_STEPS); they ride the same
    # broadcast as production markers (ops/collective._coordinator_tick)
    # and every rank records (seq, token, RETUNE) on delivery — digest
    # equality with the fault-free pass proves exactly-once,
    # fleet-coherent application across the fault.
    retune_steps = {int(v) for v in
                    os.environ.get("HVD_TPU_CHAOS_RETUNE_STEPS",
                                   "").replace(";", ",").split(",")
                    if v.strip()}
    retune_pending: List = []
    retune_seq = 0

    def tick() -> List:
        if _chaos.active():
            _chaos.sleep_site("coord.tick_delay")
        ctrl.expire_grace()
        if ctrl.lost_ranks:
            lost = sorted(ctrl.lost_ranks)
            why = "; ".join(
                f"rank {r}: {ctrl.lost_reasons[r]}" for r in lost
                if r in ctrl.lost_reasons) or "terminated unexpectedly"
            ctrl.broadcast_responses([Response(
                ResponseType.SHUTDOWN,
                error_message=f"rank(s) {lost} lost: {why}")])
            _diag(0, f"rank(s) {lost} lost: {why}")
        ctrl.flush_unrouted()
        marker = cache.take_flush_marker() if cache is not None else None
        if cache is not None:
            replayed, groups, epoch, compact = cache.take_ready(
                lambda _psid: _THRESHOLD)
        else:
            replayed, groups, epoch, compact = [], [], 0, True
        retunes, retune_pending[:] = list(retune_pending), []
        negotiated = coord.poll_responses({})
        if _chaos.active():
            negotiated = _chaos.maybe_reorder("coord.reorder",
                                              negotiated)
        resps = (([marker] if marker is not None else [])
                 + retunes + replayed + negotiated)
        n_other = ((1 if marker is not None else 0) + len(retunes)
                   + len(negotiated))
        # Controller cache observation BEFORE the broadcast — same
        # ordering contract as the production drain loop: a worker's
        # hit bit for a freshly broadcast entry may arrive before the
        # send returns, and must find the entry already inserted.
        replay_ids = frozenset(id(r) for r in replayed)
        if cache is not None:
            for r in resps:
                cache.observe_response(r, replay=id(r) in replay_ids)
        if resps:
            if compact and groups and n_other == 0:
                ctrl.broadcast_replay(groups, epoch)
            else:
                ctrl.broadcast_responses(resps)
        return resps

    names = set(_cp_names())
    data_types = (ResponseType.ALLREDUCE, ResponseType.ALLGATHER,
                  ResponseType.BROADCAST, ResponseType.REDUCESCATTER,
                  ResponseType.ALLTOALL)
    steps = _cp_steps()
    pull_step = (3 * steps) // 4
    for step in range(steps):
        if step in retune_steps:
            retune_pending.append(Response(
                ResponseType.RETUNE,
                tensor_names=[f"fusion_threshold={_THRESHOLD << 1}",
                              "cycle_time=0.004"],
                tensor_sizes=[retune_seq]))
            retune_seq += 1
        for n in sorted(names):
            ctrl.submit(_cp_req(0, n))
        done: set = set()
        deadline = time.monotonic() + CP_STEP_DEADLINE
        withdrew = False
        while done != names:
            for r in tick():
                if r.response_type in data_types:
                    for n in r.tensor_names:
                        done.add(n)
                        records.append((step, n, r.response_type.name))
                elif r.response_type == ResponseType.RETUNE:
                    for n in r.tensor_names:
                        records.append((int(r.tensor_sizes[0]), n,
                                        "RETUNE"))
                elif r.response_type == ResponseType.ERROR:
                    _diag(0, f"negotiation failed: {r.error_message}")
            if not withdrew and time.monotonic() > deadline:
                # The bounded end of a silently-lost frame: fail the
                # op group-wide (the runtime's synchronize-timeout →
                # withdraw path, mirrored here).
                withdrew = True
                for n in sorted(names - done):
                    coord.withdraw(n, 0)
            time.sleep(0.002)
        if step == pull_step:
            # One fleet-wide metrics pull mid-run: under the tree this
            # exercises the merged FRAME_METRICS_TREE aggregation (and
            # after an interior fault, the re-parented paths); every
            # live rank must answer.
            from .. import telemetry as _telemetry

            snaps = ctrl.collect_metrics(_telemetry.metrics(),
                                         timeout=10.0)
            if len(snaps) < np_:
                _diag(0, f"metrics pull covered only "
                         f"{sorted(snaps)} of {np_} ranks")
            _check_mem_gauges(snaps, np_)
    _result(0, records)
    ctrl.broadcast_responses([Response(ResponseType.SHUTDOWN)])
    time.sleep(0.3)  # let the workers drain the shutdown
    ctrl.close()


def run_cp_worker(rank: int, port: int, np_: int = 2) -> None:
    """Ranks 1..N-1 of the cp fleet: the real WorkerTransport (or its
    tree overlay when HVD_TPU_TREE arms it) + response-cache replica,
    mirroring the worker half of ops/collective._drain."""
    from ..ops import cache as _cache_mod
    from ..ops import transport as T
    from ..ops.wire import ResponseType

    _seed_mem_probe(rank)
    kill_step = int(os.environ.get("HVD_TPU_CHAOS_KILL_STEP", "-1"))
    layout = _cp_layout(np_)
    if layout is not None:
        from ..ops import tree as _tree

        w = _tree.TreeWorkerTransport("127.0.0.1", port, rank, layout)
    else:
        w = T.WorkerTransport("127.0.0.1", port, rank)
    if _cache_mod.cache_enabled() and w.controller_cache:
        w.cache = _cache_mod.ResponseCache(rank=rank)
    records = []
    names = set(_cp_names())
    data_types = (ResponseType.ALLREDUCE, ResponseType.ALLGATHER,
                  ResponseType.BROADCAST, ResponseType.REDUCESCATTER,
                  ResponseType.ALLTOALL)
    for step in range(_cp_steps()):
        if step == kill_step:
            sys.stderr.flush()
            os._exit(1)  # hard crash: no atexit handshake, no reconnect
        reqs = {}
        for n in sorted(names):
            req = _cp_req(rank, n)
            reqs[n] = req
            w.submit(req)
        w.flush_requests()
        done: set = set()
        deadline = time.monotonic() + CP_STEP_DEADLINE + 5.0
        while done != names:
            if time.monotonic() > deadline:
                _diag(rank, f"step {step} never completed "
                            f"({sorted(names - done)} missing)")
            resps = w.poll_responses()
            if resps is None:
                time.sleep(0.002)
                continue
            for r in resps:
                cache = w.cache  # may be dropped by a reconnect
                if cache is not None:
                    cache.observe_response(
                        r, own_requests={rank: reqs})
                if r.response_type in data_types:
                    for n in r.tensor_names:
                        done.add(n)
                        records.append((step, n, r.response_type.name))
                elif r.response_type == ResponseType.RETUNE:
                    # hvd-tune marker: record the apply exactly as the
                    # controller does — the recover digest proves
                    # exactly-once delivery across the fault.
                    for n in r.tensor_names:
                        records.append((int(r.tensor_sizes[0]), n,
                                        "RETUNE"))
                elif r.response_type == ResponseType.ERROR:
                    _diag(rank,
                          f"negotiation failed: {r.error_message}")
                elif r.response_type == ResponseType.SHUTDOWN:
                    if r.error_message:
                        _diag(rank, f"shutdown: {r.error_message}")
                    _diag(rank, "controller shut down mid-run")
    _result(rank, records)
    w.request_shutdown()
    w.close()


# ---------------------------------------------------------------------------
# local scenarios (real jax stack, single process)
# ---------------------------------------------------------------------------

def scenario_ckpt(exhaust: bool) -> None:
    """Background checkpoint write under an injected flaky filesystem
    (ckpt.oserror).  Recover: the published bytes are identical to the
    fault-free run.  Exhaustion: CheckpointError at wait() naming the
    injected fault."""
    import tempfile

    import numpy as np

    from ..utils import checkpoint as ckpt

    tree = {"w": np.arange(64, dtype=np.float32),
            "b": np.full((8,), 3.0, np.float32)}
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "model.msgpack")
        handle = ckpt.write_tree_async(path, tree, step=7)
        try:
            handle.wait(timeout=60.0)
        except ckpt.CheckpointError as e:
            if exhaust:
                _diag(0, f"checkpoint failed after retries: {e}")
            raise
        if exhaust:
            print("CHAOS_NOTE: exhaustion scenario unexpectedly "
                  "succeeded", file=sys.stderr)
            raise SystemExit(1)
        with open(path, "rb") as f:
            blob = f.read()
        with open(path + ".step") as f:
            step = f.read()
        _result(0, [("ckpt", hashlib.sha256(blob).hexdigest(), step)])


def scenario_input_stall() -> None:
    """A tiny data-parallel training loop through prefetch_to_device
    with injected loader stalls: the trained parameters must be
    bitwise-identical to the fault-free run (prefetch adds latency,
    never reorders or drops batches)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu as hvd

    hvd.init(devices=jax.devices())
    try:
        nrep = hvd.size()
        rng = np.random.RandomState(7)
        batches = [rng.normal(size=(nrep * 4, 8)).astype("float32")
                   for _ in range(10)]

        w = jnp.zeros((8,), jnp.float32)

        @jax.jit
        def step(w, x):
            return w + jnp.tanh(x).mean(axis=0) * 0.1

        it = hvd.prefetch_to_device(iter(batches), depth=2)
        seen = 0
        for dev_batch in it:
            w = step(w, dev_batch)
            seen += 1
        host = np.asarray(w)
        _result(0, [("input", seen,
                     hashlib.sha256(host.tobytes()).hexdigest())])
    finally:
        hvd.shutdown()


def _build_server():
    import jax

    from ..models.transformer import TransformerConfig, init_transformer
    from ..serving.engine import InferenceEngine
    from ..serving.server import LMServer

    cfg = TransformerConfig(vocab_size=256, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_seq_len=64)
    params = init_transformer(jax.random.PRNGKey(5), cfg)
    engine = InferenceEngine(params, cfg, max_slots=2, page_size=8,
                             capacity=64)
    return LMServer(engine, port=0).start()


def _post_generate(port: int, payload: dict, timeout: float = 60.0):
    import urllib.request

    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def scenario_serving_disconnect() -> None:
    """serving.disconnect fires inside the /generate client probe: the
    first request's slot is released through the abort path
    (serving.client_disconnects counts it) and the FOLLOW-UP request —
    the digested result — completes identically to the fault-free
    run."""
    from .. import chaos as _chaos
    from .. import telemetry as _telemetry

    srv = _build_server()
    try:
        faulted = _chaos.active()
        first: dict = {}
        try:
            first = _post_generate(
                srv.port, {"tokens": [5, 6, 7], "max_tokens": 24,
                           "timeout": 45.0})
        except Exception as e:  # noqa: BLE001 — 499 surfaces as an
            # HTTPError on the faulted pass; the follow-up is the test
            first = {"error": str(e)}
        follow = _post_generate(
            srv.port, {"tokens": [9, 10, 11], "max_tokens": 8,
                       "timeout": 45.0})
        if faulted:
            snap = _telemetry.metrics()
            got = snap.get("serving.client_disconnects",
                           {}).get("value", 0)
            if got < 1:
                _diag(0, f"client disconnect was injected but never "
                         f"counted (serving.client_disconnects={got}; "
                         f"first reply: {first})")
        _result(0, [("serve", tuple(follow["tokens"]),
                     follow["finish_reason"])])
    finally:
        srv.close()


def scenario_serving_spec_disconnect() -> None:
    """serving.disconnect fires mid-SPECULATION: the engine runs a
    draft model (speculative decoding) and the prefix cache, the first
    request dies at the client probe, and its iteration-boundary abort
    must release the target AND draft KV slots and decrement the
    prefix refcounts (a leak would show as diverging page accounting).
    The follow-up request shares the first one's prompt header — a
    prefix-cache hit — and must complete identically to the fault-free
    pass."""
    import jax

    from .. import chaos as _chaos
    from .. import telemetry as _telemetry
    from ..models.transformer import TransformerConfig, init_transformer
    from ..serving.engine import InferenceEngine
    from ..serving.server import LMServer

    cfg = TransformerConfig(vocab_size=256, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_seq_len=64)
    dcfg = TransformerConfig(vocab_size=256, d_model=32, n_heads=2,
                             n_layers=1, d_ff=32, max_seq_len=64)
    params = init_transformer(jax.random.PRNGKey(5), cfg)
    draft = init_transformer(jax.random.PRNGKey(6), dcfg)
    engine = InferenceEngine(params, cfg, max_slots=2, page_size=8,
                             capacity=64, draft=(draft, dcfg),
                             spec_tokens=3, prefix_cache=True)
    srv = LMServer(engine, port=0).start()
    try:
        faulted = _chaos.active()
        header = list(range(40, 56))  # two full 8-token pages
        first: dict = {}
        try:
            first = _post_generate(
                srv.port, {"tokens": header + [5, 6, 7],
                           "max_tokens": 24, "timeout": 45.0})
        except Exception as e:  # noqa: BLE001 — 499 surfaces as an
            # HTTPError on the faulted pass; the follow-up is the test
            first = {"error": str(e)}
        follow = _post_generate(
            srv.port, {"tokens": header + [9, 10, 11],
                       "max_tokens": 8, "timeout": 45.0})
        if faulted:
            snap = _telemetry.metrics()
            got = snap.get("serving.client_disconnects",
                           {}).get("value", 0)
            if got < 1:
                _diag(0, f"client disconnect was injected but never "
                         f"counted (serving.client_disconnects={got}; "
                         f"first reply: {first})")
        # Page accounting after the abort: every slot idle, so free +
        # cached must cover every allocatable page on BOTH stores, and
        # no cached page may still hold a reference — a leak here is a
        # divergence between the passes (the digest covers it).
        stats = engine.cache.prefix_stats()
        target_ok = (engine.cache.free_pages()
                     == engine.cache.total_pages)
        draft_ok = (engine.draft_cache.free_pages()
                    == engine.draft_cache.total_pages)
        _result(0, [("serve", tuple(follow["tokens"]),
                     follow["finish_reason"]),
                    ("pages", target_ok, draft_ok,
                     stats["referenced_pages"],
                     stats["cached_pages"])])
    finally:
        srv.close()


def scenario_serving_storm() -> None:
    """A burst of concurrent /generate requests against two decode
    slots: every request must complete (or fail explicitly) — the
    front door never hangs under a storm."""
    import threading

    srv = _build_server()
    try:
        out: Dict[int, object] = {}

        def one(i: int) -> None:
            try:
                out[i] = tuple(_post_generate(
                    srv.port, {"tokens": [3 + i, 4, 5],
                               "max_tokens": 6,
                               "timeout": 90.0})["tokens"])
            except Exception as e:  # noqa: BLE001 — an explicit
                out[i] = f"error: {e}"  # failure is contract-legal

        threads = [threading.Thread(target=one, args=(i,), daemon=True)
                   for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120.0)
        alive = [i for i, t in enumerate(threads) if t.is_alive()]
        if alive:
            _diag(0, f"storm requests {alive} still hanging")
        _result(0, sorted(("storm", i, str(out.get(i)))
                          for i in range(12)))
    finally:
        srv.close()


def scenario_router_replica_death() -> None:
    """Two real replicas (identical params, so completions are
    bitwise-identical wherever they run) behind the REAL Router over
    real HTTP.  The faulted pass drains the replica that served the
    first request and then kills its front door hard: the remaining
    dispatches must fail over to the survivor, and the digested
    completions must match the fault-free fleet's exactly."""
    import jax

    from .. import chaos as _chaos
    from ..models.transformer import TransformerConfig, init_transformer
    from ..routing import Router, RouterConfig
    from ..routing.replica import HttpReplicaClient
    from ..serving.engine import InferenceEngine
    from ..serving.server import LMServer

    from ..telemetry import exporter as _exporter

    cfg = TransformerConfig(vocab_size=256, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_seq_len=64)
    params = init_transformer(jax.random.PRNGKey(5), cfg)

    def replica():
        engine = InferenceEngine(params, cfg, max_slots=2, page_size=8,
                                 capacity=64, prefix_cache=True)
        # Private routes: two replicas in one process must not clobber
        # each other's /generate + /healthz (LMServer docstring).
        return LMServer(engine, port=0,
                        routes=_exporter.RouteRegistry()).start()

    servers = {"a": replica(), "b": replica()}
    router = Router(RouterConfig(probe_base=0.01))
    try:
        for name, srv in servers.items():
            router.add_replica(
                name, HttpReplicaClient("127.0.0.1", srv.port))
        router.poll()
        records = []
        header = list(range(40, 56))  # two full 8-token pages
        status, first = router.dispatch(
            {"tokens": header + [5, 6, 7], "max_tokens": 12})
        if status != 200:
            _diag(0, f"first dispatch failed: {status} {first}")
        records.append(("req0", tuple(first["tokens"]),
                        first["finish_reason"]))
        if _chaos.fire("router.replica_kill") is not None:
            victim = first["router"]["replica"]
            router.drain_replica(victim)  # real POST /drain
            servers[victim].close()       # then the hard death
            router.poll()                 # -> ReplicaUnreachable
        for i, prompt in enumerate((header + [9, 10, 11],
                                    [7, 8, 9, 10])):
            status, resp = router.dispatch({"tokens": prompt,
                                            "max_tokens": 8})
            if status != 200:
                _diag(0, f"dispatch {i + 1} failed after the replica "
                         f"death: {status} {resp}")
            records.append((f"req{i + 1}", tuple(resp["tokens"]),
                            resp["finish_reason"]))
        down = sorted(n for n, s in router.replica_status().items()
                      if s["status"] != "ready")
        if _chaos.active():
            if not down:
                _diag(0, "the kill was injected but every replica "
                         "still reads ready")
            print(f"[hvd-route] failed over from {down} to the "
                  f"surviving replica", flush=True)
        _result(0, records)
    finally:
        for srv in servers.values():
            try:
                srv.close()
            except Exception:  # noqa: BLE001 — the victim is already
                pass           # closed on the faulted pass


def scenario_router_restart() -> None:
    """The REAL RouterServer runs in a SEPARATE process over two real
    in-process replicas; the faulted pass SIGKILLs it mid-generation.
    The replicas must abort the severed connections via the client
    probe (no slot leak), a fresh router over the same fleet serves
    the resubmitted request, and the digested completions are
    identical to the never-killed run (the serving bitwise contract
    makes the retry the same answer)."""
    import signal
    import threading
    import urllib.request

    import jax

    from .. import chaos as _chaos
    from .. import telemetry as _telemetry
    from ..models.transformer import TransformerConfig, init_transformer
    from ..serving.engine import InferenceEngine
    from ..serving.server import LMServer

    from ..telemetry import exporter as _exporter

    # Wide enough that a 220-token generation takes whole seconds on
    # CPU — the SIGKILL below must land mid-generation.
    cfg = TransformerConfig(vocab_size=256, d_model=64, n_heads=2,
                            n_layers=2, d_ff=256, max_seq_len=256)
    params = init_transformer(jax.random.PRNGKey(5), cfg)

    def replica():
        engine = InferenceEngine(params, cfg, max_slots=2, page_size=8,
                                 capacity=256)
        return LMServer(engine, port=0,
                        routes=_exporter.RouteRegistry()).start()

    servers = [replica(), replica()]

    def boot_router():
        port = _free_port()
        env = dict(os.environ)
        env.pop("HVD_TPU_FAULTS", None)  # the router child is plain
        env["HVD_TPU_CHAOS_REPLICAS"] = ",".join(
            str(s.port) for s in servers)
        env["HVD_TPU_CHAOS_ROUTER_PORT"] = str(port)
        proc = subprocess.Popen(
            [sys.executable, "-m", "horovod_tpu.chaos",
             "--scenario", "router_restart_node"],
            env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL)
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                _diag(0, f"router child exited {proc.returncode} "
                         f"before becoming healthy")
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/healthz",
                        timeout=2.0) as resp:
                    if resp.status == 200:
                        return proc, port
            except Exception:  # noqa: BLE001 — still booting
                time.sleep(0.1)
        proc.kill()
        _diag(0, "router child never became healthy")

    proc, port = boot_router()
    try:
        records = []
        r0 = _post_generate(port, {"tokens": [5, 6, 7],
                                   "max_tokens": 8})
        records.append(("req0", tuple(r0["tokens"]),
                        r0["finish_reason"]))
        long_payload = {"tokens": [11, 12, 13, 14], "max_tokens": 220}
        if _chaos.fire("router.kill") is not None:
            severed: Dict[str, object] = {}

            def fire_and_forget() -> None:
                try:
                    severed["resp"] = _post_generate(
                        port, long_payload, timeout=120.0)
                except Exception as e:  # noqa: BLE001 — the router
                    severed["error"] = str(e)  # we kill takes it down
            th = threading.Thread(target=fire_and_forget, daemon=True)
            th.start()
            time.sleep(0.15)  # let the replica start decoding
            proc.send_signal(signal.SIGKILL)
            proc.wait()
            th.join(timeout=30.0)
            proc, port = boot_router()
            aborted = 0
            deadline = time.monotonic() + 45.0
            while time.monotonic() < deadline:
                snap = _telemetry.metrics()
                aborted = snap.get("serving.client_disconnects",
                                   {}).get("value", 0)
                if aborted >= 1:
                    break
                time.sleep(0.2)
            if aborted < 1:
                _diag(0, f"router killed mid-generation but no "
                         f"replica aborted the orphaned request "
                         f"(client_disconnects={aborted}; severed "
                         f"reply: {severed})")
            print(f"[hvd-route] replica aborted the severed router "
                  f"connection (client_disconnects={aborted}); "
                  f"resubmitting through the restarted router",
                  flush=True)
        rl = _post_generate(port, long_payload, timeout=120.0)
        records.append(("long", tuple(rl["tokens"]),
                        rl["finish_reason"]))
        r2 = _post_generate(port, {"tokens": [9, 10, 11],
                                   "max_tokens": 8})
        records.append(("req2", tuple(r2["tokens"]),
                        r2["finish_reason"]))
        _result(0, records)
    finally:
        proc.kill()
        proc.wait()
        for srv in servers:
            srv.close()


def _router_restart_node() -> None:
    """(child helper, no matrix row) The router process of
    ``router_restart``: the REAL RouterServer over HTTP clients to the
    parent scenario's replicas; the parent SIGKILLs it mid-generation
    on the faulted pass."""
    from ..routing import Router, RouterConfig, RouterServer
    from ..routing.replica import HttpReplicaClient

    ports = [int(p) for p in
             os.environ["HVD_TPU_CHAOS_REPLICAS"].split(",")]
    router = Router(RouterConfig(probe_base=0.01))
    for i, port in enumerate(ports):
        router.add_replica(f"r{i}",
                           HttpReplicaClient("127.0.0.1", port))
    RouterServer(
        router, port=int(os.environ["HVD_TPU_CHAOS_ROUTER_PORT"]),
        poll_interval=0.2).start()
    while True:  # serve until the parent kills us
        time.sleep(60.0)


LOCAL_SCENARIOS = {
    "ckpt_flaky": lambda: scenario_ckpt(exhaust=False),
    "ckpt_exhaustion": lambda: scenario_ckpt(exhaust=True),
    "input_stall": scenario_input_stall,
    "serving_disconnect": scenario_serving_disconnect,
    "serving_spec_disconnect": scenario_serving_spec_disconnect,
    "serving_storm": scenario_serving_storm,
    "router_replica_death": scenario_router_replica_death,
    "router_restart": scenario_router_restart,
    "router_restart_node": _router_restart_node,
}


# ---------------------------------------------------------------------------
# The runner
# ---------------------------------------------------------------------------

def _child_env(s: Scenario, faulted: bool,
               extra: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    env = dict(os.environ)
    env.pop("HVD_TPU_FAULTS", None)
    env.update(s.env)
    if faulted and s.spec:
        env["HVD_TPU_FAULTS"] = s.spec
    env.setdefault("JAX_PLATFORMS", "cpu")
    # hvd-race: fleet children run with the data-race detector and
    # donation sanitizer armed (like HVD_TPU_LOCK_CHECK via env
    # inheritance from conftest) — chaos is exactly where cross-thread
    # interleavings and recovery-path stale reads surface.
    env.setdefault("HVD_TPU_LOCK_CHECK", "1")
    env.setdefault("HVD_TPU_RACE_CHECK", "1")
    env.setdefault("HVD_TPU_DONATION_CHECK", "1")
    if s.kind == "local":
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if not f.startswith(
                     "--xla_force_host_platform_device_count")]
        flags.append("--xla_force_host_platform_device_count=4")
        env["XLA_FLAGS"] = " ".join(flags).strip()
    env.update(extra or {})
    return env


@dataclass
class PassResult:
    rc: Optional[int]   # None = killed at the cap (HANG)
    output: str
    results: Dict[int, str]  # rank -> CHAOS_RESULT line payload
    seconds: float


_RESULT_RE = re.compile(
    r"CHAOS_RESULT rank=(\d+) n=(\d+) digest=([0-9a-f]{24})")


def _parse_results(output: str) -> Dict[int, str]:
    # Matched by the exact field shapes (_result writes a 24-hex-char
    # digest), not by line splitting: a concurrent writer on the same
    # fd can interleave a log fragment mid-line (observed: a
    # "[hvd-tree]" relay line glued onto a digest token under tier-1
    # load), and that must not read as a digest mismatch.
    out: Dict[int, str] = {}
    for m in _RESULT_RE.finditer(output):
        out[int(m.group(1))] = f"n={m.group(2)} digest={m.group(3)}"
    return out


def _run_pass(s: Scenario, faulted: bool) -> PassResult:
    t0 = time.monotonic()
    if s.kind == "local":
        procs = [subprocess.Popen(
            [sys.executable, "-m", "horovod_tpu.chaos",
             "--scenario", s.name],
            env=_child_env(s, faulted, {"HVD_TPU_RANK": "0"}),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT)]
    else:
        port = _free_port()
        # Tree mode: interiors bind relay ports at base+rank; a fresh
        # base per pass keeps parallel passes from colliding (harmless
        # for flat fleets, which never bind them).
        tree_base = _free_port()
        procs = []
        for rank in range(s.np):
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "horovod_tpu.chaos",
                 "--node", str(rank), "--np", str(s.np),
                 "--port", str(port), "--scenario", s.name],
                env=_child_env(s, faulted,
                               {"HVD_TPU_RANK": str(rank),
                                "HVD_TPU_TREE_PORT_BASE":
                                    str(tree_base)}),
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
            if rank == 0:
                time.sleep(0.2)  # let the controller bind first
    deadline = t0 + s.cap
    outputs: List[str] = [""] * len(procs)
    hang = False
    for i, p in enumerate(procs):
        remaining = deadline - time.monotonic()
        try:
            out, _ = p.communicate(timeout=max(0.1, remaining))
            outputs[i] = out.decode(errors="replace")
        except subprocess.TimeoutExpired:
            hang = True
            p.kill()
            out, _ = p.communicate()
            outputs[i] = out.decode(errors="replace")
    output = "\n".join(outputs)
    rcs = [p.returncode for p in procs]
    rc: Optional[int] = None if hang else max(rcs)
    return PassResult(rc=rc, output=output,
                      results=_parse_results(output),
                      seconds=time.monotonic() - t0)


def run_scenario(s: Scenario, verbose: bool = False) -> Dict:
    """Run one scenario end to end; returns its report dict."""
    report: Dict = {"scenario": s.name, "expect": s.expect,
                    "spec": s.spec, "cap": s.cap}

    def fail(status: str, detail: str, *passes: PassResult) -> Dict:
        report.update(status=status, detail=detail)
        print(f"  FAIL [{status}] {s.name}: {detail}", flush=True)
        for p in passes:
            tail = "\n".join(p.output.splitlines()[-25:])
            print(f"  ---- pass output tail ----\n{tail}", flush=True)
        return report

    if s.expect == "complete":
        p = _run_pass(s, faulted=False)
        report["seconds"] = p.seconds
        if p.rc is None:
            return fail("HANG", f"still running at the {s.cap:.0f}s "
                                f"cap", p)
        if p.rc != 0:
            return fail("FAIL", f"exit {p.rc}", p)
        report["status"] = "PASS"
        print(f"  PASS {s.name} ({p.seconds:.1f}s)", flush=True)
        return report

    base: Optional[PassResult] = None
    if s.expect == "recover":
        # Diagnostic scenarios need no baseline (nothing is compared;
        # the scenario's env may itself carry the fault, e.g. the
        # grace-expiry hard kill).
        base = _run_pass(s, faulted=False)
        if base.rc is None:
            return fail("HANG", "fault-free pass hit the cap", base)
        if base.rc != 0:
            return fail("FAIL", f"fault-free pass exited {base.rc}",
                        base)
    fp = _run_pass(s, faulted=True)
    report["seconds"] = (base.seconds if base else 0.0) + fp.seconds
    if fp.rc is None:
        return fail("HANG", f"faulted run still alive at the "
                            f"{s.cap:.0f}s cap — the no-hang "
                            f"contract violation", fp)
    if s.expect == "recover":
        if fp.rc != 0:
            return fail("FAIL", f"expected recovery, got exit {fp.rc}",
                        fp)
        if fp.results != base.results:
            return fail(
                "DIVERGED",
                f"recovered but results differ: fault-free "
                f"{base.results} vs faulted {fp.results}", base, fp)
        if s.needle and s.needle not in fp.output:
            return fail("FAIL", f"recovered, but the fault was never "
                                f"exercised ({s.needle!r} not in "
                                f"output)", fp)
    else:  # diagnostic
        if fp.rc == 0:
            return fail("FAIL", "expected a named failure, run "
                                "exited 0", fp)
        if s.needle and s.needle not in fp.output:
            return fail("FAIL", f"failed, but without the diagnostic "
                                f"naming the fault ({s.needle!r} not "
                                f"in output)", fp)
    report["status"] = "PASS"
    print(f"  PASS {s.name} ({report['seconds']:.1f}s)", flush=True)
    if verbose:
        print(fp.output)
    return report


def run_matrix(only: Optional[List[str]] = None,
               verbose: bool = False) -> int:
    todo = ([find(n) for n in only] if only else SCENARIOS)
    print(f"hvd-chaos matrix: {len(todo)} scenario(s)", flush=True)
    reports = []
    for s in todo:
        print(f"- {s.name} [{s.kind} np={s.np if s.kind == 'cp' else 1}"
              f" expect={s.expect}] {s.doc}", flush=True)
        reports.append(run_scenario(s, verbose=verbose))
    failed = [r for r in reports if r.get("status") != "PASS"]
    print(json.dumps({"scenarios": reports,
                      "passed": len(reports) - len(failed),
                      "failed": len(failed)}, indent=1))
    return 1 if failed else 0
