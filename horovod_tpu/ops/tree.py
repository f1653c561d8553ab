"""Tree-structured control-plane overlay: the thousand-rank scale-out.

Every remaining O(world) cost in the control plane funnels through rank
0: the flat star (one TCP connection per worker, ops/transport.py — the
original Horovod topology, arXiv:1802.05799) means every drain tick
delivers world-1 FRAME_REQUEST_BATCH frames to one process, and every
``cluster_metrics()`` / ``dump_fleet_trace()`` pull collects world-1
replies point-to-point — the flat-topology scaling wall characterized
in arXiv:1810.11112.  This module turns the star into a **fanout-ary
tree**:

* **Upward aggregation** — interior ranks parse their children's
  coalesced request frames, merge the cache-hit bit-vectors (grouped by
  ``(epoch, entry set)`` across ranks — in the steady state every rank
  hits the same entries, so a whole subtree collapses into ONE group),
  concatenate the full requests, and forward a single
  ``FRAME_SUBTREE_BATCH`` per tick.  ``FRAME_METRICS`` /
  ``FRAME_TRACE`` pull replies aggregate the same way
  (``FRAME_METRICS_TREE`` / ``FRAME_TRACE_TREE``).  Rank 0 receives
  ≤ fanout frames per cycle instead of world-1.
* **Downward relay** — interiors copy every root broadcast to their
  children verbatim, in order, so each rank's downward stream IS the
  root's broadcast stream bit-for-bit.  That invariant is what keeps
  every response-cache replica index-aligned across interior merging,
  and what makes **re-parenting** possible: the root keeps ONE shared
  broadcast ring, and any rank can resume from its global stream index
  regardless of which path used to feed it.
* **Self-healing** — a rank whose parent link dies reconnects straight
  to the root's session-resume listener (the PR-8 machinery): the root
  adopts it as a direct child, replays the missed broadcast suffix
  from the shared ring, and the worker replays its own unacknowledged
  upward suffix (duplicate submits/bits are idempotent by design).  An
  interior that loses a child reports ``FRAME_CHILD_LOST`` after a
  grace window; only the root arbitrates liveness — a re-parented rank
  ignores the stale report, a dead one gets its own grace window and
  then the dead-peer diagnostic.  The tree heals into a flatter shape
  rather than reconstructing; a lost interior degrades its subtree to
  direct root children, never orphans it.

Tree shape
----------
Ranks are ordered slice-major using the same ICI x DCN contract as
``core/topology.replica_hierarchy`` (real multi-host jobs group ranks
by host/slice; ``HVD_TPU_VIRTUAL_SLICES`` declares contiguous virtual
slices for dryruns), then arranged as a heap: ``parent(order[i]) =
order[(i-1) // fanout]``.  Subtrees nest inside slices, so aggregation
traffic rides ICI and only the top of the tree crosses DCN.

Env contract (docs/deploy.md, docs/performance.md):
  HVD_TPU_TREE=auto|on|off       auto (default): tree when world size
                                 reaches HVD_TPU_TREE_THRESHOLD
  HVD_TPU_TREE_FANOUT=<k>        children per interior node (default 8)
  HVD_TPU_TREE_THRESHOLD=<n>     auto-on world size (default 64)
  HVD_TPU_TREE_PORT_BASE=<p>     relay listen ports (base + rank;
                                 default controller port + 1000)
  HVD_TPU_TREE_HOSTS=r=host,...  interior host map (default: the
                                 controller host — single-host fleets)
  HVD_TPU_TREE_PULL_TIMEOUT=<s>  interior partial-aggregation flush
                                 deadline for metrics/trace pulls

Like every knob that changes the control-plane wire conversation, the
tree knobs must be uniform across ranks (they ride the HELLO env
fingerprint — ops/compression.env_fingerprint).
"""

from __future__ import annotations

import os
import socket
import struct
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from . import transport as T
from . import wire
from .. import chaos as _chaos
from .. import telemetry as _telemetry
from .. import trace as _trace
from ..analysis import lockorder as _lockorder
from ..analysis import threads as _athreads
from ..analysis import races as _races
from ..telemetry import flight as _flight
from .wire import Request, Response, ResponseType

TREE_ENV = "HVD_TPU_TREE"
FANOUT_ENV = "HVD_TPU_TREE_FANOUT"
THRESHOLD_ENV = "HVD_TPU_TREE_THRESHOLD"
PORT_BASE_ENV = "HVD_TPU_TREE_PORT_BASE"
HOSTS_ENV = "HVD_TPU_TREE_HOSTS"
PULL_TIMEOUT_ENV = "HVD_TPU_TREE_PULL_TIMEOUT"


def tree_mode() -> str:
    mode = os.environ.get(TREE_ENV, "auto").lower()
    if mode not in ("auto", "on", "off"):
        raise ValueError(f"{TREE_ENV}={mode!r}: expected auto, on or off")
    return mode


def tree_fanout() -> int:
    v = int(os.environ.get(FANOUT_ENV, "8"))
    if v < 1:
        raise ValueError(f"{FANOUT_ENV}={v}: expected >= 1")
    return v


def tree_threshold() -> int:
    return int(os.environ.get(THRESHOLD_ENV, "64"))


def pull_timeout() -> float:
    return float(os.environ.get(PULL_TIMEOUT_ENV, "5"))


def validate_env() -> None:
    """Fail ``hvd.init()`` — not the first drain tick — on malformed
    tree knobs (the same up-front contract every other control-plane
    knob follows)."""
    tree_mode()
    tree_fanout()
    tree_threshold()
    base = os.environ.get(PORT_BASE_ENV)
    if base:
        int(base)
    hosts = os.environ.get(HOSTS_ENV)
    if hosts:
        _parse_hosts(hosts)


def tree_active(world: int) -> bool:
    """Whether the overlay is armed for this world size."""
    mode = tree_mode()
    if mode == "off" or world < 3:
        return False
    if mode == "on":
        return True
    return world >= tree_threshold()


def _parse_hosts(spec: str) -> Dict[int, str]:
    out: Dict[int, str] = {}
    for kv in spec.split(","):
        kv = kv.strip()
        if not kv:
            continue
        r, _, h = kv.partition("=")
        out[int(r)] = h
    return out


def relay_port(controller_port: int, rank: int) -> int:
    """Deterministic relay listen port for an interior rank — every
    rank derives the same map with no extra rendezvous round."""
    base = int(os.environ.get(PORT_BASE_ENV, "0") or 0)
    if not base:
        base = controller_port + 1000
    return base + rank


def parent_address(controller_host: str, controller_port: int,
                   parent: int) -> Tuple[str, int]:
    """Where a child connects: the controller itself for parent 0,
    otherwise the parent's relay listener (host from HVD_TPU_TREE_HOSTS
    when the fleet spans machines; the controller host by default —
    the single-host multiprocess deployment)."""
    if parent == 0:
        return controller_host, controller_port
    host = _parse_hosts(os.environ.get(HOSTS_ENV, "")).get(
        parent, controller_host)
    return host, relay_port(controller_port, parent)


# ---------------------------------------------------------------------------
# Layout
# ---------------------------------------------------------------------------

def _slice_table(world: int) -> Optional[List[int]]:
    """Slice id per rank, from the same HVD_TPU_VIRTUAL_SLICES contract
    ``core/topology.replica_hierarchy`` applies to the replica axis —
    contiguous equal blocks, or None when the process space is flat."""
    k = int(os.environ.get("HVD_TPU_VIRTUAL_SLICES", "0") or 0)
    if k > 1 and world % k == 0 and world // k >= 1:
        ici = world // k
        return [r // ici for r in range(world)]
    return None


@dataclass(frozen=True)
class TreeLayout:
    """The agreed tree shape: every rank derives the identical layout
    from (world, fanout, slice table) with no communication."""

    world: int
    fanout: int
    order: Tuple[int, ...]          # heap order; order[0] == 0
    pos: Dict[int, int]             # rank -> index in order

    def parent(self, rank: int) -> Optional[int]:
        i = self.pos[rank]
        if i == 0:
            return None
        return self.order[(i - 1) // self.fanout]

    def children(self, rank: int) -> Tuple[int, ...]:
        i = self.pos[rank]
        lo = i * self.fanout + 1
        return tuple(self.order[j]
                     for j in range(lo, min(lo + self.fanout,
                                            len(self.order))))

    def subtree(self, rank: int) -> Tuple[int, ...]:
        """The rank and every descendant (preorder)."""
        out = [rank]
        stack = list(self.children(rank))
        while stack:
            r = stack.pop()
            out.append(r)
            stack.extend(self.children(r))
        return tuple(out)

    def is_interior(self, rank: int) -> bool:
        return rank != 0 and bool(self.children(rank))

    def interior_ranks(self) -> Tuple[int, ...]:
        return tuple(r for r in self.order if self.is_interior(r))

    def depth(self) -> int:
        """Edges on the longest root-to-leaf path."""
        d = 0
        n = len(self.order)
        i = n - 1
        while i > 0:
            i = (i - 1) // self.fanout
            d += 1
        return d


def build_layout(world: int, fanout: Optional[int] = None,
                 slices: Optional[Sequence[int]] = None) -> TreeLayout:
    """Derive the tree shape.  Ranks order slice-major (ICI x DCN:
    subtrees nest inside slices so aggregation rides the fast links),
    rank 0 always the root; then a ``fanout``-ary heap over that
    order."""
    if fanout is None:
        fanout = tree_fanout()
    if slices is None:
        slices = _slice_table(world)
    rest = [r for r in range(world) if r != 0]
    if slices is not None:
        rest.sort(key=lambda r: (slices[r], r))
    order = tuple([0] + rest)
    return TreeLayout(world=world, fanout=fanout, order=order,
                      pos={r: i for i, r in enumerate(order)})


def expected_root_frames(world: int, fanout: Optional[int] = None) -> int:
    """Frames rank 0 receives per steady-state tick under the tree —
    one merged envelope per direct child (vs world-1 flat)."""
    return len(build_layout(world, fanout).children(0))


def depth_bound(world: int, fanout: Optional[int] = None) -> int:
    return max(1, build_layout(world, fanout).depth())


# ---------------------------------------------------------------------------
# Wire helpers (handshake + merged frames)
# ---------------------------------------------------------------------------

def pack_hello_tree(entries: List[Tuple[int, str, str]]) -> bytes:
    """``entries`` = (rank, hostname, env fingerprint) for a whole
    subtree, the subtree's own root FIRST (the controller reads
    ``entries[0]`` as the connecting child)."""
    out = [struct.pack("<H", len(entries))]
    for rank, host, fp in entries:
        hb = host.encode("utf-8")
        fb = fp.encode("utf-8")
        out.append(struct.pack("<iH", rank, len(hb)) + hb
                   + struct.pack("<H", len(fb)) + fb)
    return b"".join(out)


def parse_hello_tree(payload: bytes) -> List[Tuple[int, str, str]]:
    (n,) = struct.unpack_from("<H", payload)
    off = 2
    out = []
    for _ in range(n):
        rank, hlen = struct.unpack_from("<iH", payload, off)
        off += 6
        host = payload[off:off + hlen].decode("utf-8")
        off += hlen
        (flen,) = struct.unpack_from("<H", payload, off)
        off += 2
        fp = payload[off:off + flen].decode("utf-8")
        off += flen
        out.append((rank, host, fp))
    return out


def pack_topo_tree(cache_flag: int,
                   entries: List[Tuple[int, "T.Topology"]]) -> bytes:
    out = [struct.pack("<BH", cache_flag, len(entries))]
    for rank, t in entries:
        out.append(struct.pack("<iiiii", rank, t.local_rank,
                               t.local_size, t.cross_rank, t.cross_size))
    return b"".join(out)


def parse_topo_tree(payload: bytes) -> Tuple[int, Dict[int, "T.Topology"]]:
    cache_flag, n = struct.unpack_from("<BH", payload)
    off = 3
    out: Dict[int, T.Topology] = {}
    for _ in range(n):
        rank, lr, ls, cr, cs = struct.unpack_from("<iiiii", payload, off)
        off += 20
        out[rank] = T.Topology(lr, ls, cr, cs)
    return cache_flag, out


def pack_merged_pull(rnd: int,
                     entries: List[Tuple[int, bytes]]) -> bytes:
    out = [struct.pack("<IH", rnd, len(entries))]
    for rank, blob in entries:
        out.append(struct.pack("<iI", rank, len(blob)) + blob)
    return b"".join(out)


def parse_merged_pull(payload: bytes) -> Tuple[int, List[Tuple[int,
                                                               bytes]]]:
    rnd, n = struct.unpack_from("<IH", payload)
    off = 6
    out = []
    for _ in range(n):
        rank, blen = struct.unpack_from("<iI", payload, off)
        off += 8
        out.append((rank, payload[off:off + blen]))
        off += blen
    return rnd, out


# -- subtree batch (the merged negotiation envelope) -----------------------
#
# Payload: <H nsections> then typed sections:
#   kind 0 bits:    <B><I epoch><H nranks><i*nranks><H nidx><I*nidx>
#                   — every listed rank hit exactly these cache entries
#                   at this epoch (the steady-state group: one section
#                   for the whole subtree)
#   kind 1 reqs:    <B><i rank><H nreq><packed Requests...>
#   kind 2 arrival: <B><i rank><B len><trace ctx bytes>
#   kind 3 counts:  <B><H n> + n x (<i rank><I cum>) — cumulative
#                   upward frames per origin rank whose content has
#                   been folded into envelopes (the re-parent resume
#                   protocol's bookkeeping)

def parse_request_batch(payload: bytes) -> Tuple[int, int, List[int],
                                                 List[bytes], bytes]:
    """Split one flat FRAME_REQUEST_BATCH payload into its parts
    (rank, epoch, hit indices, packed request blobs, trace ctx) —
    the interior's parse side of the merge.  Byte-exact: re-submitting
    the parts reproduces the flat path's processing verbatim."""
    rank, epoch, nbits = struct.unpack_from("<iII", payload)
    off = 12
    bitvec = payload[off:off + nbits]
    off += nbits
    idxs: List[int] = []
    for byte_i, b in enumerate(bitvec):
        while b:
            low = b & -b
            idxs.append(byte_i * 8 + low.bit_length() - 1)
            b ^= low
    (nreq,) = struct.unpack_from("<H", payload, off)
    off += 2
    blobs: List[bytes] = []
    for _ in range(nreq):
        start = off
        _req, off = Request.unpack(payload, off)
        blobs.append(payload[start:off])
    return rank, epoch, idxs, blobs, payload[off:]


def pack_subtree_batch(bits: List[Tuple[int, Tuple[int, ...],
                                        Tuple[int, ...]]],
                       reqs: List[Tuple[int, List[bytes]]],
                       arrivals: List[Tuple[int, bytes]],
                       counts: Dict[int, int]) -> bytes:
    """Assemble one merged envelope.  ``bits`` = (epoch, ranks, idxs)
    groups; ``reqs`` = (rank, packed blobs); ``arrivals`` = (rank, raw
    trace ctx); ``counts`` = cumulative per-rank upward frame counts."""
    sections: List[bytes] = []
    for epoch, ranks, idxs in bits:
        sections.append(
            struct.pack("<BIH", 0, epoch, len(ranks))
            + struct.pack(f"<{len(ranks)}i", *ranks)
            + struct.pack("<H", len(idxs))
            + (struct.pack(f"<{len(idxs)}I", *idxs) if idxs else b""))
    for rank, blobs in reqs:
        sections.append(struct.pack("<BiH", 1, rank, len(blobs))
                        + b"".join(blobs))
    for rank, ctx in arrivals:
        sections.append(struct.pack("<BiB", 2, rank, len(ctx)) + ctx)
    if counts:
        items = sorted(counts.items())
        sections.append(struct.pack("<BH", 3, len(items))
                        + b"".join(struct.pack("<iI", r, c)
                                   for r, c in items))
    return struct.pack("<H", len(sections)) + b"".join(sections)


def iter_subtree_sections(payload: bytes):
    """Yield the envelope's sections: ("bits", epoch, ranks, idxs),
    ("reqs", rank, [Request]), ("arrival", rank, ctx tuple | None),
    ("counts", {rank: cum})."""
    (n,) = struct.unpack_from("<H", payload)
    off = 2
    for _ in range(n):
        (kind,) = struct.unpack_from("<B", payload, off)
        off += 1
        if kind == 0:
            epoch, nranks = struct.unpack_from("<IH", payload, off)
            off += 6
            ranks = struct.unpack_from(f"<{nranks}i", payload, off)
            off += 4 * nranks
            (nidx,) = struct.unpack_from("<H", payload, off)
            off += 2
            idxs = struct.unpack_from(f"<{nidx}I", payload, off) \
                if nidx else ()
            off += 4 * nidx
            yield ("bits", epoch, list(ranks), list(idxs))
        elif kind == 1:
            rank, nreq = struct.unpack_from("<iH", payload, off)
            off += 6
            reqs = []
            for _r in range(nreq):
                req, off = Request.unpack(payload, off)
                reqs.append(req)
            yield ("reqs", rank, reqs)
        elif kind == 2:
            rank, clen = struct.unpack_from("<iB", payload, off)
            off += 5
            ctx = _trace.unpack_ctx(payload[off:off + clen], 0) \
                if clen else None
            off += clen
            yield ("arrival", rank, ctx)
        elif kind == 3:
            (nc,) = struct.unpack_from("<H", payload, off)
            off += 2
            counts: Dict[int, int] = {}
            for _c in range(nc):
                r, c = struct.unpack_from("<iI", payload, off)
                off += 8
                counts[r] = c
            yield ("counts", counts)
        else:  # pragma: no cover - version skew guard
            raise ValueError(f"unknown subtree section kind {kind}")


def merge_batch_items(items: List[Tuple]) -> Tuple[
        List[Tuple[int, Tuple[int, ...], Tuple[int, ...]]],
        List[Tuple[int, List[bytes]]],
        List[Tuple[int, bytes]]]:
    """Group buffered per-rank items for one envelope.  ``items``:
    ("bits", epoch, rank, idx tuple) singles or pre-grouped
    ("bits", epoch, ranks tuple, idxs) from a child envelope;
    ("reqs", rank, [blobs]); ("arrival", rank, ctx bytes).  Bits merge
    by (epoch, idx set) — the steady state collapses a subtree into a
    single group; request order per rank is preserved."""
    bit_groups: Dict[Tuple[int, Tuple[int, ...]], List[int]] = {}
    req_by_rank: Dict[int, List[bytes]] = {}
    req_order: List[int] = []
    arrivals: List[Tuple[int, bytes]] = []
    for item in items:
        kind = item[0]
        if kind == "bits":
            _k, epoch, ranks, idxs = item
            if isinstance(ranks, int):
                ranks = (ranks,)
            key = (epoch, tuple(sorted(idxs)))
            bit_groups.setdefault(key, []).extend(ranks)
        elif kind == "reqs":
            _k, rank, blobs = item
            if rank not in req_by_rank:
                req_order.append(rank)
                req_by_rank[rank] = []
            req_by_rank[rank].extend(blobs)
        elif kind == "arrival":
            arrivals.append((item[1], item[2]))
    bits = [(epoch, tuple(sorted(set(ranks))), idxs)
            for (epoch, idxs), ranks in sorted(bit_groups.items())]
    reqs = [(r, req_by_rank[r]) for r in req_order]
    return bits, reqs, arrivals


# ---------------------------------------------------------------------------
# The tree worker / relay transport
# ---------------------------------------------------------------------------

@dataclass
class _ChildLink:
    """One accepted child connection on an interior's relay listener.
    ``conn``/``grace_deadline``/``reported`` are mutated under
    TreeWorkerTransport._links_lock; the rx thread owns the reads."""

    rank: int
    conn: Optional[socket.socket]
    covers: set = field(default_factory=set)
    rx_thread: Optional[threading.Thread] = None
    grace_deadline: Optional[float] = None
    reported: bool = False


@dataclass
class _Pull:
    """One in-flight metrics/trace aggregation round at an interior."""

    kind: str                       # "m" | "t"
    rnd: int
    deadline: float
    got: Dict[int, bytes] = field(default_factory=dict)
    sent: bool = False


@_races.race_checked
class TreeWorkerTransport(T.WorkerTransport):
    """A non-root rank under the tree overlay.

    Leaves are plain workers whose "controller" is their parent's relay
    listener; interiors additionally accept their children, merge the
    subtree's upward traffic into per-tick envelopes, and relay every
    downward broadcast verbatim.  Reconnects ALWAYS target the root's
    session-resume listener (the re-parent path): the root is the
    session authority, and a re-parented interior keeps serving its
    own children on its new uplink — a lost parent flattens the tree,
    it never orphans a subtree.
    """

    def __init__(self, host: str, port: int, rank: int, layout: TreeLayout,
                 hostname: Optional[str] = None,
                 connect_timeout: float = 60.0):
        self.layout = layout
        # super().__init__ re-sets this; the child-accept phase below
        # runs first and needs it for ports/diagnostics.
        self.rank = rank
        self._root_host, self._root_port = host, port
        self._reparented = False
        self._children_ranks = layout.children(rank)
        self._links: Dict[int, _ChildLink] = {}
        self._links_lock = _lockorder.make_lock(
            "TreeWorkerTransport._links_lock")
        # Broadcasts that arrive between our own handshake completing
        # (uplink rx thread live) and the children's TOPO slices going
        # out must not overtake the handshake on the child links —
        # buffered here, flushed by _finish_children, so every child's
        # stream starts exactly at global index 0.
        # guarded_by: _links_lock
        self._relay_ready = False
        self._relay_buffer: List[Tuple[int, bytes]] = []
        self._pulls: Dict[Tuple[str, int], _Pull] = {}  # guarded_by: _pulls_lock
        self._pulls_lock = _lockorder.make_lock(
            "TreeWorkerTransport._pulls_lock")
        # Serializes an envelope's pop+send against the verbatim
        # forwards that must stay ORDERED BEHIND it: without it, the
        # ticker thread could pop a child's buffered batch, get
        # preempted, and let the child-rx thread ship a later WITHDRAW/
        # SIGNATURE first — inverting that child's frame order on the
        # merged stream.  Re-entrant: the forward path holds it across
        # flush_requests() + its own _send.
        self._flush_lock = _lockorder.make_rlock(
            "TreeWorkerTransport._flush_lock")
        # Buffered upward child traffic, merged into the next envelope.
        # Shares the flush path with the inherited _pending buffer, so
        # both ride ONE per-tick frame; guarded by the same _buf_lock
        # (created by super().__init__ — nothing touches these before
        # the child rx threads start, which is after that).
        self._child_items: List[Tuple] = []
        self._pending_frame_counts: Dict[int, int] = {}
        self._pending_counts: Dict[int, int] = {}
        self._fwd_count: Dict[int, int] = {}
        self._ticker: Optional[threading.Thread] = None
        self._hello_entries: List[Tuple[int, str, str]] = []
        self._child_hellos: Dict[int, List[Tuple[int, str, str]]] = {}
        self._srv: Optional[socket.socket] = None
        # Interiors collect their children's subtree HELLOs FIRST: the
        # merged HELLO_TREE this rank sends upward must cover the whole
        # subtree before the root will complete its handshake.
        if self._children_ranks:
            self._accept_children(port)
        parent = layout.parent(rank)
        phost, pport = parent_address(host, port, parent)
        super().__init__(phost, pport, rank, hostname=hostname,
                         connect_timeout=connect_timeout)
        # Handshake done: hand each child its TOPO slice, arm frame
        # deadlines, start the relay rx threads + the merge ticker.
        if self._children_ranks:
            self._finish_children()

    # -- bootstrap ---------------------------------------------------------
    def _accept_children(self, controller_port: int) -> None:
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind(("0.0.0.0", relay_port(controller_port, self.rank)))
        srv.listen(len(self._children_ranks))
        accept_timeout = float(
            os.environ.get("HVD_TPU_CONNECT_TIMEOUT", "120"))
        srv.settimeout(accept_timeout)
        self._srv = srv
        got: Dict[int, socket.socket] = {}
        for _ in range(len(self._children_ranks)):
            try:
                conn, _addr = srv.accept()
            except socket.timeout:
                missing = sorted(set(self._children_ranks) - set(got))
                raise TimeoutError(
                    f"tree rank {self.rank}: child ranks "
                    f"{missing} did not connect within "
                    f"{accept_timeout}s") from None
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            ftype, payload = T._recv_frame(conn)
            if ftype != T.FRAME_HELLO_TREE:
                raise RuntimeError(
                    f"tree rank {self.rank}: expected "
                    f"HELLO_TREE from a child, got {ftype}")
            entries = parse_hello_tree(payload)
            child = entries[0][0]
            self._child_hellos[child] = entries
            got[child] = conn
            with self._links_lock:
                self._links[child] = _ChildLink(
                    rank=child, conn=conn,
                    covers={r for r, _h, _f in entries})
        # Children are in; the relay listener's job is done (reconnects
        # go to the root, never back through an interior).
        srv.close()
        self._srv = None

    def _handshake(self, hostname: Optional[str]) -> None:
        from . import compression as _compression

        own = (self.rank, hostname or socket.gethostname(),
               _compression.env_fingerprint())
        entries = [own]
        for child in self._children_ranks:
            entries.extend(self._child_hellos.get(child, []))
        self._hello_entries = entries
        T._send_frame(self._sock, T.FRAME_HELLO_TREE,
                      pack_hello_tree(entries))
        ftype, payload = T._recv_frame(self._sock)
        if ftype != T.FRAME_TOPO_TREE:
            raise RuntimeError(
                f"tree rank {self.rank} expected TOPO_TREE from its "
                f"parent, got {ftype}")
        cache_flag, topo_map = parse_topo_tree(payload)
        self.controller_cache = bool(cache_flag)
        self.topology = topo_map[self.rank]
        self._topo_map = topo_map

    def _finish_children(self) -> None:
        with self._links_lock:
            links = list(self._links.values())
        for link in links:
            slice_entries = [(r, self._topo_map[r])
                             for r in sorted(link.covers)]
            T._send_frame(link.conn, T.FRAME_TOPO_TREE,
                          pack_topo_tree(
                              1 if self.controller_cache else 0,
                              slice_entries))
            link.conn.settimeout(T._frame_timeout())
            th = threading.Thread(
                target=self._child_rx, args=(link,),
                name=f"hvd-tree-rx-{self.rank}-{link.rank}", daemon=True)
            link.rx_thread = th
            th.start()
        # Drain-then-arm: buffered frames go out BEFORE ready flips, so
        # a concurrently arriving broadcast (which keeps buffering
        # until ready) can never overtake them on a child link.
        while True:
            with self._links_lock:
                if not self._relay_buffer:
                    self._relay_ready = True
                    break
                buffered, self._relay_buffer = self._relay_buffer, []
            for ftype, payload in buffered:
                self._relay_send(ftype, payload)
        tick = float(os.environ.get("HOROVOD_CYCLE_TIME", 5.0)) / 1000.0
        self._ticker = threading.Thread(
            target=self._tick_loop, args=(max(0.001, tick),),
            name=f"hvd-tree-tick-{self.rank}", daemon=True)
        self._ticker.start()

    # -- downward relay ----------------------------------------------------
    def _relay_downward(self, ftype: int, payload: bytes) -> None:
        with self._links_lock:
            if not self._relay_ready:
                if self._links:
                    self._relay_buffer.append((ftype, payload))
                return
        self._relay_send(ftype, payload)

    def _relay_send(self, ftype: int, payload: bytes) -> None:
        # Snapshot (link, conn) PAIRS: _drop_link (a concurrent child
        # rx thread seeing EOF) nulls link.conn, and dereferencing it
        # again after the lock would raise AttributeError — which the
        # OSError handler below does not catch, and which would kill
        # the uplink rx thread and stall the whole subtree.
        with self._links_lock:
            links = [(l, l.conn) for l in self._links.values()
                     if l.conn is not None]
        for link, conn in links:
            if _chaos.active() \
                    and _chaos.fire("tree.relay_reset") is not None:
                # The "interior node died" wire effect on ONE child
                # link: the child's recv fails and it re-parents to
                # the root (deterministically testable — the chaos
                # matrix tree_interior_down scenario).
                T._hard_close(conn)
                self._drop_link(link,
                                "hvd-chaos: tree.relay_reset")
                continue
            try:
                # No dup: each child's downward stream must stay the
                # root broadcast stream index-exact (the re-parent
                # resume replays from that global index).
                T._send_frame_or_fault(conn, ftype, payload,
                                       allow_dup=False)
                T._M_TREE_RELAYED.inc()
            except OSError as e:
                self._drop_link(link, f"relay send failed: {e}")

    # -- upward relay (child rx threads) -----------------------------------
    def _child_rx(self, link: _ChildLink) -> None:  # thread: rx
        _athreads.set_role("rx")
        try:
            self._child_rx_inner(link)
        except Exception:
            import traceback

            _telemetry.exception_event(
                "tree-child-rx", traceback.format_exc())
            raise

    def _child_rx_inner(self, link: _ChildLink) -> None:
        conn = link.conn
        while True:
            try:
                ftype, payload = T._recv_frame(
                    conn, peer=f"child rank {link.rank}")
            except OSError:
                ftype = None
            if ftype is None:
                if not (self._closing
                        or self.shutdown_received.is_set()):
                    self._drop_link(link, "eof")
                return
            if ftype == T.FRAME_REQUEST_BATCH:
                rank, epoch, idxs, blobs, tail = \
                    parse_request_batch(payload)
                with self._buf_lock:
                    if idxs:
                        self._child_items.append(
                            ("bits", epoch, (rank,), tuple(idxs)))
                    if blobs:
                        self._child_items.append(("reqs", rank, blobs))
                    if tail:
                        self._child_items.append(("arrival", rank, tail))
                    self._pending_frame_counts[link.rank] = \
                        self._pending_frame_counts.get(link.rank, 0) + 1
                T._M_TREE_MERGED.inc()
            elif ftype == T.FRAME_SUBTREE_BATCH:
                self._buffer_child_envelope(link, payload)
                T._M_TREE_MERGED.inc()
            elif ftype in (T.FRAME_METRICS, T.FRAME_METRICS_TREE,
                           T.FRAME_TRACE, T.FRAME_TRACE_TREE):
                kind = "m" if ftype in (T.FRAME_METRICS,
                                        T.FRAME_METRICS_TREE) else "t"
                if ftype in (T.FRAME_METRICS, T.FRAME_TRACE):
                    crank, rnd = struct.unpack_from("<iI", payload)
                    entries = [(crank, payload[8:])]
                else:
                    rnd, entries = parse_merged_pull(payload)
                with self._buf_lock:
                    self._pending_frame_counts[link.rank] = \
                        self._pending_frame_counts.get(link.rank, 0) + 1
                self._pull_add(kind, rnd, entries)
                T._M_TREE_MERGED.inc()
            else:
                # WITHDRAW / SIGNATURE / PONG / SHUTDOWN / CHILD_LOST /
                # legacy REQUEST: forward verbatim, AFTER flushing any
                # buffered batches so this child's frame order is
                # preserved on the merged stream.  _flush_lock makes
                # flush+forward atomic against the ticker's own flush.
                with self._flush_lock:
                    self.flush_requests()
                    self._send(ftype, payload)
                with self._buf_lock:
                    self._fwd_count[link.rank] = \
                        self._fwd_count.get(link.rank, 0) + 1

    def _buffer_child_envelope(self, link: _ChildLink,
                               payload: bytes) -> None:
        """A child interior's merged envelope: keep its groups intact
        (they re-merge with ours), max-merge its cumulative counts."""
        with self._buf_lock:
            for sec in iter_subtree_sections(payload):
                kind = sec[0]
                if kind == "bits":
                    _k, epoch, ranks, idxs = sec
                    self._child_items.append(
                        ("bits", epoch, tuple(ranks), tuple(idxs)))
                elif kind == "reqs":
                    _k, rank, reqs = sec
                    self._child_items.append(
                        ("reqs", rank, [r.pack() for r in reqs]))
                elif kind == "arrival":
                    _k, rank, ctx = sec
                    if ctx is not None:
                        self._child_items.append(
                            ("arrival", rank,
                             struct.pack("<IIQ", ctx[0], ctx[1],
                                         ctx[2])))
                elif kind == "counts":
                    for r, c in sec[1].items():
                        if c > self._pending_counts.get(r, 0):
                            self._pending_counts[r] = c
            self._pending_frame_counts[link.rank] = \
                self._pending_frame_counts.get(link.rank, 0) + 1

    # -- the per-tick merge ------------------------------------------------
    def flush_requests(self) -> None:
        """Ship the tick's merged envelope: this rank's own pending
        requests/bits PLUS everything its children delivered since the
        last tick, as ONE FRAME_SUBTREE_BATCH (leaves fall back to the
        flat FRAME_REQUEST_BATCH their parent knows how to merge)."""
        if not self._children_ranks:
            super().flush_requests()
            return
        with self._flush_lock:
            self._flush_requests_merged()

    def _flush_requests_merged(self) -> None:
        # guarded_by: _flush_lock (pop-to-send must be atomic vs the
        # verbatim-forward path — see _flush_lock's comment)
        with self._buf_lock:
            own, self._pending = self._pending, []
            items = self._child_items
            self._child_items = []
            frame_counts = self._pending_frame_counts
            self._pending_frame_counts = {}
            merged_counts = self._pending_counts
            self._pending_counts = {}
            for r, n in frame_counts.items():
                self._fwd_count[r] = self._fwd_count.get(r, 0) + n
            for r, c in merged_counts.items():
                if c > self._fwd_count.get(r, 0):
                    self._fwd_count[r] = c
            counts = dict(self._fwd_count)
        own_items: List[Tuple] = []
        by_epoch: Dict[int, List[int]] = {}
        blobs: List[bytes] = []
        for item in own:
            if item[0] == "bit":
                by_epoch.setdefault(item[1], []).append(item[2])
            else:
                blobs.append(item[1])
        for epoch in sorted(by_epoch):
            own_items.append(("bits", epoch, (self.rank,),
                              tuple(by_epoch[epoch])))
        if blobs:
            own_items.append(("reqs", self.rank, blobs))
        if own:
            own_items.append(("arrival", self.rank, _trace.pack_ctx()))
        all_items = own_items + items
        if not all_items:
            return
        bits, reqs, arrivals = merge_batch_items(all_items)
        T._M_BATCH_REQS.inc(sum(len(b) for _r, b in reqs))
        T._M_BATCH_BITS.inc(sum(len(i) for _e, rs, i in bits
                                for _rr in rs))
        _flight.record("frame_tx_subtree", len(bits), len(reqs))
        self._send(T.FRAME_SUBTREE_BATCH,
                   pack_subtree_batch(bits, reqs, arrivals, counts))

    # -- metrics / trace pull aggregation ----------------------------------
    def _expected_pull(self) -> int:
        return len(self.layout.subtree(self.rank))

    def _pull_add(self, kind: str, rnd: int,
                  entries: List[Tuple[int, bytes]]) -> None:
        supplement: List[Tuple[int, bytes]] = []
        with self._pulls_lock:
            key = (kind, rnd)
            pull = self._pulls.get(key)
            if pull is None:
                pull = _Pull(kind=kind, rnd=rnd,
                             deadline=time.monotonic() + pull_timeout())
                self._pulls[key] = pull
            if pull.sent:
                # Entries landing AFTER a partial flush (every level
                # of a deep tree arms the same deadline, so a child
                # interior's own partial flush can lose the race to
                # ours): forward them as a SUPPLEMENTARY merged frame
                # instead of dropping a whole live subtree from the
                # pull — the root's round dict accepts entries for as
                # long as the round's waiter is live.
                supplement = [(r, b) for r, b in entries
                              if r not in pull.got]
                for rank, blob in supplement:
                    pull.got[rank] = blob
            else:
                for rank, blob in entries:
                    pull.got[rank] = blob
            ready = (not pull.sent
                     and len(pull.got) >= self._expected_pull())
        if supplement:
            ftype = T.FRAME_METRICS_TREE if kind == "m" \
                else T.FRAME_TRACE_TREE
            self._send(ftype, pack_merged_pull(rnd, sorted(supplement)))
        if ready:
            self._pull_flush(kind, rnd)

    def _pull_flush(self, kind: str, rnd: int) -> None:
        with self._pulls_lock:
            pull = self._pulls.get((kind, rnd))
            if pull is None or pull.sent:
                return
            pull.sent = True
            entries = sorted(pull.got.items())
        ftype = T.FRAME_METRICS_TREE if kind == "m" \
            else T.FRAME_TRACE_TREE
        self._send(ftype, pack_merged_pull(rnd, entries))

    def _answer_metrics(self, rnd: int) -> None:
        if not self._children_ranks:
            super()._answer_metrics(rnd)
            return
        self._pull_add("m", rnd, [(self.rank, self._metrics_snapshot())])

    def _answer_trace(self, rnd: int) -> None:
        if not self._children_ranks:
            super()._answer_trace(rnd)
            return
        self._pull_add("t", rnd, [(self.rank, self._trace_snapshot())])

    # -- link health / sweeps ----------------------------------------------
    def _drop_link(self, link: _ChildLink, why: str) -> None:
        with self._links_lock:
            conn, link.conn = link.conn, None
            if conn is not None:
                T._wake_close(conn)
            if self._closing or link.reported:
                return
            if link.grace_deadline is None:
                link.grace_deadline = (time.monotonic()
                                       + T._grace_seconds())
                _flight.record("tree_link_down", link.rank, why)
                print(f"[hvd-tree] rank {self.rank}: child rank "
                      f"{link.rank} link lost ({why}); it should "
                      f"re-parent to the root", file=sys.stderr)

    def _sweep(self) -> None:
        now = time.monotonic()
        report: List[Tuple[int, set]] = []
        with self._links_lock:
            for link in self._links.values():
                if (link.grace_deadline is not None
                        and not link.reported
                        and now > link.grace_deadline):
                    link.reported = True
                    report.append((link.rank, set(link.covers)))
        for crank, covers in report:
            # Escalate to the root (the liveness arbiter): every rank
            # this link covered is unreachable VIA US; ranks that
            # re-parented meanwhile are ignored there.
            self.flush_requests()
            for r in sorted(covers):
                reason = (f"child link of interior rank {self.rank} "
                          f"died without re-parent")
                rb = reason.encode("utf-8")
                self._send(T.FRAME_CHILD_LOST,
                           struct.pack("<iH", r, len(rb)) + rb)
        overdue: List[Tuple[str, int]] = []
        with self._pulls_lock:
            for key, pull in list(self._pulls.items()):
                if pull.sent and now > pull.deadline:
                    del self._pulls[key]  # straggler window over
                elif not pull.sent and now > pull.deadline:
                    if pull.got:
                        overdue.append(key)
                    else:
                        del self._pulls[key]
        for kind, rnd in overdue:
            # Partial flush: a dead subtree member must not starve the
            # root's pull of the live members' snapshots.
            self._pull_flush(kind, rnd)

    def _tick_loop(self, tick: float) -> None:  # thread: ticker
        _athreads.set_role("ticker")
        while not self._closing:
            time.sleep(tick)
            try:
                self.flush_requests()
                self._sweep()
            except OSError:
                pass  # uplink mid-reconnect; the ring buffers for us
            except Exception:  # noqa: BLE001 — a dead ticker would
                # silently stall the whole subtree's merge cadence;
                # dump the forensic trail and keep ticking.
                import traceback

                _telemetry.exception_event(
                    "tree-ticker", traceback.format_exc())

    # -- failure propagation / reconnect -----------------------------------
    def _poison(self, detail: str) -> None:
        # The subtree below us can no longer reach the root either:
        # hand children the same synthetic SHUTDOWN diagnosis so they
        # fail loudly instead of idling on a silent stream.  (This
        # frame is outside the root's broadcast stream, but poison is
        # terminal — nobody resumes from it.)
        resp = Response(
            ResponseType.SHUTDOWN,
            error_message="Horovod has been shut down: interior tree "
            f"rank {self.rank} lost the controller ({detail}).")
        payload = wire.pack_response_list([resp]) + _trace.pack_ctx()
        with self._links_lock:
            links = [l for l in self._links.values()
                     if l.conn is not None]
        for link in links:
            try:
                T._send_frame(link.conn, T.FRAME_RESPONSES, payload)
            except OSError:
                pass
        super()._poison(detail)

    def _reconnect(self) -> Optional[str]:
        if not self._reparented and (self._host, self._port) != (
                self._root_host, self._root_port):
            # Re-parent: the root runs the only session-resume listener
            # (interior relays do not resume).  A re-parented interior
            # keeps its children — the subtree rides the new uplink.
            print(f"[hvd-tree] rank {self.rank}: parent link lost; "
                  f"re-parenting to the root controller at "
                  f"{self._root_host}:{self._root_port}",
                  file=sys.stderr)
            _flight.record("tree_reparent_attempt", self.rank)
            self._host, self._port = self._root_host, self._root_port
            self._reparented = True
        return super()._reconnect()

    def close(self) -> None:
        with self._links_lock:
            links = list(self._links.values())
            self._links = {}
        for link in links:
            if link.conn is not None:
                T._wake_close(link.conn)
        if self._srv is not None:
            try:
                self._srv.close()
            except OSError:
                pass
        super().close()


# ---------------------------------------------------------------------------
# Dryrun simulation (tests/test_tree.py bounds the root's frames with it)
# ---------------------------------------------------------------------------

def steady_envelope(layout: TreeLayout, child: int, epoch: int,
                    idxs: Sequence[int]) -> bytes:
    """The envelope one direct-root child ships for a steady-state tick
    where every rank of its subtree hit the same cache entries — built
    through the SAME grouping path the live interiors run."""
    items = [("bits", epoch, (r,), tuple(idxs))
             for r in layout.subtree(child)]
    bits, reqs, arrivals = merge_batch_items(items)
    counts = {r: 1 for r in layout.subtree(child) if r != child}
    return pack_subtree_batch(bits, reqs, arrivals, counts)


def simulate_cycle_frames(world: int,
                          fanout: Optional[int] = None) -> Dict[str, int]:
    """Frame accounting for one steady-state negotiation cycle and one
    metrics/trace pull, flat vs tree — the quantity
    tests/test_tree.py bounds (rank-0 rx frames <= c * fanout *
    log_fanout(world))."""
    layout = build_layout(world, fanout)
    root_children = len(layout.children(0))
    return {
        "world": world,
        "fanout": layout.fanout,
        "depth": layout.depth(),
        "flat_frames_per_cycle": world - 1,
        "tree_frames_per_cycle": root_children,
        "flat_frames_per_pull": world - 1,
        "tree_frames_per_pull": root_children,
        "interior_ranks": len(layout.interior_ranks()),
    }
