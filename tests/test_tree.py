"""Tree-structured control-plane overlay (ops/tree.py).

Unit coverage of the layout math, the merged wire formats and the
root-side aggregation equivalence, plus an np=3 REAL-process leg
(controller + interior + leaf over TCP loopback, no XLA — the chaos cp
fleet machinery) asserting the tree's negotiation results are
byte-identical to the flat star's and that cache replicas stay
index-aligned across an interior merge.
"""

import math
import os
import socket
import struct

import pytest

from horovod_tpu.ops import cache as cache_mod
from horovod_tpu.ops import transport as T
from horovod_tpu.ops import tree
from horovod_tpu.ops import wire
from horovod_tpu.ops.wire import Request


def _req(rank, name, shape=(8,)):
    return Request(rank, wire.RequestType.ALLREDUCE,
                   wire.DataType.FLOAT32, name, -1, -1, shape,
                   wire.ReduceOp.SUM, 0, ())


# ---------------------------------------------------------------------------
# Layout
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("world", [2, 3, 4, 7, 8, 9, 17, 64, 256, 1024])
@pytest.mark.parametrize("fanout", [1, 2, 4, 8])
def test_layout_invariants(world, fanout):
    layout = tree.build_layout(world, fanout)
    assert layout.order[0] == 0
    assert sorted(layout.order) == list(range(world))
    seen = set()
    for r in range(world):
        assert len(layout.children(r)) <= fanout
        # every rank walks up to the root without cycles
        hops = 0
        cur = r
        while cur != 0:
            cur = layout.parent(cur)
            hops += 1
            assert hops <= world
        seen.add(r)
        if fanout > 1:
            assert hops <= math.ceil(math.log(max(world, 2), fanout)) + 1
    assert seen == set(range(world))
    # subtrees partition the world under the root
    covered = [0]
    for c in layout.children(0):
        covered.extend(layout.subtree(c))
    assert sorted(covered) == list(range(world))


def test_layout_slice_major_ordering(monkeypatch):
    # 8 ranks, 2 virtual slices: subtrees must nest inside slices —
    # the ICI x DCN contract replica_hierarchy applies to the data
    # plane, applied here to the control plane's tree shape.
    monkeypatch.setenv("HVD_TPU_VIRTUAL_SLICES", "2")
    layout = tree.build_layout(8, 2)
    # slice 0 = ranks 0..3, slice 1 = ranks 4..7; the order visits
    # slice 0 (sans root) before slice 1
    rest = [r for r in layout.order[1:]]
    assert rest == sorted(rest, key=lambda r: (r // 4, r))


@pytest.mark.parametrize("world", [64, 256, 1024])
def test_root_frames_drop_from_linear_to_fanout_log(world):
    stats = tree.simulate_cycle_frames(world, 8)
    flat = stats["flat_frames_per_cycle"]
    got = stats["tree_frames_per_cycle"]
    bound = 2 * 8 * max(1, math.ceil(math.log(world, 8)))
    assert got <= bound, (world, got, bound)
    assert got < flat / 4
    assert stats["tree_frames_per_pull"] == got


def test_tree_active_modes(monkeypatch):
    monkeypatch.setenv(tree.TREE_ENV, "off")
    assert not tree.tree_active(4096)
    monkeypatch.setenv(tree.TREE_ENV, "on")
    assert tree.tree_active(3)
    assert not tree.tree_active(2)  # a 2-rank "tree" IS the star
    monkeypatch.setenv(tree.TREE_ENV, "auto")
    monkeypatch.setenv(tree.THRESHOLD_ENV, "16")
    assert not tree.tree_active(15)
    assert tree.tree_active(16)


def test_validate_env_rejects_typos(monkeypatch):
    monkeypatch.setenv(tree.TREE_ENV, "sometimes")
    with pytest.raises(ValueError, match="auto, on or off"):
        tree.validate_env()
    monkeypatch.setenv(tree.TREE_ENV, "auto")
    monkeypatch.setenv(tree.FANOUT_ENV, "0")
    with pytest.raises(ValueError, match="expected >= 1"):
        tree.validate_env()


# ---------------------------------------------------------------------------
# Wire round trips
# ---------------------------------------------------------------------------

def test_hello_topo_roundtrip():
    entries = [(3, "hostA", "K=a;L=b"), (5, "hostB", "K=a;L=b")]
    assert tree.parse_hello_tree(tree.pack_hello_tree(entries)) == entries
    topo = [(3, T.Topology(0, 2, 1, 2)), (5, T.Topology(1, 2, 1, 2))]
    flag, parsed = tree.parse_topo_tree(tree.pack_topo_tree(1, topo))
    assert flag == 1
    assert parsed == dict(topo)


def test_merged_pull_roundtrip():
    entries = [(1, b'{"a": 1}'), (2, b"[]"), (7, b"")]
    rnd, out = tree.parse_merged_pull(tree.pack_merged_pull(42, entries))
    assert rnd == 42 and out == entries


def test_request_batch_parse_is_byte_exact():
    # Build a flat FRAME_REQUEST_BATCH payload the way the worker does.
    reqs = [_req(2, "a"), _req(2, "b", shape=(4, 4))]
    idxs = [0, 3, 9]
    arr = bytearray(max(idxs) // 8 + 1)
    for b in idxs:
        arr[b // 8] |= 1 << (b % 8)
    bitvec = bytes(arr)
    blob = b"".join(r.pack() for r in reqs)
    payload = (struct.pack("<iII", 2, 5, len(bitvec)) + bitvec
               + struct.pack("<H", len(reqs)) + blob + b"\x00" * 16)
    rank, epoch, got_idxs, blobs, ctx = tree.parse_request_batch(payload)
    assert (rank, epoch) == (2, 5)
    assert got_idxs == idxs
    assert b"".join(blobs) == blob
    assert len(ctx) == 16
    # re-parsed requests are field-identical
    for raw, orig in zip(blobs, reqs):
        back, _ = Request.unpack(raw)
        assert back.tensor_name == orig.tensor_name
        assert tuple(back.tensor_shape) == tuple(orig.tensor_shape)


def test_subtree_batch_roundtrip_and_grouping():
    items = [
        ("bits", 1, (2,), (0, 1)),
        ("bits", 1, (3,), (0, 1)),     # same entries -> same group
        ("bits", 2, (4,), (0,)),       # different epoch -> own group
        ("reqs", 2, [_req(2, "x").pack()]),
        ("arrival", 3, b"\x01" * 16),
    ]
    bits, reqs, arrivals = tree.merge_batch_items(items)
    assert bits == [(1, (2, 3), (0, 1)), (2, (4,), (0,))]
    payload = tree.pack_subtree_batch(bits, reqs, arrivals,
                                      {2: 7, 3: 9})
    secs = list(tree.iter_subtree_sections(payload))
    kinds = [s[0] for s in secs]
    assert kinds == ["bits", "bits", "reqs", "arrival", "counts"]
    assert secs[0][1:] == (1, [2, 3], [0, 1])
    assert secs[1][1:] == (2, [4], [0])
    assert secs[2][1] == 2 and secs[2][2][0].tensor_name == "x"
    assert secs[3][1] == 3 and secs[3][2] is not None
    assert secs[4][1] == {2: 7, 3: 9}


def test_merged_envelope_drives_cache_like_flat_bits():
    """Root-side equivalence: feeding a whole subtree's steady-state
    envelope through the section iterator accounts the IDENTICAL
    per-rank hits the flat per-rank frames would — same entries ready,
    same pending sets (cache-replica alignment across the merge)."""
    def build_cache(ranks):
        cache = cache_mod.ResponseCache(rank=0)
        for name in ("g0", "g1"):
            cache.stage_negotiated(
                name, {rr: _req(rr, name) for rr in ranks})
            resp = wire.Response(
                wire.ResponseType.ALLREDUCE, tensor_names=[name],
                tensor_shapes=[(8,)],
                tensor_type=wire.DataType.FLOAT32)
            cache.observe_response(resp)
        return cache

    ranks = [0, 1, 2, 3, 4]
    layout = tree.build_layout(5, 2)
    epoch = 0
    idxs = [0, 1]

    flat = build_cache(ranks)
    for r in ranks:
        for i in idxs:
            assert flat.hit_from_wire(i, r, epoch) is None
    flat_ready = flat.take_ready(lambda _p: 1 << 20)

    merged = build_cache(ranks)
    for i in idxs:  # rank 0's own hits
        assert merged.hit_from_wire(i, 0, epoch) is None
    for child in layout.children(0):
        env = tree.steady_envelope(layout, child, epoch, idxs)
        for sec in tree.iter_subtree_sections(env):
            if sec[0] == "bits":
                _k, ep, rs, ii = sec
                for r in rs:
                    for i in ii:
                        assert merged.hit_from_wire(i, r, ep) is None
    merged_ready = merged.take_ready(lambda _p: 1 << 20)
    assert [r.tensor_names for r in flat_ready[0]] \
        == [r.tensor_names for r in merged_ready[0]]
    assert flat_ready[1:] == merged_ready[1:]


# ---------------------------------------------------------------------------
# np=3 real-process leg: flat vs tree byte identity
# ---------------------------------------------------------------------------

def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_cp_fleet(extra_env):
    """One np=3 cp fleet pass (the chaos matrix machinery: real
    processes, real sockets, no XLA); returns {rank: result-line}."""
    from horovod_tpu.chaos import matrix as M

    s = M.Scenario("tree_identity", "cp", "complete", np=3, cap=120.0,
                   env=dict(extra_env))
    p = M._run_pass(s, faulted=False)
    assert p.rc == 0, f"fleet pass failed (rc={p.rc}):\n" \
        + "\n".join(p.output.splitlines()[-30:])
    assert sorted(p.results) == [0, 1, 2], p.results
    return p.results, p.output


def test_np3_tree_results_byte_identical_to_flat():
    """The tentpole contract: controller + interior + leaf (fanout=1
    chain) produce negotiation records BYTE-IDENTICAL to the flat
    star's, with the response cache replicas index-aligned across the
    interior's merged frames (a desync would abort the run), and the
    fleet metrics pull answered by every rank through the merged
    FRAME_METRICS_TREE path."""
    base = {"HVD_TPU_CHAOS_CP_STEPS": "12",
            "HVD_TPU_TREE_PORT_BASE": str(_free_port())}
    flat_results, _ = _run_cp_fleet({**base, "HVD_TPU_TREE": "off"})
    tree_results, tree_out = _run_cp_fleet(
        {**base, "HVD_TPU_TREE": "on", "HVD_TPU_TREE_FANOUT": "1"})
    # identical records on every rank, and tree == flat bit-for-bit
    assert tree_results == flat_results
    assert len(set(tree_results.values())) == 1


def test_np3_tree_direct_leaves_fanout8():
    """The other np=3 shape: fanout 8 puts BOTH workers directly under
    the root (tree mode with no interior).  Leaves speak the flat
    FRAME_REQUEST_BATCH their parent merges — here the parent IS the
    root, which must accept it alongside envelopes."""
    results, _ = _run_cp_fleet({
        "HVD_TPU_CHAOS_CP_STEPS": "8",
        "HVD_TPU_TREE_PORT_BASE": str(_free_port()),
        "HVD_TPU_TREE": "on", "HVD_TPU_TREE_FANOUT": "8"})
    assert len(set(results.values())) == 1


def test_np3_tree_memory_gauges_aggregate_exactly():
    """hvd-mem satellite: the np=3 FRAME_METRICS_TREE pull must carry
    the memory gauge family from EVERY rank through the interior's
    merge, with fleet min/max/mean exact.  Each cp rank seeds a
    rank-keyed ledger entry ((rank+1) MiB); the controller asserts the
    aggregated gauge per-rank values and min/max/mean bit-for-bit
    (chaos.matrix._check_mem_gauges _diags on any mismatch) and prints
    the CHAOS_MEMGAUGES marker only when exact."""
    results, out = _run_cp_fleet({
        "HVD_TPU_CHAOS_CP_STEPS": "12",
        "HVD_TPU_TREE_PORT_BASE": str(_free_port()),
        "HVD_TPU_TREE": "on", "HVD_TPU_TREE_FANOUT": "1"})
    assert len(set(results.values())) == 1
    assert "CHAOS_MEMGAUGES ranks=3 ok" in out


def test_np3_flat_memory_gauges_aggregate_exactly():
    """Same exactness contract over the flat FRAME_METRICS star — the
    baseline the tree merge must match."""
    _, out = _run_cp_fleet({
        "HVD_TPU_CHAOS_CP_STEPS": "12",
        "HVD_TPU_TREE_PORT_BASE": str(_free_port()),
        "HVD_TPU_TREE": "off"})
    assert "CHAOS_MEMGAUGES ranks=3 ok" in out


def test_np3_tree_cache_replicas_survive_interior_merge():
    """Cache-replica alignment: with the response cache ON (the fleet
    default) the steady state broadcasts compact FRAME_RESPONSE_BATCH
    index frames, which every rank — including the leaf BEHIND the
    interior — must rebuild from an index-aligned replica.  A replica
    desync fails the run loudly, so a green pass with replays IS the
    alignment proof; we additionally require replays actually happened
    on a worker."""
    base = {"HVD_TPU_CHAOS_CP_STEPS": "12",
            "HVD_TPU_TREE_PORT_BASE": str(_free_port()),
            "HVD_TPU_TREE": "on", "HVD_TPU_TREE_FANOUT": "1"}
    results, out = _run_cp_fleet(base)
    assert len(set(results.values())) == 1
    assert "replica desync" not in out
