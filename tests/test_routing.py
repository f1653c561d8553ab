"""hvd-route: the fleet router tier (docs/routing.md).

The load-bearing test here is the chain-hash byte-identity gate: the
router derives prompt-header keys with ``routing/affinity.py`` and the
replica indexes pages with ``serving/kv_cache.py`` — if the two schemes
ever diverge (dtype, page alignment, fingerprint seed), affinity
routing silently goes cold with no error anywhere.  The rest covers the
router's scoring/failover state machine and the fleet autoscaler over
in-memory fake replicas (the same four-method client surface
``HttpReplicaClient`` implements for real fleets).
"""

import pytest

from horovod_tpu.routing import affinity
from horovod_tpu.routing.autoscale import AutoscaleConfig, FleetAutoscaler
from horovod_tpu.routing.replica import ReplicaUnreachable
from horovod_tpu.routing.router import Router, RouterConfig

PAGE = 4
PPS = 4
FP = "fp-router-test"


def _complete(prompt, n):
    """A rolling-hash completion oracle: state is a pure fold over
    tokens-so-far, so a continuation from any partial point reproduces
    the uninterrupted rollout exactly — the same bitwise property the
    serving engine's greedy decode has."""
    s = 0
    for t in prompt:
        s = (s * 1103515245 + int(t) + 12345) & 0x7FFFFFFF
    out = []
    for _ in range(n):
        t = (s * 48271 + 11) % 251
        out.append(t)
        s = (s * 1103515245 + t + 12345) & 0x7FFFFFFF
    return out


class FakeReplica:
    """In-memory replica speaking the router's client surface."""

    def __init__(self, name, queue_depth=0, kv_free=64,
                 fingerprint=FP, ready=True):
        self.name = name
        self.queue_depth = queue_depth
        self.kv_free = kv_free
        self.fingerprint = fingerprint
        self.ready = ready
        self.prefix_index = []   # hex digests advertised in /healthz
        self.chains = []         # token chains for /prefixes and /drain
        self.drain_after = None  # emit this many tokens, then 503
        self.unreachable = False
        self.resumed = []
        self.generated = 0

    def health(self):
        if self.unreachable:
            raise ReplicaUnreachable(self.name)
        return 200, {"serving": {
            "ready": self.ready, "queue_depth": self.queue_depth,
            "kv_free_pages": self.kv_free, "kv_total_pages": 64,
            "page_size": PAGE, "pages_per_slot": PPS,
            "fingerprint": self.fingerprint,
            "prefix_index": list(self.prefix_index)}}

    def generate(self, payload, timeout=None):
        if self.unreachable:
            raise ReplicaUnreachable(self.name)
        self.generated += 1
        prompt = [int(t) for t in payload["tokens"]]
        m = int(payload["max_tokens"])
        if self.drain_after is not None:
            k = min(self.drain_after, m)
            self.drain_after = None
            self.ready = False
            return 503, {"tokens": _complete(prompt, k),
                         "finish_reason": "draining"}
        return 200, {"tokens": _complete(prompt, m),
                     "finish_reason": "length"}

    def drain(self):
        if self.unreachable:
            raise ReplicaUnreachable(self.name)
        self.ready = False
        return 200, {"requests": [],
                     "prefixes": [list(c) for c in self.chains]}

    def prefixes(self):
        return 200, {"prefixes": [list(c) for c in self.chains]}

    def resume(self, payload):
        self.resumed.append(payload)
        return 200, {"resumed": 0,
                     "seeded": len(payload.get("prefixes") or [])}


def _fleet(*reps):
    r = Router(RouterConfig(probe_base=0.0), sleep=lambda s: None)
    for rep in reps:
        r.add_replica(rep.name, rep)
    r.poll()
    return r


# -- satellite: chain-hash byte identity router <-> kv_cache --------------

def test_prompt_header_hashes_byte_identical_to_live_kv_cache():
    """The router-side header keys must be EXACTLY the keys a live
    PagedKVCache publishes and looks up — hex-decode the router's
    strings and compare them to the cache's index bytes."""
    from horovod_tpu.serving.kv_cache import PagedKVCache

    cache = PagedKVCache(n_layers=1, n_heads=1, head_dim=2,
                         max_slots=2, pages_per_slot=PPS,
                         page_size=PAGE, prefix_cache=True,
                         fingerprint=FP)
    tokens = [7, 3, 1, 4, 9, 2, 6, 8, 5, 0]  # 2 full pages + 2 tail

    # The raw scheme delegation, digest for digest.
    assert cache._chain_hashes(tokens, 2) == affinity.chain_hashes(
        FP.encode(), tokens, PAGE, 2)

    # Publish through the real slot path; the index keys must be the
    # router's published_page_hashes, byte for byte.
    cache.begin_slot(0, len(tokens))
    assert cache.publish_prefix(0, tokens) == 2
    published = affinity.published_page_hashes(FP.encode(), tokens,
                                               PAGE, PPS)
    assert len(published) == 2
    assert set(cache.export_prefix_hashes()) == set(published)
    assert set(cache._index) == {bytes.fromhex(h) for h in published}

    # The router's strict-prefix header bound mirrors lookup_prefix:
    # same page count hit on a warm lookup.
    header = affinity.prompt_header_hashes(FP.encode(), tokens,
                                           PAGE, PPS)
    assert len(cache.lookup_prefix(tokens)) == len(header) == 2

    # An exactly page-aligned prompt keeps one suffix token to prefill:
    # header is one page SHORTER than what the replica published.
    aligned = tokens[:8]
    assert len(affinity.prompt_header_hashes(FP.encode(), aligned,
                                             PAGE, PPS)) == 1
    assert len(cache.lookup_prefix(aligned)) == 1

    # Divergent fingerprint ⇒ disjoint keys (the seed is load-bearing).
    other = affinity.prompt_header_hashes(b"other-model", tokens,
                                          PAGE, PPS)
    assert not set(other) & set(header)


def test_prompt_header_hashes_edge_cases():
    fp = FP.encode()
    assert affinity.prompt_header_hashes(fp, [], PAGE, PPS) == []
    # Shorter than one page + suffix token: no header pages.
    assert affinity.prompt_header_hashes(fp, [1, 2, 3, 4], PAGE,
                                         PPS) == []
    # pages_per_slot caps the chain.
    long = list(range(6 * PAGE + 1))
    assert len(affinity.prompt_header_hashes(fp, long, PAGE, PPS)) == PPS
    # Chain property: a longer prompt's header extends the shorter's.
    a = affinity.prompt_header_hashes(fp, long[:9], PAGE, PPS)
    b = affinity.prompt_header_hashes(fp, long[:13], PAGE, PPS)
    assert b[:len(a)] == a


# -- router selection ------------------------------------------------------

def test_select_least_loaded():
    r0 = FakeReplica("r0", queue_depth=3)
    r1 = FakeReplica("r1", queue_depth=0)
    router = _fleet(r0, r1)
    name, affinity_pages = router.select([1, 2, 3, 4, 5])
    assert (name, affinity_pages) == ("r1", 0)


def test_select_affinity_outweighs_queue():
    prompt = list(range(2 * PAGE + 3))
    warm = affinity.prompt_header_hashes(FP.encode(), prompt, PAGE, PPS)
    r0 = FakeReplica("r0", queue_depth=1)
    r0.prefix_index = warm
    r1 = FakeReplica("r1", queue_depth=0)
    router = _fleet(r0, r1)
    name, pages = router.select(prompt)
    assert (name, pages) == ("r0", 2)  # score 1-2 < 0


def test_select_no_affinity_credit_for_foreign_fingerprint():
    prompt = list(range(2 * PAGE + 3))
    r0 = FakeReplica("r0", queue_depth=1, fingerprint="other-model")
    # Even advertising the right keys: a different model's pages are
    # not this prompt's KV.
    r0.prefix_index = affinity.prompt_header_hashes(
        FP.encode(), prompt, PAGE, PPS)
    r1 = FakeReplica("r1", queue_depth=0)
    router = _fleet(r1, r0)  # r1 polled config wins the fleet fp
    name, pages = router.select(prompt)
    assert (name, pages) == ("r1", 0)


def test_select_headroom_penalty_avoids_full_replica():
    r0 = FakeReplica("r0", queue_depth=0, kv_free=0)
    r1 = FakeReplica("r1", queue_depth=5)
    router = _fleet(r0, r1)
    name, _ = router.select(list(range(9)))
    assert name == "r1"


def test_select_deterministic_tie_break():
    r0 = FakeReplica("r0")
    r1 = FakeReplica("r1")
    router = _fleet(r0, r1)
    picks = {router.select([1, 2, 3, 4, 5])[0] for _ in range(5)}
    assert picks == {"r0"}  # name order breaks exact ties


# -- dispatch: failover + continuation merge -------------------------------

def test_dispatch_stamps_and_counts():
    r0 = FakeReplica("r0")
    router = _fleet(r0)
    status, resp = router.dispatch({"tokens": [5, 3, 8], "max_tokens": 6})
    assert status == 200
    assert resp["tokens"] == _complete([5, 3, 8], 6)
    assert resp["router"]["replica"] == "r0"
    assert resp["router"]["failovers"] == 0


def test_dispatch_drain_continuation_digest_identical():
    """A 503-with-partials mid-flight resubmits as a continuation; the
    merged completion must equal the uninterrupted single-replica
    rollout token for token."""
    prompt, m = [9, 1, 7, 7, 2], 12
    r0 = FakeReplica("r0")
    r0.drain_after = 5
    r1 = FakeReplica("r1", queue_depth=1)  # loses the first selection
    router = _fleet(r0, r1)
    status, resp = router.dispatch({"tokens": prompt, "max_tokens": m})
    assert status == 200
    assert resp["tokens"] == _complete(prompt, m)
    assert resp["router"]["replica"] == "r1"
    assert resp["router"]["resubmits"] == 1
    assert resp["router"]["failovers"] == 1
    assert router.replica_status()["r0"]["status"] == "draining"


def test_dispatch_unreachable_marks_dead_then_backoff_revives():
    now = [100.0]
    r0 = FakeReplica("r0")
    r1 = FakeReplica("r1", queue_depth=1)
    router = Router(RouterConfig(probe_base=0.0),
                    clock=lambda: now[0], sleep=lambda s: None)
    router.add_replica("r0", r0)
    router.add_replica("r1", r1)
    router.poll()
    r0.unreachable = True
    status, resp = router.dispatch({"tokens": [1, 2, 3],
                                    "max_tokens": 4})
    assert status == 200
    assert resp["router"]["replica"] == "r1"
    assert resp["router"]["failovers"] == 1
    assert router.replica_status()["r0"]["status"] == "dead"
    # Dead replicas are not re-probed before their backoff expires...
    r0.unreachable = False
    router.poll()
    assert router.replica_status()["r0"]["status"] == "dead"
    # ...and rejoin the fleet once it does.
    now[0] += 60.0
    router.poll()
    assert router.replica_status()["r0"]["status"] == "ready"


def test_dispatch_no_ready_replica_is_503():
    r0 = FakeReplica("r0", ready=False)
    router = _fleet(r0)
    status, resp = router.dispatch({"tokens": [1], "max_tokens": 2})
    assert status == 503
    assert "no ready replica" in resp["error"]


def test_dispatch_rejects_tokenless_payload():
    router = _fleet(FakeReplica("r0"))
    status, _ = router.dispatch({"max_tokens": 4})
    assert status == 400


def test_dispatch_optimistically_publishes_affinity():
    """After a 200 the router credits the replica with the prompt's
    full pages BEFORE the next health poll — the back-to-back warm
    path."""
    prompt = list(range(2 * PAGE + 1))
    r0 = FakeReplica("r0")
    r1 = FakeReplica("r1")
    router = _fleet(r0, r1)
    first, _ = router.select(prompt)
    router.dispatch({"tokens": prompt, "max_tokens": 4})
    name, pages = router.select(prompt)
    assert name == first
    assert pages == 2


def test_drain_replica_exports_and_stops_traffic():
    r0 = FakeReplica("r0")
    r0.chains = [[1, 2, 3, 4], [1, 2, 3, 4, 5, 6, 7, 8]]
    router = _fleet(r0)
    export = router.drain_replica("r0")
    assert export["prefixes"] == r0.chains
    router.poll()
    assert router.ready_count() == 0


# -- fleet autoscaler ------------------------------------------------------

def _autoscaler(router, cfg, launched, price=None, headroom=None):
    def launch(name):
        rep = FakeReplica(name)
        launched[name] = rep
        return rep

    return FleetAutoscaler(router, launch,
                           retire=lambda name: launched.pop(name, None),
                           cfg=cfg, price=price, headroom=headroom)


def test_autoscaler_scale_up_seeds_from_donor():
    r0 = FakeReplica("r0", queue_depth=10)
    r0.chains = [[1, 2, 3, 4, 5, 6, 7, 8]]
    r0.prefix_index = affinity.published_page_hashes(
        FP.encode(), r0.chains[0], PAGE, PPS)
    router = _fleet(r0)
    launched = {}
    scaler = _autoscaler(router, AutoscaleConfig(
        min_replicas=1, max_replicas=3, up_load=4.0, down_load=0.5,
        sustain=2, cooldown=2), launched)
    assert scaler.observe() is None        # sustain tick 1
    assert scaler.observe() == "up:auto1"  # tick 2 fires
    assert "auto1" in router.replica_names()
    # The newcomer was ghost-seeded from the busiest survivor's index.
    assert launched["auto1"].resumed == [
        {"requests": [], "prefixes": r0.chains}]
    # Cooldown: the next tick is quiet even though r0 is still loaded.
    assert scaler.observe() is None


def test_autoscaler_planner_veto():
    r0 = FakeReplica("r0", queue_depth=10)
    router = _fleet(r0)
    scaler = _autoscaler(router, AutoscaleConfig(
        min_replicas=1, max_replicas=3, up_load=4.0, sustain=1,
        cooldown=0), {},
        price=lambda: 2 * 1024, headroom=lambda: 1024)
    assert scaler.observe() == "veto:up"
    assert router.replica_names() == ["r0"]


def test_autoscaler_scale_down_drains_victim_and_donates():
    r0 = FakeReplica("r0")
    auto1 = FakeReplica("auto1")
    auto1.chains = [[5, 6, 7, 8, 9, 10, 11, 12]]
    router = _fleet(r0, auto1)
    launched = {"auto1": auto1}
    scaler = _autoscaler(router, AutoscaleConfig(
        min_replicas=1, max_replicas=3, up_load=50.0, down_load=1.0,
        sustain=2, cooldown=1), launched)
    with scaler._lock:
        scaler._launched.append("auto1")  # as if this scaler booted it
    assert scaler.observe() is None
    assert scaler.observe() == "down:auto1"  # prefers its own boots
    assert router.replica_names() == ["r0"]
    assert "auto1" not in launched           # retire hook ran
    # The victim's warm chains were donated to the survivor.
    assert r0.resumed == [{"requests": [], "prefixes": auto1.chains}]


def test_autoscaler_never_below_min_or_with_dead_replica():
    r0 = FakeReplica("r0")
    router = _fleet(r0)
    scaler = _autoscaler(router, AutoscaleConfig(
        min_replicas=1, max_replicas=3, down_load=1.0, sustain=1,
        cooldown=0), {})
    assert scaler.observe() is None  # total == min_replicas
    r1 = FakeReplica("r1")
    router.add_replica("r1", r1)
    router.poll()
    r1.unreachable = True
    router.poll()
    # A dead replica mid-failover is not overcapacity: no scale-down.
    assert scaler.observe() is None


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-q"]))
