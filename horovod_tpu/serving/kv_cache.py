"""Paged KV cache: fixed-size pages, free-list recycling, TP sharding.

Storage is one device array for each part of the entry a model caches
(``pages``; the model says what that is, serving/models.py).  A
multi-head model caches two, ``k_pages``/``v_pages: [n_layers, n_pages,
page_size, n_heads * head_dim]`` (a token's heads side by side in the
minor dimension: with a 64-wide ``head_dim`` of its own there, half a
lane row, the TPU lays the store out PAGES-minor and every gather first
copies all of it); a latent-attention model ONE, ``[n_layers, n_pages,
page_size, kv_rank + rope_dim]`` (``entry_widths``).  Beside them lives
a HOST page table (``[max_slots, pages_per_slot]``
int32, numpy) mapping each decode slot's logical positions onto
physical pages.  Pages are allocated on demand as a sequence grows and
recycled through a free list the moment the scheduler evicts it, so
slot reuse never copies or zeroes KV data: the next sequence simply
maps fresh pages and the old values become unreachable (masked by
:func:`..models.transformer.cache_attention` long before they are
overwritten).

**Per-slot stores** (``slot_stores``): what a model keeps of a sequence
that is no function of position lives beside the pages in the SAME
manager, one array ``[layers, max_slots, *shape]`` a store, indexed by
the decode slot and not by the page table: a window layer's ring of the
last ``window`` keys or values (``kind: "window"``: its bytes a slot are
bounded by the window, whatever ``capacity`` is), recurrent state
(``kind: "state"``) and room the decode program works in and keeps
across iterations so that it is never re-allocated nor zeroed (``kind:
"scratch"``; a dimension given as ``"capacity"`` is the slot's).  They
are fixed at construction, so admission headroom counts pages alone; a
slot's rows are REPLACED whole by the prefill that fills it (recurrent
state has no mask that could hide an evicted sequence's), so
``free_slot`` has nothing of theirs to recycle but the slot itself.  The executables take and return ``arrays`` (pages,
then per-slot stores), all donated.

**Layer groups** (``groups``): a model whose layers do not all keep the
same positions declares more than one paged group, each with its layer
count, its own page arrays ``[group layers, group pages, page_size,
width]``, its own page table and its own free list.  The first group is
the ``full`` one: every position of a sequence, ``pages_per_slot``
entries a slot, everything above.  A group that declares a ``window``
keeps only what a window layer can still attend: its table has ``R =
ceil(window / page_size) + 1`` entries a slot, used as a ring of PAGES
(logical page ``j`` lies in entry ``j mod R``).  A page is taken from the
group's free list when the sequence first reaches it, never more than
``R`` a slot; from logical page ``R`` on the entry's page is written over
in place (what it held has left the window by then), and all go back at
``free_slot``.  The executables see ONE table, the groups' side by side
(``table_width`` columns), and every group's arrays in ``arrays``.

**Page pools** (``pool_pages``): left out, every group holds all its
slots at their largest (``max_slots x entries``), and a free slot implies
room.  Given (``memory/planner.size_page_pools`` splits a byte budget),
the pools are smaller than that and admission RESERVES: ``begin_slot``
is told how far the sequence may grow, ``headroom`` is each pool's free
pages less what live slots have reserved and not yet mapped, and
``admission_need`` prices a request in every pool, so no sequence that
was admitted runs out of pages as it decodes.

Page 0 is the reserved *trash* page: unmapped table entries point at
it, so the executables' scatters of padded/inactive positions land
somewhere harmless instead of needing per-position predication.
Nothing ever reads trash through an unmasked attention row (entry
``j`` is only unmasked for ``j <= q_pos < length``, and every position
``< length`` is mapped by construction); written values are finite, so
masked rows contribute exact zeros regardless of trash content — the
bitwise contract does not depend on it.

Tensor parallelism: the minor (heads) dimension is sharded over the
mesh's ``model`` axis with a ``NamedSharding``, whole heads a shard —
the SAME partition ``parallel/tensor.py`` gives the training attention
(heads column-parallel), so a model served on its training mesh reuses the
training layout and GSPMD partitions prefill/decode along heads with
no code change here.

**Shared-prefix page cache** (hvd-spec, docs/inference.md): completed
prompt-prefix pages are hashed — a page-aligned CHAIN hash over the
token ids, keyed by the engine's model/config fingerprint, so the hash
of page ``j`` commits to every token before it — into a refcounted
read-only index.  A new request whose prompt extends a cached prefix
maps those pages into its page table copy-free (``begin_slot``'s
``prefix_pages``) and prefills only the suffix; repeated system
prompts, few-shot headers and RAG contexts become page-table lookups.
Shared pages are never written (decode/verify scatters target
positions ``>= length > shared coverage`` by construction) and never
freed while referenced: ``free_slot`` decrements refcounts, and a page
whose count reaches zero parks in an LRU of *reclaimable* cached pages
— still index-hittable, recycled only when the free list runs dry.
"""

from __future__ import annotations

import weakref
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from .. import telemetry as _telemetry
from ..analysis import lockorder as _lockorder
from ..analysis import races as _races
from ..core.topology import MODEL_AXIS
from ..memory import ledger as _mem
from ..memory import planner as _planner
from ..routing.affinity import chain_hashes as _chain_hash_scheme

# hvd-mem satellite: free-page headroom next to serving.batch_occupancy
# — the ROADMAP-item-2 router tier dispatches on how much KV room a
# replica has LEFT, not just how deep its queue is.  Push-fed (set
# under the cache lock at every page-management transition), so it is
# current in /healthz, the FRAME_METRICS fleet pull and every flight
# dump's tail.
_M_KV_FREE = _telemetry.gauge(
    "serving.kv_free_pages",
    "KV pages available for allocation: free list + reclaimable "
    "prefix-cache pages (admission headroom)")
_M_KV_TOTAL = _telemetry.gauge(
    "serving.kv_total_pages",
    "allocatable KV pages (capacity; excludes the trash page)")
_M_KV_RECLAIM = _telemetry.gauge(
    "serving.kv_reclaimable_pages",
    "unreferenced prefix-cache pages (reclaimed LRU-first when the "
    "free list runs dry; counted inside kv_free_pages)")
_M_PREFIX_CACHED = _telemetry.gauge(
    "serving.prefix_cached_pages",
    "pages currently held by the shared-prefix index (referenced + "
    "reclaimable)")
_M_PREFIX_HITS = _telemetry.counter(
    "serving.prefix_hits",
    "admissions that mapped at least one cached prefix page copy-free")
_M_PREFIX_PAGES = _telemetry.counter(
    "serving.prefix_pages_shared",
    "cached prefix pages mapped into admitted slots (copy-free)")
_M_PREFIX_BYTES = _telemetry.counter(
    "serving.prefix_bytes_saved",
    "KV bytes NOT recomputed thanks to prefix-cache hits (global "
    "logical bytes of the shared pages)")
_M_STATE_RESETS = _telemetry.counter(
    "serving.state_slot_resets",
    "slots whose per-slot stores (recurrent state, window rings) an "
    "admission's prefill replaced whole")
_M_RING_REUSED = _telemetry.counter(
    "serving.window_pages_reused",
    "window-group ring entries written over in place: logical pages a "
    "sequence reached past its ring's length, which took no new page")
_M_PREFIX_HITS_DRAFT = _telemetry.counter(
    "serving.prefix_hits_draft",
    "admissions whose speculative DRAFT prefill mapped cached prefix "
    "pages copy-free (the target's hits stay in serving.prefix_hits)")


class _WindowGroup:
    """One window group's host state (module docstring, "Layer groups"):
    its ring table, the highest logical page each slot has reached, its
    free list and its page arrays.  All guarded by the cache's lock."""

    def __init__(self, name: str, n_layers: int, window: int,
                 page_size: int, max_slots: int,
                 pool_pages: Optional[int]) -> None:
        if window < 1:
            raise ValueError(f"group {name!r}: window must be >= 1")
        self.name = name
        self.n_layers = n_layers
        self.window = window
        self.entries = _planner.ring_entries(window, page_size)
        self.total_pages = (max_slots * self.entries if pool_pages is None
                            else int(pool_pages))
        self.free: List[int] = list(range(1, self.total_pages + 1))
        self.table = np.zeros((max_slots, self.entries), np.int32)
        self.top = np.full((max_slots,), -1, np.int64)
        self.pages: Tuple = ()


def _group_gauges(name: str, total_pages: int) -> Tuple:
    """A layer group's gauges ``(used, peak)``: pages its live slots map
    now, and the most they mapped at once; the pool's size is set here."""
    _telemetry.gauge(
        f"serving.kv_group_pages_total.{name}",
        f"allocatable pages of the {name!r} layer group's pool"
    ).set(total_pages)
    return (_telemetry.gauge(
                f"serving.kv_group_pages_used.{name}",
                f"pages of the {name!r} layer group mapped by live slots"),
            _telemetry.gauge(
                f"serving.kv_group_pages_peak.{name}",
                f"the most pages of the {name!r} layer group live slots "
                f"have mapped at once since the store was built"))


@_races.race_checked
class PagedKVCache:
    """The paged store for one :class:`~horovod_tpu.serving.engine.
    InferenceEngine`.  The host-side bookkeeping (page table, lengths,
    free list) is guarded by an internal lock: the serve loop mutates
    it every iteration, and the engine's drain family
    (``_free_all_slots``) may run concurrently from the elastic
    thread — ``free_slot`` is idempotent and ``advance`` is a no-op on
    a freed slot, so an eviction racing the loop can never double-free
    a page or resurrect a slot.  The DEVICE page arrays are still
    single-writer (only the serve loop dispatches executables)."""

    def __init__(self, n_layers: int, n_heads: int, head_dim: int,
                 max_slots: int, pages_per_slot: int, page_size: int,
                 dtype=jnp.float32, mesh=None,
                 model_axis: str = MODEL_AXIS,
                 prefix_cache: bool = False, prefix_pages: int = 0,
                 fingerprint: str = "",
                 ledger_category: str = "serving.kv_pages",
                 entry_widths: Optional[Sequence[int]] = None,
                 slot_stores: Sequence[dict] = (),
                 groups: Sequence[dict] = (),
                 pool_pages: Optional[Sequence[int]] = None) -> None:
        if pages_per_slot < 1 or page_size < 1:
            raise ValueError("pages_per_slot and page_size must be >= 1")
        # Layer groups (module docstring): ``{"name", "n_layers"[,
        # "window"]}`` each, the full group first; none: one full group
        # of ``n_layers``.  ``pool_pages``: allocatable pages a group.
        groups = tuple(dict(g) for g in groups) or (
            {"name": "full", "n_layers": n_layers},)
        if groups[0].get("window") or not all(
                g.get("window") for g in groups[1:]):
            raise ValueError("the first layer group keeps every position "
                             "and every other one declares a window")
        if pool_pages is not None and len(pool_pages) != len(groups):
            raise ValueError(f"{len(pool_pages)} page pools for "
                             f"{len(groups)} layer groups")
        if prefix_cache and (len(groups) > 1 or pool_pages is not None):
            raise ValueError("the shared-prefix index is not written for "
                             "window groups nor for reserved pools")
        n_layers = groups[0]["n_layers"]
        if prefix_pages < 0:
            raise ValueError(f"prefix_pages must be >= 0, got "
                             f"{prefix_pages}")
        self.n_layers = n_layers
        self.n_heads = n_heads
        self.head_dim = head_dim
        self.max_slots = max_slots
        self.pages_per_slot = pages_per_slot
        self.page_size = page_size
        self.capacity = pages_per_slot * page_size  # per sequence
        # +1: trash page; +prefix_pages: dedicated headroom for the
        # shared-prefix index (the --prefix-pages planner what-if) so a
        # busy fleet is not forced to thrash cached prefixes against
        # live slots.
        self.prefix_enabled = bool(prefix_cache)
        self.prefix_pages = int(prefix_pages) if prefix_cache else 0
        self._pooled = pool_pages is not None
        self.n_pages = (1 + (pool_pages[0] if self._pooled
                             else max_slots * pages_per_slot)
                        + self.prefix_pages)
        self.dtype = dtype
        self.mesh = mesh
        self.model_axis = model_axis
        self._fingerprint = fingerprint.encode()
        self._ledger_category = ledger_category

        # The minor width of each store: keys and values of all heads
        # unless the model caches something else (one latent entry).
        self.entry_widths = (tuple(int(w) for w in entry_widths)
                             if entry_widths is not None
                             else (n_heads * head_dim,) * 2)
        sh = self.page_sharding()
        pages = []
        for width in self.entry_widths:
            store = jnp.zeros((n_layers, self.n_pages, page_size, width),
                              dtype)
            pages.append(store if sh is None else jax.device_put(store, sh))
        self.pages: Tuple = tuple(pages)
        self.group_names = tuple(g["name"] for g in groups)
        self._extra: Tuple[_WindowGroup, ...] = tuple(
            _WindowGroup(g["name"], g["n_layers"], g["window"], page_size,
                         max_slots,
                         pool_pages[i] if self._pooled else None)
            for i, g in enumerate(groups[1:], start=1))
        if self._extra and sh is not None:
            raise ValueError("window groups are not written for a sharded "
                             "model axis")
        for g in self._extra:
            g.pages = tuple(
                jnp.zeros((g.n_layers, g.total_pages + 1, page_size, width),
                          dtype) for width in self.entry_widths)
        # Pages a live slot has reserved (a cache with pool_pages) and
        # mapped, a row a group.
        # guarded_by: _lock
        self._reserved = np.zeros((len(groups), max_slots), np.int64)
        # guarded_by: _lock
        self._mapped = np.zeros((len(groups), max_slots), np.int64)
        self.table_width = pages_per_slot + sum(g.entries
                                                for g in self._extra)
        # Gauges a group, of a store with more than one or with pools
        # smaller than its slots (the full group's are serving.kv_pages_*
        # as ever).
        self._group_gauges = [
            _group_gauges(name, total) for name, total in zip(
                self.group_names,
                [self.n_pages - 1] + [g.total_pages for g in self._extra])
        ] if self._extra or self._pooled else []
        # guarded_by: _lock
        self._peak = np.zeros((len(groups),), np.int64)
        # Per-slot stores (module docstring): ``{"name", "kind", "shape",
        # "dtype"}`` each, ``shape`` led by the store's own layer count.
        self.slot_stores = tuple(dict(s) for s in slot_stores)
        if self.slot_stores and sh is not None:
            raise ValueError("per-slot stores are not written for a "
                             "sharded model axis")
        self.slot_state: Tuple = tuple(
            jnp.zeros((s["shape"][0], max_slots,
                       *(self._slot_dim(d) for d in s["shape"][1:])),
                      s["dtype"])
            for s in self.slot_stores)

        self._lock = _lockorder.make_lock("serving.PagedKVCache._lock")
        self._free: List[int] = list(range(1, self.n_pages))
        # guarded_by: _lock
        self._table = np.zeros((max_slots, pages_per_slot), np.int32)
        self._lengths = np.full((max_slots,), -1, np.int32)
        # -- shared-prefix index (all guarded_by: _lock) ------------------
        # chain hash -> physical page holding that page-aligned prefix's
        # KV; _page_hash is the reverse map (page -> hash), _page_tokens
        # keeps the token ids per entry for the elastic export,
        # _refcount counts slots currently mapping a shared page, and
        # _lru holds unreferenced cached pages in reclaim order.
        self._index: Dict[bytes, int] = {}
        self._page_hash: Dict[int, bytes] = {}
        self._page_tokens: Dict[bytes, List[int]] = {}
        self._refcount: Dict[int, int] = {}
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        # Live index-size target (hvd-tune's prefix_pages retune knob):
        # None = unbounded.  The device-side reserve is fixed at
        # construction; this caps how many pages the INDEX may hold —
        # shrink trims the reclaimable LRU, grow just lifts the cap
        # (pages come from the shared pool as prompts publish).
        self._prefix_target: Optional[int] = None  # guarded_by: _lock
        if ledger_category == "serving.kv_pages":
            _M_KV_TOTAL.set(self.total_pages)
        self._set_page_gauges_locked()
        # hvd-mem: the page arrays are THE serving framework buffer —
        # account the bytes RESIDENT on this process (addressable
        # shards: a tp-sharded store holds global/tp per rank) for the
        # store's lifetime (keyed, released by gc: replace_pages swaps
        # same-shape donated outputs, so the figure is constant while
        # the engine lives).  Dedicated prefix pages are partitioned
        # into their own ledger category (the SAME per-page byte model
        # memory/planner.prefix_pages_bytes predicts with), so
        # plan-vs-ledger stays exact with a prefix reserve resident.
        self._ledger_key = id(self)
        resident = sum(_mem.resident_nbytes(x) for x in self.pages)
        # n_pages divides both factors of the array shape, so the
        # partition is exact integer arithmetic.
        self._page_resident_bytes = resident // self.n_pages
        prefix_resident = self._page_resident_bytes * self.prefix_pages
        resident += sum(_mem.resident_nbytes(x)
                        for g in self._extra for x in g.pages)
        if _mem.enabled():
            _mem.ledger.alloc(self._ledger_category,
                              resident - prefix_resident,
                              key=self._ledger_key)
            if prefix_resident:
                _mem.ledger.alloc("serving.prefix_pages",
                                  prefix_resident, key=self._ledger_key)
        weakref.finalize(self, _mem.ledger.free, self._ledger_category,
                         key=self._ledger_key)
        if self.slot_state:
            if _mem.enabled():
                _mem.ledger.alloc("serving.slot_state",
                                  sum(self.slot_store_bytes().values()),
                                  key=self._ledger_key)
            weakref.finalize(self, _mem.ledger.free, "serving.slot_state",
                             key=self._ledger_key)
        if prefix_resident:
            weakref.finalize(self, _mem.ledger.free,
                             "serving.prefix_pages",
                             key=self._ledger_key)

    def _slot_dim(self, d) -> int:
        """A per-slot store's dimension: a number, or ``"capacity"`` (the
        slot's positions)."""
        if isinstance(d, str) and d != "capacity":
            raise ValueError(f"a per-slot store's dimension is a number "
                             f"or \"capacity\", not {d!r}")
        return self.capacity if d == "capacity" else d

    # -- sharding ----------------------------------------------------------
    def page_sharding(self) -> Optional[NamedSharding]:
        """NamedSharding for the page arrays (heads over the model
        axis), or None when the mesh has no model axis to shard over —
        the training partition, reused for serving."""
        if self.mesh is None or self.model_axis not in getattr(
                self.mesh, "axis_names", ()):
            return None
        tp = self.mesh.shape[self.model_axis]
        if tp <= 1:
            return None
        if self.n_heads % tp != 0:
            raise ValueError(
                f"tensor-parallel degree {tp} must divide n_heads "
                f"({self.n_heads}) to shard the KV head axis")
        return NamedSharding(self.mesh,
                             P(None, None, None, self.model_axis))

    # -- gauges ------------------------------------------------------------
    def _set_page_gauges_locked(self) -> None:
        # Only the primary (target) store owns the process-global
        # serving.* page gauges; a draft store (its own ledger
        # category) must not clobber them.
        if self._ledger_category != "serving.kv_pages":
            return
        if self._group_gauges:
            used = self._mapped.sum(axis=1)
            np.maximum(self._peak, used, out=self._peak)
            for (now, peak), u, p in zip(self._group_gauges, used,
                                         self._peak):
                now.set(int(u))
                peak.set(int(p))
        _M_KV_FREE.set(len(self._free) + len(self._lru))
        _M_KV_RECLAIM.set(len(self._lru))
        _M_PREFIX_CACHED.set(len(self._page_hash))

    # -- page management ---------------------------------------------------
    def begin_slot(self, slot: int, n_tokens: int,
                   prefix_pages: Sequence[int] = (),
                   reserve_tokens: Optional[int] = None) -> None:
        """Map pages for a freshly admitted sequence's first
        ``n_tokens`` positions (the prompt) and set its length.
        ``reserve_tokens``: how many positions the sequence may come to
        hold, which a cache with ``pool_pages`` reserves in every pool.
        ``prefix_pages`` (from :meth:`lookup_prefix`) are mapped
        COPY-FREE as the leading read-only pages: each gets a
        reference (it leaves the reclaimable LRU while mapped) and
        only the remainder allocates fresh pages — the suffix is all
        the caller prefills."""
        with self._lock:
            if self._lengths[slot] >= 0:
                raise ValueError(f"slot {slot} already active")
            self._table[slot] = 0
            for j, page in enumerate(prefix_pages):
                if self._page_hash.get(int(page)) is None:
                    raise ValueError(
                        f"page {page} is not a cached prefix page")
                self._table[slot, j] = int(page)
                self._ref_page_locked(int(page))
            self._lengths[slot] = 0
            self._mapped[:, slot] = 0
            self._mapped[0, slot] = len(prefix_pages)
            last = (n_tokens - 1) // self.page_size
            for g in self._extra:
                # A prompt longer than the window leaves its last
                # ``entries`` pages' worth: the pages before them are
                # never mapped.
                g.table[slot] = 0
                g.top[slot] = max(last - g.entries, -1)
            if self._pooled:
                self._reserved[:, slot] = self._pages_for(
                    max(n_tokens, reserve_tokens or 0))
            self._ensure_locked(slot, n_tokens - 1)
            self._lengths[slot] = n_tokens
            if self.slot_state:
                # The prefill this admission runs replaces the slot's
                # rows of every per-slot store.
                _M_STATE_RESETS.inc()
            if prefix_pages:
                # Split by store: the target's hits stay on the
                # historical serving.prefix_hits family; a DRAFT
                # store's hits (its own ledger category) count on the
                # draft counter so the hvd-spec satellite's win is
                # observable separately (hvd-route retunes on the sum).
                if self._ledger_category == "serving.kv_pages":
                    _M_PREFIX_HITS.inc()
                    _M_PREFIX_PAGES.inc(len(prefix_pages))
                    _M_PREFIX_BYTES.inc(
                        len(prefix_pages) * self.page_global_bytes)
                else:
                    _M_PREFIX_HITS_DRAFT.inc()
            self._set_page_gauges_locked()

    def ensure(self, slot: int, pos: int) -> int:
        """Map pages so position ``pos`` of ``slot`` is writable;
        returns how many it mapped.
        A no-op on a freed slot: the serve loop reads ``length`` and
        calls this as two separate lock holds, so a drain landing
        between them must not map pages into the freed slot — its own
        idempotence check would then never recycle them (a permanent
        page leak), and ``begin_slot`` zeroes the row on reuse."""
        with self._lock:
            if self._lengths[slot] < 0:
                return 0
            return self._ensure_locked(slot, pos)

    def _alloc_page_locked(self) -> int:
        """One allocatable page: free list first, then the LRU of
        unreferenced cached prefix pages (refcount-aware eviction — a
        REFERENCED shared page is never a candidate by construction:
        it is absent from both pools)."""
        if self._free:
            return self._free.pop(0)
        if self._lru:
            page, _ = self._lru.popitem(last=False)
            self._drop_index_locked(page)
            return page
        raise RuntimeError(
            "paged KV cache out of pages (free list and prefix-cache "
            "LRU both empty) — sizing guarantees this cannot happen "
            "while every slot stays within pages_per_slot")

    def _drop_index_locked(self, page: int) -> None:
        key = self._page_hash.pop(page, None)
        if key is not None:
            self._index.pop(key, None)
            self._page_tokens.pop(key, None)
        self._refcount.pop(page, None)

    def _ref_page_locked(self, page: int) -> None:
        self._refcount[page] = self._refcount.get(page, 0) + 1
        self._lru.pop(page, None)

    def _unref_page_locked(self, page: int) -> None:
        rc = self._refcount.get(page, 0) - 1
        if rc <= 0:
            self._refcount.pop(page, None)
            self._lru[page] = None
            self._lru.move_to_end(page)
        else:
            self._refcount[page] = rc

    def _ensure_locked(self, slot: int, pos: int) -> int:
        if pos >= self.capacity:
            raise ValueError(
                f"position {pos} exceeds per-slot capacity "
                f"{self.capacity}")
        mapped = 0
        top = pos // self.page_size
        for p in range(top + 1):
            if self._table[slot, p] == 0:
                self._table[slot, p] = self._alloc_page_locked()
                mapped += 1
        self._mapped[0, slot] += mapped
        for i, g in enumerate(self._extra, start=1):
            # Only the logical pages the slot has not reached yet: a
            # fresh page while the ring has an empty entry, else the
            # entry's own page, written over in place.
            for j in range(int(g.top[slot]) + 1, top + 1):
                e = j % g.entries
                if g.table[slot, e] != 0:
                    _M_RING_REUSED.inc()
                    continue
                if not g.free:
                    raise RuntimeError(
                        f"layer group {g.name!r} out of pages: its pool "
                        f"is smaller than the live slots' windows "
                        f"(admission reserves against this)")
                g.table[slot, e] = g.free.pop(0)
                self._mapped[i, slot] += 1
                mapped += 1
            g.top[slot] = max(int(g.top[slot]), top)
        self._set_page_gauges_locked()
        return mapped

    def advance(self, slot: int) -> int:
        """One decoded token was written at the current length; map the
        page first via :meth:`ensure`.  Returns the new length, or -1
        without advancing when the slot was freed by a concurrent
        eviction (a drain racing the loop must not resurrect it)."""
        with self._lock:
            if self._lengths[slot] < 0:
                return -1
            self._lengths[slot] += 1
            return int(self._lengths[slot])

    def free_slot(self, slot: int) -> None:
        """Evict: recycle the slot's pages.  Refcount-aware: a page the
        prefix index holds is UNREFERENCED (parked in the reclaimable
        LRU when its count reaches zero — never put on the free list
        while cached), every other page goes back on the free list.
        Idempotent — a second free of the same slot (the serve loop
        and a concurrent drain both evicting) is a no-op, never a
        double-insert into the free list."""
        with self._lock:
            if self._lengths[slot] < 0:
                return
            for p in range(self.pages_per_slot):
                page = int(self._table[slot, p])
                if page != 0:
                    if page in self._page_hash:
                        self._unref_page_locked(page)
                    else:
                        self._free.append(page)
            self._table[slot] = 0
            for g in self._extra:
                g.free.extend(int(p) for p in g.table[slot] if p != 0)
                g.table[slot] = 0
                g.top[slot] = -1
            self._mapped[:, slot] = 0
            self._reserved[:, slot] = 0
            self._lengths[slot] = -1
            self._set_page_gauges_locked()

    # -- admission headroom ------------------------------------------------
    def _pages_for(self, n_tokens: int) -> np.ndarray:
        """Pages a sequence of ``n_tokens`` positions maps in each group:
        all of them in the full group, at most the ring in a window one."""
        pages = -(-n_tokens // self.page_size)
        return np.asarray([pages] + [min(pages, g.entries)
                                     for g in self._extra], np.int64)

    def headroom(self) -> np.ndarray:
        """Pages each group's pool can still promise an admission, the
        full group first: its free pages (:meth:`free_pages`) and, of a
        cache with ``pool_pages``, less what the live slots have reserved
        and not mapped yet."""
        with self._lock:
            free = np.asarray([len(self._free) + len(self._lru)]
                              + [len(g.free) for g in self._extra],
                              np.int64)
            if self._pooled:
                free -= np.clip(self._reserved - self._mapped, 0,
                                None).sum(axis=1)
            return free

    def admission_need(self, tokens: Sequence[int],
                       max_new_tokens: int = 0) -> np.ndarray:
        """What admitting this prompt takes of :meth:`headroom`, a group
        an entry: the prompt's pages (:meth:`admission_cost` in the full
        group); of a cache with ``pool_pages`` the pages the sequence may
        come to hold with ``max_new_tokens`` more, which ``begin_slot``
        reserves."""
        if self._pooled:
            return self._pages_for(min(len(tokens) + max_new_tokens,
                                       self.capacity))
        need = self._pages_for(len(tokens))
        need[0] = self.admission_cost(tokens)
        return need

    def group_pages(self) -> Dict[str, Tuple[int, int]]:
        """``name -> (pages mapped by live slots, allocatable pages)``
        a layer group."""
        with self._lock:
            used = self._mapped.sum(axis=1)
            totals = [self.total_pages] + [g.total_pages
                                           for g in self._extra]
            return {name: (int(u), int(t)) for name, u, t
                    in zip(self.group_names, used, totals)}

    # -- shared-prefix index -----------------------------------------------
    @property
    def page_global_bytes(self) -> int:
        """GLOBAL logical KV bytes of one page (every store, all
        layers) — the byte model memory/planner.prefix_pages_bytes
        shares."""
        return (self.n_layers * self.page_size * sum(self.entry_widths)
                * jnp.dtype(self.dtype).itemsize)

    def _chain_hashes(self, tokens: Sequence[int],
                      n_pages: int) -> List[bytes]:
        """Chain hash per page boundary: ``h_j`` commits to the model
        fingerprint AND every token of pages ``0..j`` — a hit on page
        ``j`` implies the whole prefix matches, so the index needs no
        token comparison on lookup.  Delegates to the jax-free
        ``routing.affinity`` scheme: the router tier derives these SAME
        keys from /healthz exports for prefix-affinity dispatch, and a
        silent divergence would zero the fleet's affinity hit rate
        (tests/test_routing.py gates byte-identity)."""
        return _chain_hash_scheme(self._fingerprint, tokens,
                                  self.page_size, n_pages)

    def lookup_prefix(self, tokens: Sequence[int]) -> List[int]:
        """Physical pages of the longest cached page-aligned STRICT
        prefix of ``tokens`` (at least one suffix token always remains
        to prefill — the admission needs its logits to sample from).
        Pure: no refcounts move until :meth:`begin_slot` maps the
        pages, so the admission-headroom gate can call this freely."""
        if not self.prefix_enabled or not tokens:
            return []
        max_pages = min((len(tokens) - 1) // self.page_size,
                        self.pages_per_slot)
        if max_pages <= 0:
            return []
        hashes = self._chain_hashes(tokens, max_pages)
        pages: List[int] = []
        with self._lock:
            for key in hashes:
                page = self._index.get(key)
                if page is None:
                    break
                pages.append(page)
        return pages

    def admission_cost(self, tokens: Sequence[int]) -> int:
        """How much of the :meth:`free_pages` budget admitting this
        prompt consumes, EXACTLY: fresh pages for the unshared tail,
        plus one unit per shared prefix page currently parked in the
        reclaimable LRU (mapping it moves it to referenced — out of
        the pool — while a page other slots already reference costs
        nothing).  The scheduler's page-budget gate prices admissions
        with this; under the default sizing it is a safety net (a
        free slot always implies enough headroom), but the arithmetic
        stays honest for overcommitted configs."""
        if not tokens:
            return 0
        total = -(-len(tokens) // self.page_size)
        if not self.prefix_enabled:
            return total
        max_pages = min((len(tokens) - 1) // self.page_size,
                        self.pages_per_slot)
        hashes = self._chain_hashes(tokens, max_pages) if max_pages \
            else []
        with self._lock:
            shared = 0
            lru_hits = 0
            for key in hashes:
                page = self._index.get(key)
                if page is None:
                    break
                shared += 1
                if page in self._lru:
                    lru_hits += 1
        return total - shared + lru_hits

    def publish_prefix(self, slot: int, tokens: Sequence[int]) -> int:
        """Insert ``slot``'s fully-prefilled prompt pages into the
        index (pages entirely covered by ``tokens`` — pad garbage past
        the prompt never lands in a published page).  Pages already
        indexed (including the slot's own looked-up prefix) are
        skipped; newly published pages become shared with the slot
        holding the first reference.  Returns how many pages were newly
        published."""
        if not self.prefix_enabled:
            return 0
        ps = self.page_size
        n_full = min(len(tokens) // ps, self.pages_per_slot)
        if n_full <= 0:
            return 0
        hashes = self._chain_hashes(tokens, n_full)
        published = 0
        with self._lock:
            if self._lengths[slot] < 0:
                return 0
            for j in range(n_full):
                page = int(self._table[slot, j])
                if page == 0:
                    break
                key = hashes[j]
                if key in self._index or page in self._page_hash:
                    continue
                if (self._prefix_target is not None
                        and len(self._page_hash)
                        >= self._prefix_target):
                    break  # retuned cap reached — stop publishing
                self._index[key] = page
                self._page_hash[page] = key
                self._page_tokens[key] = [int(t)
                                          for t in tokens[:(j + 1) * ps]]
                self._refcount[page] = self._refcount.get(page, 0) + 1
                published += 1
            if published:
                self._set_page_gauges_locked()
        return published

    def alloc_ghost(self, n_pages: int) -> np.ndarray:
        """A ``[1, pages_per_slot]`` table row of ``n_pages`` freshly
        allocated pages bound to NO slot — the elastic seed path
        (:meth:`publish_ghost`) prefills cached prefixes through it on
        a relaunched engine without burning a decode slot."""
        if not 0 < n_pages <= self.pages_per_slot:
            raise ValueError(
                f"ghost prefix needs 1..{self.pages_per_slot} pages, "
                f"got {n_pages}")
        row = np.zeros((1, self.pages_per_slot), np.int32)
        with self._lock:
            for j in range(n_pages):
                row[0, j] = self._alloc_page_locked()
        return row

    def free_ghost(self, row: np.ndarray) -> None:
        """Return a ghost row's pages to the free list WITHOUT
        indexing them — the seed path's failure cleanup (a prefill
        that raised must not strand allocated pages outside every
        pool, or the sizing invariant silently erodes)."""
        with self._lock:
            for page in row[0]:
                if int(page) != 0:
                    self._free.append(int(page))
            self._set_page_gauges_locked()

    def publish_ghost(self, row: np.ndarray,
                      tokens: Sequence[int]) -> int:
        """Index the ghost row's prefilled pages with refcount zero
        (straight into the reclaimable LRU — hittable, evictable).
        Pages whose chain hash is already indexed go back on the free
        list.  Returns the newly indexed page count."""
        ps = self.page_size
        n_pages = sum(1 for p in row[0] if p != 0)
        n_full = min(len(tokens) // ps, n_pages)
        hashes = self._chain_hashes(tokens, n_full)
        published = 0
        with self._lock:
            for j in range(self.pages_per_slot):
                page = int(row[0, j])
                if page == 0:
                    continue
                key = hashes[j] if j < n_full else None
                if (key is not None and key not in self._index
                        and (self._prefix_target is None
                             or len(self._page_hash)
                             < self._prefix_target)):
                    self._index[key] = page
                    self._page_hash[page] = key
                    self._page_tokens[key] = [
                        int(t) for t in tokens[:(j + 1) * ps]]
                    self._lru[page] = None
                    self._lru.move_to_end(page)
                    published += 1
                else:
                    self._free.append(page)
            self._set_page_gauges_locked()
        return published

    def export_prefixes(self) -> List[List[int]]:
        """The cached prefixes as token-id lists, MAXIMAL chains only
        (an entry that is a strict prefix of another cached entry is
        implied by it — seeding the long chain republishes every page
        boundary).  The elastic drain exports this so a relaunched
        fleet rebuilds the shared pages instead of re-prefilling every
        cached prefix cold."""
        with self._lock:
            chains = sorted((list(t) for t in self._page_tokens.values()),
                            key=len, reverse=True)
        out: List[List[int]] = []
        for c in chains:
            if not any(len(k) > len(c) and k[:len(c)] == c for k in out):
                out.append(c)
        return out

    def export_prefix_hashes(self, limit: int = 512) -> List[str]:
        """The index keys as hex chain-hash digests, most recently
        published last, bounded to ``limit`` (newest kept) — the
        /healthz affinity export the router tier matches its
        router-side header hashes against.  Hex (not token chains):
        the router needs membership, not reconstruction, and the
        payload stays small on a hot index."""
        with self._lock:
            keys = list(self._index)
        return [k.hex() for k in keys[-int(limit):]]

    def set_prefix_target(self, n_pages: Optional[int]) -> int:
        """Retune the live index-size cap (hvd-tune's ``prefix_pages``
        knob).  Shrinking evicts reclaimable LRU pages back to the
        free list until the index fits (REFERENCED shared pages are
        untouchable — the cap converges as slots release them);
        growing just lifts the cap.  Returns the index size after the
        trim."""
        with self._lock:
            self._prefix_target = None if n_pages is None \
                else max(0, int(n_pages))
            if self._prefix_target is not None:
                while (len(self._page_hash) > self._prefix_target
                       and self._lru):
                    page, _ = self._lru.popitem(last=False)
                    self._drop_index_locked(page)
                    self._free.append(page)
                self._set_page_gauges_locked()
            return len(self._page_hash)

    def reclaimable_pages(self) -> int:
        """Unreferenced cached prefix pages — allocatable on demand, so
        they count toward admission headroom."""
        with self._lock:
            return len(self._lru)

    def prefix_stats(self) -> Dict[str, int]:
        """Index occupancy for /healthz and tests."""
        with self._lock:
            return {
                "cached_pages": len(self._page_hash),
                "referenced_pages": len(self._refcount),
                "reclaimable_pages": len(self._lru),
            }

    def length(self, slot: int) -> int:
        with self._lock:
            return int(self._lengths[slot])

    @property
    def total_pages(self) -> int:
        """Allocatable pages (the trash page is never handed out) —
        the ONE place the reserved-page invariant is priced in."""
        return self.n_pages - 1

    def free_pages(self) -> int:
        """Pages available for allocation: the free list PLUS the
        unreferenced cached prefix pages (reclaimed LRU-first on
        demand) — the honest admission-headroom figure /healthz and
        the scheduler's page-budget gate consume."""
        with self._lock:
            return len(self._free) + len(self._lru)

    def lengths(self) -> np.ndarray:
        """Every slot's cached length (-1 inactive), as
        :meth:`device_tables` would ship them now."""
        with self._lock:
            return self._lengths.copy()

    def table_row(self, slot: int) -> np.ndarray:
        """One slot's page-table row, ``[1, pages_per_slot]`` (a copy —
        the live table may be mutated by a concurrent eviction)."""
        with self._lock:
            return self._wide_locked(slice(slot, slot + 1))

    # -- device views ------------------------------------------------------
    def host_tables(self) -> Tuple[np.ndarray, np.ndarray]:
        """(page_table, lengths) as of ONE lock hold, copies: what the
        serve loop edits into the tables of an iteration it launches
        ahead of the host's own state."""
        with self._lock:
            return self._wide_locked(slice(None)), self._lengths.copy()

    def _wide_locked(self, rows) -> np.ndarray:
        """Rows of the table as the executables take it, a copy: the
        full group's entries, then each window group's ring."""
        if not self._extra:
            return self._table[rows].copy()
        return np.concatenate([self._table[rows]]
                              + [g.table[rows] for g in self._extra],
                              axis=1)

    def device_tables(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(page_table, lengths) as device arrays for the executables
        (replicated under a mesh — they are tiny)."""
        table_np, lengths_np = self.host_tables()
        table = jnp.asarray(table_np)
        lengths = jnp.asarray(lengths_np)
        if self.mesh is not None and self.page_sharding() is not None:
            rep = NamedSharding(self.mesh, P())
            table = jax.device_put(table, rep)
            lengths = jax.device_put(lengths, rep)
        return table, lengths

    @property
    def arrays(self) -> Tuple:
        """Every device array of the cache, as the executables take and
        return them: the page arrays (group after group), then the
        per-slot stores."""
        return self.pages + tuple(
            x for g in self._extra for x in g.pages) + self.slot_state

    def slot_store_bytes(self) -> Dict[str, int]:
        """Resident bytes of the per-slot stores by ``kind``."""
        out: Dict[str, int] = {}
        for spec, x in zip(self.slot_stores, self.slot_state):
            out[spec["kind"]] = (out.get(spec["kind"], 0)
                                 + _mem.resident_nbytes(x))
        return out

    def replace_pages(self, *arrays) -> None:
        """Install the executables' donated outputs, :attr:`arrays` in
        their order (the old references were consumed by the dispatch)."""
        n = len(self.pages)
        if len(arrays) != n * (1 + len(self._extra)) + len(self.slot_state):
            raise ValueError(f"{len(arrays)} page arrays for a cache of "
                             f"{len(self.arrays)}")
        self.pages = tuple(arrays[:n])
        for i, g in enumerate(self._extra, start=1):
            g.pages = tuple(arrays[i * n:(i + 1) * n])
        self.slot_state = tuple(arrays[n * (1 + len(self._extra)):])

    @property
    def k_pages(self):
        return self.pages[0]

    @property
    def v_pages(self):
        return self.pages[1]
