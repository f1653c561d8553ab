"""The hvd-serve inference engine: donated AOT prefill/decode executables
over the paged KV cache, driven by the continuous-batching scheduler.

Megakernel-style data plane (docs/inference.md): each serving phase is
ONE compiled XLA program — gather of the pages it attends →
cache-aware forward → scatter of the new KV entries back into the paged
store — with the page arrays donated, so a decode iteration is a single
dispatch whose working set updates in place.  The engine knows no
model: what a token leaves in the cache and the step functions the
executables wrap come from the model's serving protocol
(serving/models.py; below, the dense multi-head decoder's).  Prefill,
verify and propose gather every slot's whole page-table row into a dense
capacity-long view (:func:`..models.transformer.forward_step`); decode
gathers, layer by layer, only the leading pages of each row that the
iteration's longest live sequence reaches — the smallest rung of a
ladder of page counts, picked inside the program from its ``lengths``
(:func:`..models.transformer.forward_step_paged`; the ladder is the
dense decoder's alone: the other families' kernels walk the page
table).  The decode
loop runs one iteration ahead (:meth:`InferenceEngine._decode_iteration`):
the executable takes the greedy token itself and hands it to the next
launch on the device, so the host prepares and launches iteration i+1
while the device runs i, and fetches ``[slots] int32`` a pass, not the
logits.  An admission joins that pipeline
(:meth:`InferenceEngine._prefill_step`): the prefill executable takes
the first token itself and sets it in the token vector the next launch
reads, so the decode behind a prefill is queued before the host has
seen the token.  Executables are
built ahead of time (``jit(...).lower(...).compile()``) and recorded
in the PR-5 persistent-cache manifest under
``variant: "serving"`` (ops/megakernel.py ``record_manifest_entry``):
:meth:`InferenceEngine.warm_start` rebuilds every recorded executable
at startup — against a warm compile-cache directory
(core/state.compile_cache_dir) the XLA compile is a disk-cache read —
so a relaunched serving fleet reaches full token rate before its first
request, and ``/healthz`` reports NOT_READY until it has.

Bitwise contract (tests/test_serving.py): a prefill of the prompt followed by N single-token
decode iterations reproduces, bit for bit, the logits of the
non-incremental :func:`..models.transformer.serving_forward` of the
same tokens — greedy generation is therefore exactly reproducible
across the static/continuous schedulers, batch compositions, slot
assignments, and engine relaunches.  Two rules carry it: every token
block is at least 2 wide (decode pads a discarded dummy column —
XLA:CPU's single-row gemv accumulates differently from the gemm every
other width uses), and comparisons are jit↔jit (the eager path fuses
differently).

Multi-host serving: rank 0 owns the scheduler and the HTTP front door;
workers mirror its per-iteration plan (admissions, then sampled
tokens/evictions) over the control plane's object collectives and run
the identical executables — the same rank-0-decides/broadcast
convention the checkpoint and elastic paths use.
"""

from __future__ import annotations

import json
import os
import time
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from .. import telemetry as _telemetry
from .. import trace as _trace
from ..analysis import donation as _donation
from ..analysis import lockorder as _lockorder
from ..analysis import threads as _athreads
from ..core.topology import MODEL_AXIS
from ..memory import oom as _oom
from ..memory import planner as _mem_planner
from ..telemetry import flight as _flight
from ..ops import megakernel as _megakernel
from .kv_cache import PagedKVCache
from .models import serving_model as _serving_model
from .scheduler import (ContinuousBatchingScheduler, FinishReason,
                        Request)

_M_TTFT = _telemetry.histogram(
    "serving.ttft_seconds", "seconds",
    "time from submission to the first generated token")
_M_TOKEN_LAT = _telemetry.histogram(
    "serving.token_seconds", "seconds",
    "per-token decode latency (one continuous-batching iteration)")
_M_QUEUE_WAIT = _telemetry.histogram(
    "serving.queue_wait_seconds", "seconds",
    "time from submission to the scheduler granting a decode slot")
_M_TOKENS = _telemetry.counter(
    "serving.tokens_generated", "tokens sampled across all sequences")
_M_PREFILLS = _telemetry.counter(
    "serving.prefills", "prefill executions (one per admission)")
_M_DECODES = _telemetry.counter(
    "serving.decode_iterations", "batched decode iterations")
_M_AHEAD = _telemetry.counter(
    "serving.decode_ahead", "decode iterations launched while the "
    "previous one's tokens were still unfetched (the loop ran one ahead); "
    "over serving.decode_iterations it is the share of the loop that "
    "hid the host behind the device")
_M_PREFILL_AHEAD = _telemetry.counter(
    "serving.prefill_ahead", "admission prefills whose successor decode "
    "was launched before the host fetched the prefill's token (the first "
    "token went from the prefill program to the decode on the device); "
    "over serving.prefills it is the share of admissions that did not "
    "drain the decode loop")
_M_VIEW_TOKENS = _telemetry.counter(
    "serving.decode_view_tokens", "tokens of the KV store per slot that "
    "the decode iterations attended (where a kernel reads the pages in "
    "place, the live tokens rounded up to whole pages; the rung the "
    "dense decoder's ladder or mamba2_hybrid's chunk list rode); over "
    "serving.decode_iterations it is the mean view")
_M_PREFILL_TOKENS = _telemetry.counter(
    "serving.prefill_tokens", "real prompt tokens the admission prefills "
    "ran through the model (a prefix-cache hit's shared tokens and a "
    "bucket's padding are not among them)")
_M_PREFILL_ROWS = _telemetry.counter(
    "serving.prefill_rows", "rows the admission prefills' programs "
    "computed: a prompt's bucket, or what the model's ``prefill_rows("
    "bucket, n_valid)`` says of a program that follows the prompt inside "
    "its bucket; over serving.prefill_tokens it is the padding's cost")
_M_WARM = _telemetry.counter(
    "serving.warm_starts", "serving executables AOT-rebuilt at startup")
_M_TABLES_BYTES = _telemetry.counter(
    "serving.tables_h2d_bytes", "bytes of page table, lengths and token "
    "override the decode passes copied to the device (serve.tables)")
_M_SPEC_PROPOSED = _telemetry.counter(
    "serving.spec_proposed", "draft tokens proposed per speculative "
    "iteration (spec_tokens per active greedy slot)")
_M_SPEC_ACCEPTED = _telemetry.counter(
    "serving.spec_accepted", "draft tokens the bitwise-greedy verify "
    "accepted (the bonus/correction token is not counted)")
_M_SPEC_RATE = _telemetry.gauge(
    "serving.spec_acceptance_rate", "cumulative spec_accepted / "
    "spec_proposed for this engine")


# hvd-trace regions of one serving iteration (docs/tracing.md), all on
# the serve-loop thread and all carrying ``iter``, the engine's own
# iteration counter.  Per iteration and per prefill, never per slot or
# per token.  ``serve.tables`` .. ``serve.sample`` tile one decode
# pass: serving.token_seconds reads the first one's start and the last
# one's end, so they are timed (a speculative iteration starts at its
# launch; a pass that only retires the iteration in flight at its
# wait).  Run ahead, the tables and the launch are the NEXT iteration's
# and the wait and the sampling the one before's.  ``serve.prefill`` is
# what the loop spends on an admission: its enqueue and, where the first
# token stayed on the device for the decode queued behind it, a second
# region of the name around the fetch of that token.
_R_ITERATION = _trace.region("serve.iteration", "serve", timed=True)
_R_ADMIT = _trace.region("serve.admit", "serve")
_R_PREFILL = _trace.region("serve.prefill", "serve")
_R_ENSURE = _trace.region("serve.ensure", "serve")
_R_TABLES = _trace.region("serve.tables", "serve", timed=True)
_R_LAUNCH = _trace.region("serve.launch", "serve", timed=True)
_R_LOGITS_WAIT = _trace.region("serve.logits_wait", "serve", timed=True)
_R_SAMPLE = _trace.region("serve.sample", "serve", timed=True)
_R_WARM_START = _trace.region("serve.warm_start", "init")

# What a pass of the serve loop was, named at its end (``_step``) from
# what it found and what it left: ``start`` — nothing was in flight, the
# pipeline starts (two launches where the loop may run ahead; behind an
# admission in the cells, but a loop that resumes after a ``sync``
# stretch starts without one); ``admission`` — an iteration was in
# flight and at least one prefill was enqueued behind it; ``steady`` —
# one in flight, no prefill, the next one launched; ``retire`` — nothing
# launched, the iteration in flight retired; ``sync`` — depth 0: the
# loop may not run ahead (``_runs_ahead``), or the iteration was
# speculative.  A pass with nothing to do has no kind.  The kind goes
# on the ``serve.iteration`` span, and the pass's seconds (the region's
# own clock reads) into the kind's histogram;
# ``serving.token_seconds`` holds every kind in one.
PASS_KINDS = ("start", "admission", "steady", "retire", "sync")
_M_PASS = {
    kind: _telemetry.histogram(
        "serving.pass_seconds." + kind, "seconds",
        f"one serve.iteration of a {kind} pass of the serve loop")
    for kind in PASS_KINDS}


def _make_cache(model, max_slots: int, pages_per_slot: int,
                page_size: int, kv_pool_bytes: Optional[int] = None,
                kv_expected_tokens: Optional[int] = None,
                **kw) -> PagedKVCache:
    """The paged store for what ``model`` caches.  ``kv_pool_bytes``: the
    bytes its page pools may take together, split over its layer groups
    by ``memory/planner.size_page_pools`` for sequences of
    ``kv_expected_tokens`` positions (left out: every slot at capacity,
    and no byte budget at all means every group holds that)."""
    entry = model.cache_entry()
    groups = entry.get("groups", ())
    pools = None
    if kv_pool_bytes is not None:
        pools = _mem_planner.size_page_pools(
            groups or ({"name": "full", "n_layers": entry["n_layers"]},),
            sum(entry["widths"]) * jnp.dtype(model.cfg.dtype).itemsize,
            page_size, pages_per_slot, max_slots, kv_pool_bytes,
            expected_tokens=kv_expected_tokens)
    cache = PagedKVCache(entry["n_layers"], entry["n_heads"],
                         entry["head_dim"], max_slots, pages_per_slot,
                         page_size, dtype=model.cfg.dtype,
                         entry_widths=entry["widths"],
                         slot_stores=entry.get("slot_stores", ()),
                         groups=groups, pool_pages=pools, **kw)
    if cache.slot_state:
        model.observe_stores(cache.slot_store_bytes())
    return cache


class _Flight:
    """One decode iteration from its plan to its retirement: who rides
    it, the view it attends, the tables it is launched with and, once
    launched, the program's outputs, still on the device."""

    __slots__ = ("riders", "view", "lengths", "inputs", "fresh", "sent",
                 "tokens", "logits", "extras")

    def __init__(self, riders: Dict[int, Request], view, lengths,
                 inputs, fresh: int, sent: Tuple) -> None:
        self.riders = riders    # slot -> the request decoding there
        self.view = view
        self.lengths = lengths  # the host's, as launched (-1: not riding)
        self.inputs = inputs    # (table, lengths, override or None)
        self.fresh = fresh      # riders whose first token the host lacks
        # What the plan cost: (bytes copied to the device, pages mapped
        # for riders one past their cached length, seconds in the
        # copies' calls).
        self.sent = sent
        self.tokens = self.logits = None
        self.extras: Tuple = ()


class InferenceEngine:
    """Continuous-batching inference over one decoder LM.

    ``params``/``cfg`` are a parameter pytree and its config: the
    training-side :class:`~horovod_tpu.models.transformer.
    TransformerConfig`, or any config whose ``serving_model()`` supplies
    the serving protocol (serving/models.py; ``models/latent_moe.py``'s
    latent-attention mixture of experts is one).  With a
    ``mesh`` that has a ``model`` axis, the KV head axis and the
    attention/FFN compute shard over it exactly like the training
    forward (the ``parallel/tensor.py`` layout, via GSPMD).  Threading
    contract: the data plane (``step``/``follow``/``generate``/
    ``run_until_idle``) is driven from ONE thread (the serve loop);
    ``submit`` is thread-safe (the scheduler's lock), and the
    drain-family methods — ``drain``, ``import_requests``,
    ``export_requests`` — may run from other threads (the elastic
    resize path) concurrently with the loop, serialized by
    ``_drain_lock``.  ``abort_all`` is the exception: it broadcasts on
    the control plane, so under multiprocess it must be called from
    the serve-loop thread only (between iterations — see its
    docstring); single-process callers may treat it like the rest of
    the drain family.
    """

    def __init__(self, params: Any, cfg, *, mesh=None, max_slots: int = 8,
                 page_size: int = 16, capacity: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 model_axis: str = MODEL_AXIS,
                 prefix_cache: Optional[bool] = None,
                 prefix_pages: Optional[int] = None,
                 draft: Optional[Tuple[Any, Any]] = None,
                 spec_tokens: Optional[int] = None,
                 kv_pool_bytes: Optional[int] = None,
                 kv_expected_tokens: Optional[int] = None) -> None:
        cap = capacity if capacity is not None else cfg.max_seq_len
        cap = min(cap, cfg.max_seq_len)
        cap -= cap % page_size
        # Compare against the page-floored max_seq_len, or the default
        # capacity (None -> max_seq_len) is spuriously rejected when
        # page_size < max_seq_len < 2*page_size with an unaligned
        # max_seq_len.
        max_cap = cfg.max_seq_len - cfg.max_seq_len % page_size
        if cap < 2 * page_size and cap < max_cap:
            raise ValueError(
                f"capacity {capacity} too small for page_size "
                f"{page_size} (needs >= 2 pages' worth or "
                f"max_seq_len)")
        if cap < 2:
            raise ValueError("KV capacity must be >= 2")
        self.cfg = cfg
        self.model = _serving_model(cfg)
        self.mesh = mesh
        self.eos_id = eos_id
        self.max_slots = max_slots
        # Shared-prefix page cache (hvd-spec): on unless the env or the
        # kwarg opts out; hits are bitwise-invisible, so the default is
        # safe.  The fingerprint keys the chain hashes to this model's
        # config — the cache is per-engine, so parameters are fixed
        # once the fingerprint matches.
        if prefix_cache is None:
            prefix_cache = os.environ.get(
                "HVD_TPU_PREFIX_CACHE", "1") != "0"
        # The dedicated prefix reserve defaults from the env so the
        # RETUNE actuation path (hvd-tune's prefix_pages knob, applied
        # via HVD_TPU_PREFIX_PAGES) reaches the next engine build
        # without a code change at every call site.
        if prefix_pages is None:
            prefix_pages = int(os.environ.get(
                "HVD_TPU_PREFIX_PAGES", "0"))
        if prefix_cache and not self.model.prefix_cache:
            _flight.record("serve_prefix_cache_off",
                           self.model.prefix_cache_why)
            prefix_cache, prefix_pages = False, 0
        if draft is not None and not self.model.speculative:
            raise ValueError(
                f"{type(self.model).__name__} has no verify/propose "
                f"programs: speculative decoding is not supported for "
                f"this model")
        fingerprint = json.dumps(self.model.identity(), sort_keys=True)
        # Exported verbatim in /healthz: the router tier keys its
        # prefix-affinity chain hashes off this (routing/affinity.py).
        self.fingerprint = fingerprint
        if (not self.model.tensor_parallel and mesh is not None
                and dict(mesh.shape).get(model_axis, 1) > 1):
            raise ValueError(
                f"{type(self.model).__name__} "
                f"{self.model.tensor_parallel_why}: its store cannot be "
                f"sharded over the '{model_axis}' axis (serve it with "
                f"mesh=None)")
        self.cache = _make_cache(
            self.model, max_slots, cap // page_size, page_size,
            mesh=mesh, model_axis=model_axis,
            prefix_cache=prefix_cache, prefix_pages=prefix_pages,
            fingerprint=fingerprint, kv_pool_bytes=kv_pool_bytes,
            kv_expected_tokens=kv_expected_tokens)
        self.capacity = self.cache.capacity
        self.scheduler = ContinuousBatchingScheduler(max_slots,
                                                     self.capacity)
        # Where the replicated parameters and the tiny control arrays
        # live under a sharded store (None: wherever jax puts them).
        self._replicated = (
            NamedSharding(mesh, P()) if mesh is not None
            and self.cache.page_sharding() is not None else None)
        self.params = jax.tree_util.tree_map(self._rep, params)
        # Speculative decoding (hvd-spec): a draft model over the same
        # mesh proposes spec_tokens greedy tokens per iteration; ONE
        # donated verify executable runs the target over the block and
        # accepts via the bitwise-greedy rule.  Draft absent => the
        # decode path is bitwise-unchanged.
        if spec_tokens is None:
            spec_tokens = int(os.environ.get("HVD_TPU_SPEC_TOKENS", "3"))
        self.spec_tokens = spec_tokens
        self._draft_params = None
        self._draft_cfg = None
        self._draft_model = None
        self.draft_cache: Optional[PagedKVCache] = None
        if draft is not None:
            # Validated only when a draft is armed: without one the
            # depth is unused, and HVD_TPU_SPEC_TOKENS=0 in the
            # environment must not break draft-less engines.
            if spec_tokens < 1:
                raise ValueError(
                    f"spec_tokens must be >= 1, got {spec_tokens}")
            draft_params, draft_cfg = draft
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab_size {draft_cfg.vocab_size} must "
                    f"match the target's {cfg.vocab_size} (the "
                    f"acceptance rule compares token ids)")
            if draft_cfg.max_seq_len < cap:
                raise ValueError(
                    f"draft max_seq_len {draft_cfg.max_seq_len} must "
                    f"cover the KV capacity {cap}")
            self._draft_cfg = draft_cfg
            self._draft_model = _serving_model(draft_cfg)
            # The draft store rides the shared-prefix index too
            # (hvd-spec tail): a prompt-header hit skips the DRAFT
            # prefill as well as the target's.  Its chain hashes are
            # keyed by the DRAFT config's fingerprint — the two caches
            # hold different models' KV, so their indexes must never
            # collide on a shared token prefix.
            self.draft_cache = _make_cache(
                self._draft_model, max_slots, cap // page_size, page_size,
                mesh=mesh, model_axis=model_axis,
                prefix_cache=prefix_cache,
                fingerprint=json.dumps(self._draft_model.identity(),
                                       sort_keys=True),
                ledger_category="serving.draft_kv")
            draft_params = jax.tree_util.tree_map(self._rep, draft_params)
            self._draft_params = draft_params
            # hvd-mem: the draft's replicated parameters are a
            # framework-resident cost the planner's --draft-layers
            # what-if predicts; account the per-process resident bytes.
            from ..memory import ledger as _mem_ledger

            self._draft_ledger_key = id(self)
            if _mem_ledger.enabled():
                _mem_ledger.ledger.alloc(
                    "serving.draft_params",
                    sum(_mem_ledger.resident_nbytes(x) for x in
                        jax.tree_util.tree_leaves(draft_params)),
                    key=self._draft_ledger_key)
            import weakref

            weakref.finalize(self, _mem_ledger.ledger.free,
                             "serving.draft_params",
                             key=self._draft_ledger_key)
            # hvd-tune: armed speculative engines are live-retunable
            # (set_spec_tokens rides RETUNE stream markers) and feed the
            # controller's acceptance-rate sensor.
            from ..tuning import actuation as _actuation

            _actuation.register_spec_engine(self)
        # hvd-tune: every engine (speculative or not) is known to the
        # actuation layer so the prefix_pages knob can live-retune its
        # cache's index cap and price moves via page_global_bytes.
        from ..tuning import actuation as _tune_actuation

        _tune_actuation.register_serving_engine(self)
        self._buckets = [b for b in
                         (2 ** i for i in range(1, 31))
                         if b <= self.capacity]
        if self._buckets[-1] != self.capacity:
            self._buckets.append(self.capacity)
        self._exec: Dict[Tuple, Any] = {}
        # The decode loop runs one iteration ahead (_decode_iteration):
        # the iteration launched and not yet fetched, and the two token
        # vectors a launch takes when the host has nothing to say: no
        # previous tokens (a start), no override (a steady pass).
        self._inflight: Optional[_Flight] = None
        # An admission rides that pipeline (_admit_prefill): the
        # prefills whose first token is still on the device, (slot,
        # request, token, bucket) in admission order, and the token
        # vector the last of them returned, which the next launch
        # takes for its ``prev``.
        self._fresh: List[Tuple[int, Request, Any, int]] = []
        self._carry = None
        self._no_tokens = self._rep(np.zeros((max_slots,), np.int32))
        self._no_override = self._rep(np.full((max_slots,), -1, np.int32))
        self._last_token = np.zeros((max_slots,), np.int32)
        # The second-newest context token per slot — the catch-up
        # column of the draft's propose block (see
        # models/transformer.speculative_propose).
        self._prev_token = np.zeros((max_slots,), np.int32)
        self._spec_proposed = 0
        self._iter = 0              # serve.* regions' ``iter``
        # Admission prefills the loop has waited out: counted where the
        # host takes (or drops) the first token.  A request's tokens
        # are stamped with it, so two stamps tell how many prefills the
        # gap between them held.
        self._prefills_waited = 0
        self._prefill_bucket = 0    # the last admission prefill's
        self._spec_accepted = 0
        self._ready = False
        self._drained = False
        # Serializes drain/abort_all/import_requests: the serve loop's
        # recovery and the elastic thread's drain_commit run
        # concurrently, and "_drained" check-then-acts must be atomic
        # with the scheduler drain they guard (or a recovery could
        # re-open admission after a commit and silently lose requests).
        # Ordering: _drain_lock is taken BEFORE scheduler._lock, never
        # across a collective (which can block indefinitely).
        self._drain_lock = _lockorder.make_lock(
            "serving.InferenceEngine._drain_lock")
        self._manifest_dir: Optional[str] = None  # warm_start override

    # -- readiness / warm start -------------------------------------------
    @property
    def ready(self) -> bool:
        """True once :meth:`warm_start` completed — the ``/healthz``
        readiness bit (NOT_READY before; the load-balancer keeps
        traffic away until the executables exist)."""
        return self._ready

    def mark_unready(self) -> None:
        """Failure latch: flip ``/healthz`` back to NOT_READY.  Called
        when recovery itself failed and the engine's state can no
        longer be trusted — the load balancer drains traffic instead
        of feeding requests into a blackhole."""
        self._ready = False

    def health(self) -> Tuple[bool, dict]:
        """Exporter health contributor (exporter.register_health).
        ``kv_free_pages`` is the hvd-mem satellite: the router tier
        needs admission HEADROOM (can this replica take a long prompt)
        next to queue depth — occupancy alone says nothing about how
        full the occupied slots' page budgets are."""
        prefix = self.cache.prefix_stats()
        return self._ready, {
            "ready": self._ready,
            "queue_depth": self.scheduler.queue_depth(),
            "batch_occupancy": self.scheduler.occupancy(),
            # free_pages() already counts the prefix cache's
            # reclaimable pages, so the router's headroom figure stays
            # honest with a warm prefix index resident.
            "kv_free_pages": self.cache.free_pages(),
            "kv_total_pages": self.cache.total_pages,
            "kv_reclaimable_pages": prefix["reclaimable_pages"],
            "prefix_cached_pages": prefix["cached_pages"],
            # hvd-route: everything the router tier needs to derive
            # this replica's affinity keys lives in one health poll —
            # the page-hash scheme config plus the live index digests.
            "page_size": self.cache.page_size,
            "pages_per_slot": self.cache.pages_per_slot,
            "fingerprint": self.fingerprint,
            "prefix_index": self.cache.export_prefix_hashes(),
            "speculative": self._draft_params is not None,
            "spec_tokens": (self.spec_tokens
                            if self._draft_params is not None else 0),
            "slots": self.max_slots,
            "executables": len(self._exec),
        }

    def warm_start(self, directory: Optional[str] = None) -> int:
        """Build the decode executable plus every serving executable the
        persistent-cache manifest recorded for this model/mesh, then
        mark the engine ready.  On a relaunch with a warm compile-cache
        directory the compiles are disk-cache reads — the fleet serves at full token rate from the first
        request.  A non-None ``directory`` is also where this engine
        RECORDS its executables from now on (read and write sides must
        agree, or a custom warm-start dir never accumulates entries); a
        ``None`` directory keeps a previously chosen one rather than
        reverting to the env default.  Returns the number of manifest
        entries rebuilt."""
        with _R_WARM_START() as r:
            warmed = self._warm_start(directory)
            r.note(entries=warmed)
        return warmed

    def _warm_start(self, directory: Optional[str]) -> int:
        if directory is None:
            directory = self._manifest_dir
        self._manifest_dir = directory
        ident = self._manifest_identity()
        draft_ident = self._draft_model_dict()
        warmed = 0
        for entry in _megakernel.serving_entries(directory):
            if any(entry.get(k) != ident[k]
                   for k in ("model", "mesh", "slots", "page_size",
                             "pages_per_slot")):
                continue
            kind = entry.get("kind")
            # Speculative executables are keyed to the draft model and
            # the speculation depth too: a relaunch with a different
            # draft (or none) must not rebuild a foreign program.
            if kind in ("verify", "draft_propose", "draft_prefill"):
                if (draft_ident is None
                        or entry.get("draft") != draft_ident
                        or entry.get("spec") != self.spec_tokens):
                    continue
            try:
                if kind == "decode":
                    self._decode_exec()
                elif kind == "prefill":
                    b = int(entry.get("bucket") or 0)
                    if b in self._buckets:
                        self._prefill_exec(b)
                    else:
                        continue
                elif kind == "draft_prefill":
                    b = int(entry.get("bucket") or 0)
                    if b in self._buckets:
                        self._prefill_exec(b, draft=True)
                    else:
                        continue
                elif kind == "verify":
                    self._verify_exec()
                elif kind == "draft_propose":
                    self._propose_exec()
                else:
                    continue
                warmed += 1
            except Exception:  # noqa: BLE001 — a stale entry must not
                continue       # block startup; it just compiles lazily
        self._decode_exec()  # readiness == "can decode", manifest or not
        if self._draft_params is not None:
            # Readiness with a draft also means "can speculate": both
            # per-iteration executables exist before the first request.
            self._propose_exec()
            self._verify_exec()
        if warmed:
            _M_WARM.inc(warmed)
        # hvd-mem pre-flight: the engine's PER-DEVICE working set (one
        # KV shard — global/tp when the head axis is sharded — plus
        # one copy of the replicated params) against the per-device
        # HBM capacity — warned HERE, before the load balancer routes
        # traffic at a replica that cannot actually hold its cache.
        # Per-device, not global and not a per-process sum: either of
        # those cries wolf on exactly the large sharded multi-device
        # deployments this check targets (docs/memory.md).
        try:
            from ..memory import ledger as _mem_ledger

            per_device = sum(
                _mem_ledger.device_nbytes(x) for x in
                self.cache.arrays + tuple(
                    jax.tree_util.tree_leaves(self.params)))
            if self.draft_cache is not None:
                per_device += sum(
                    _mem_ledger.device_nbytes(x) for x in
                    self.draft_cache.pages + tuple(
                        jax.tree_util.tree_leaves(self._draft_params)))
            _oom.preflight_warn(per_device, "serving.warm_start",
                                "KV shard + replicated params "
                                "(per-device bytes)")
        except Exception:  # noqa: BLE001 — sizing is observability
            pass
        self._ready = True
        return warmed

    # -- manifest ----------------------------------------------------------
    def _mesh_key(self):
        if self.mesh is not None:
            return tuple(self.mesh.devices.flat)
        return (jax.devices()[0],)

    def _manifest_identity(self) -> dict:
        return {
            "variant": "serving",
            "model": self.model.identity(),
            "slots": self.max_slots,
            "page_size": self.cache.page_size,
            "pages_per_slot": self.cache.pages_per_slot,
            "mesh": _megakernel.mesh_fingerprint(self._mesh_key()),
        }

    def _draft_model_dict(self) -> Optional[dict]:
        if self._draft_model is None:
            return None
        return self._draft_model.identity()

    def _record(self, kind: str, bucket: Optional[int]) -> None:
        entry = dict(self._manifest_identity())
        entry["kind"] = kind
        entry["bucket"] = bucket
        if kind in ("verify", "draft_propose", "draft_prefill"):
            entry["draft"] = self._draft_model_dict()
            entry["spec"] = self.spec_tokens
        _megakernel.record_manifest_entry(entry, self._manifest_dir)

    # -- executables -------------------------------------------------------
    def _aot(self, key: Tuple, step, cache: PagedKVCache,
             args: Tuple) -> Any:
        """Compile the executable ``key``: ``step(params, pages, *rest)
        -> (outs, pages)``, a step function of the model's, over
        ``args = (params, *cache.arrays, *rest)``' shapes/shardings, the
        cache's arrays (positions 1 ..: pages, then per-slot stores)
        donated; cache it.  The executable takes ``args`` and returns
        ``(*outs, *arrays)``."""
        compiled = self._exec.get(key)
        if compiled is not None:
            return compiled
        n = len(cache.arrays)
        donated = tuple(range(1, 1 + n))

        def fn(params, *rest):
            outs, pages = step(params, rest[:n], *rest[n:])
            return (*outs, *pages)

        avals = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding), args)
        jfn = jax.jit(fn, donate_argnums=donated)
        compiled = jfn.lower(*avals).compile()
        # hvd-mem: harvest compiled.memory_analysis() per serving
        # executable (prefill buckets + decode) into the planner's
        # per-mesh table, where the backend implements the query.
        label = "serving/" + "/".join(str(k) for k in key)
        _mem_planner.record_compiled(label, compiled)

        # hvd-race donation sanitizer: every serving dispatch donates
        # the page arrays (positions 1 ..); routing the executable
        # through the registry turns a stale re-dispatch of donated
        # pages (a forgotten replace_pages) into a DonationError naming
        # this executable instead of XLA's opaque deletion error.
        def guarded(*call_args, _raw=compiled, _label=label):
            return _donation.guard_dispatch(_label, _raw, call_args,
                                            donated)

        self._exec[key] = guarded
        self._record(key[0], key[1] if len(key) > 1 else None)
        return guarded

    def _rep(self, x) -> jnp.ndarray:
        """Array → device, replicated under a sharded store."""
        a = jnp.asarray(x)
        if self._replicated is not None:
            a = jax.device_put(a, self._replicated)
        return a

    def _decode_step(self, params, pages, table, lengths, prev, override):
        """The model's decode program with the token chosen in it: the
        input token a slot is ``override`` where the host gave one (a
        newly admitted slot's first token, from its prefill) and else
        ``prev``, the token the previous program chose, which never
        left the device; the output leads with the greedy choice
        ``[slots] int32`` (the first index among ties, as ``np.argmax``)
        and keeps the logits and the model's extras behind it."""
        tokens = jnp.where(override >= 0, override, prev)
        outs, pages = self.model.decode(params, pages, table, lengths,
                                        tokens)
        chosen = jnp.argmax(outs[0], axis=-1).astype(jnp.int32)
        if self._replicated is not None:
            # It is the next call's ``prev``: the layout that was
            # compiled for.
            chosen = jax.lax.with_sharding_constraint(chosen,
                                                      self._replicated)
        return (chosen, *outs), pages

    def _decode_exec(self) -> Any:
        compiled = self._exec.get(("decode",))
        if compiled is not None:
            return compiled
        table, lengths = self.cache.device_tables()
        args = (self.params, *self.cache.arrays, table, lengths,
                self._no_tokens, self._no_override)
        return self._aot(("decode",), self._decode_step, self.cache, args)

    def _prefill_step(self, params, pages, table, start, n_valid, tokens,
                      prev, slot):
        """The model's prefill program with the first token chosen in
        it, as :meth:`_decode_step` chooses the later ones: the output
        leads with the greedy choice from the last real token's row
        (``int32``, the first index among ties, as ``np.argmax``), then
        ``prev`` (the ``[slots] int32`` the iteration in flight chose)
        with entry ``slot`` set to it, which the next decode launch
        takes for its own ``prev``: the token reaches the decode queued
        behind the prefill without the host.  The row and the model's
        other outputs follow.  A model with per-slot stores is told
        WHICH slot it fills."""
        which = (slot,) if self.model.slot_state else ()
        outs, pages = self.model.prefill(params, pages, table, start,
                                         n_valid, tokens, *which)
        token = jnp.argmax(outs[0], axis=-1).astype(jnp.int32)
        merged = prev.at[slot[0]].set(token)
        if self._replicated is not None:
            merged = jax.lax.with_sharding_constraint(merged,
                                                      self._replicated)
        return (token, merged, *outs), pages

    def _prefill_exec(self, bucket: int, draft: bool = False) -> Any:
        """Prefill executable, START-aware: ``start`` is the number of
        already-cached positions (0 for a cold prefill; the shared
        prefix length on a prefix-cache hit, so only the suffix runs
        through the model), ``n_valid`` the real token count in the
        padded ``tokens`` block — the last real token's logits are what
        admission samples from, in the program (:meth:`_prefill_step`).
        ``draft=True`` builds the draft model's own prefill over its
        cache (cold draft prefill on admission): nobody samples from
        it."""
        key = ("draft_prefill" if draft else "prefill", bucket)
        compiled = self._exec.get(key)
        if compiled is not None:
            # Before the example arguments are built: they are copies
            # to the device, 4-5 ms of an admission's enqueue on the chip.
            return compiled
        cache = self.draft_cache if draft else self.cache
        args = (self._draft_params if draft else self.params,
                *cache.arrays,
                self._rep(np.zeros((1, cache.table_width), np.int32)),
                self._rep(np.zeros((1,), np.int32)),
                self._rep(np.ones((1,), np.int32)),
                self._rep(np.zeros((1, bucket), np.int32)))
        if draft:
            return self._aot(key, self._draft_model.prefill, cache, args)
        args += (self._no_tokens, self._rep(np.zeros((1,), np.int32)))
        return self._aot(key, self._prefill_step, cache, args)

    def _verify_exec(self) -> Any:
        """The speculative-decoding verify program: ONE donated target
        dispatch over the ``spec_tokens + 1``-wide block ``[pending,
        d_1..d_spec]`` for every slot, returning the full per-position
        logits (the host applies the bitwise-greedy acceptance rule to
        them — the same float32 argmax the non-speculative path runs,
        so accepted tokens are exactly the non-speculative greedy
        tokens) and scattering the block's KV.  Rejected positions'
        entries are rolled back host-side (the write cursor simply does
        not advance over them) and overwritten by the next iteration's
        block before they could ever unmask."""
        cache, B = self.cache, self.max_slots
        W = self.spec_tokens + 1
        table, lengths = cache.device_tables()
        args = (self.params, *cache.pages, table, lengths,
                self._rep(np.zeros((B, W), np.int32)))
        return self._aot(("verify", W), self.model.verify, cache, args)

    def _propose_exec(self) -> Any:
        """The draft's propose program: ONE donated dispatch unrolling
        ``spec_tokens`` greedy draft steps per slot
        (models/transformer.speculative_propose) and scattering the
        derived draft KV back into the draft's paged store."""
        dcache, B = self.draft_cache, self.max_slots
        m = self.spec_tokens
        table, lengths = dcache.device_tables()
        args = (self._draft_params, *dcache.pages, table, lengths,
                self._rep(np.zeros((B,), np.int32)),
                self._rep(np.zeros((B,), np.int32)))
        return self._aot(("draft_propose", m),
                         partial(self._draft_model.propose, m=m), dcache,
                         args)

    def _bucket_for(self, n: int) -> int:
        n = max(2, min(n, self.capacity))
        for b in self._buckets:
            if b >= n:
                return b
        return self._buckets[-1]

    # -- request surface ---------------------------------------------------
    def submit(self, prompt: List[int], max_new_tokens: int = 32,
               eos_id: Optional[int] = None, temperature: float = 0.0,
               seed: int = 0, arrival: int = 0,
               prefix: Optional[List[int]] = None) -> Request:
        """``prefix`` (relaunch continuations) is attached BEFORE the
        request enters the queue: a live serve loop may admit and
        sample it immediately, and the sampling rng keys on
        ``len(prefix) + len(generated)``."""
        req = Request(prompt=[int(t) for t in prompt],
                      max_new_tokens=max_new_tokens,
                      eos_id=self.eos_id if eos_id is None else eos_id,
                      temperature=temperature, seed=seed,
                      arrival=arrival)
        if prefix is not None:
            req.prefix = list(prefix)
        req.t_submit = time.monotonic()
        return self.scheduler.submit(req)

    def generate(self, prompt: List[int], max_new_tokens: int = 32,
                 **kw) -> List[int]:
        """Synchronous convenience: submit + drive to completion."""
        req = self.submit(prompt, max_new_tokens, **kw)
        self.run_until_idle()
        return req.result(timeout=0)

    def run_until_idle(self, max_iterations: int = 1_000_000) -> int:
        """Drive :meth:`step` until queue and batch are empty; returns
        iterations run."""
        it = 0
        while not self.scheduler.idle() and it < max_iterations:
            self.step()
            it += 1
        return it

    # -- the continuous-batching iteration --------------------------------
    def step(self, now: Optional[int] = None, admit: bool = True) -> bool:
        """ONE iteration: admit into free slots (prefill each new
        sequence and take its first token from the prefill logits —
        TTFT pays no decode-batching delay; where the loop runs ahead
        the token is taken in the program and fed once the decode
        behind it is launched), then one batched decode
        over every active slot — sequences finish and admit mid-stream,
        no batch boundary.  ``now`` gates admission on logical arrival
        stamps (trace replay); None admits anything queued.
        ``admit=False`` skips admission entirely — that is the whole
        difference between this engine and a static batcher
        (admit only at batch boundaries: the reference
        tests/test_serving.py compares the completions against).
        Returns whether any work ran.

        Multi-host: rank 0 (the only rank with a scheduler) broadcasts
        the admission plan, then post-prefill state, then the sampled
        tokens, so :meth:`follow` on worker ranks mirrors the cache and
        runs the identical executables in the same order."""
        self._iter += 1
        with _R_ITERATION(iter=self._iter) as r:
            ran, kind = self._step(now, admit, r)
        if kind is not None:
            _M_PASS[kind].observe(r.seconds)
        return ran

    def _step(self, now: Optional[int], admit: bool,
              whole) -> Tuple[bool, Optional[str]]:
        """The pass inside its ``serve.iteration`` region ``whole``,
        which takes the pass's kind and the requests it admitted.
        Returns whether any work ran, and the kind (PASS_KINDS; None:
        there was nothing to do)."""
        mp = self._multiprocess()
        it = self._iter
        flying = self._inflight is not None
        with _R_ADMIT(iter=it):
            admitted = self._admit(now) if admit else []
        if mp:
            self._bcast({"stop": False,
                         "admit": [(slot, list(req.prompt))
                                   for slot, req in admitted]})
        # The admissions join the run-ahead pipeline where the decode
        # loop itself may run ahead, by its rule, over everyone alive
        # with them.
        ahead = bool(admitted) and self._runs_ahead(self.scheduler.active())
        for slot, req in admitted:
            self._admit_prefill(slot, req, ahead)
        fresh = bool(self._fresh)   # prefills enqueued, tokens unfetched
        # Clean abort of disconnected clients' slots (hvd-chaos): the
        # eviction happens HERE, at the iteration boundary on the
        # serve-loop thread — the only thread that may free KV slots —
        # and rides the step broadcast's evict list so follower cache
        # mirrors free the same pages (a handler-thread free would
        # silently desync the fleet).  _free_slot covers the draft's
        # pages too (disconnect mid-speculation).
        cancelled = [s for s in self.scheduler.evict_cancelled()
                     if self.cache.length(s) >= 0]
        for slot in cancelled:
            self._free_slot(slot)
        active = self.scheduler.active()
        # Page allocation (the host-side step that can raise — out of
        # pages) runs BEFORE the decode announcement: once a follower
        # reads a non-empty "decode" list it enters the compiled
        # program's collectives and cannot be reached by an abort
        # marker, so everything fallible on the host must happen first.
        # A speculative iteration writes spec_tokens positions past the
        # current length (target) and spec_tokens - 1 (draft), so the
        # whole block's pages map here; writes past the capacity drop
        # into trash inside the kernels.  An all-temperature batch
        # falls back to plain decode — sampled slots never consult
        # proposals, so propose + wide verify would be pure overhead
        # (the draft cache may lag for those slots; greedy slots only
        # ever ride spec iterations, which advance both caches in
        # lockstep, so their draft mirror stays exact).
        spec = (self._draft_params is not None
                and any(req.temperature <= 0.0 for _, req in active))
        depth = self.spec_tokens if spec else 0
        with _R_ENSURE(iter=it, slots=len(active)):
            self._ensure_block(active, depth)
        if mp:
            # Post-prefill sync: first sampled tokens + which slots
            # survived into the decode batch (a max_new_tokens=1
            # admission can finish at prefill).
            self._bcast({
                "last": {s: int(self._last_token[s])
                         for s, _ in active},
                "decode": [s for s, _ in active],
                "spec": spec,
                "evict": cancelled + [s for s, _ in admitted
                                      if self.cache.length(s) < 0]})
        if active:
            if spec:
                self._speculative_iteration(active)
            else:
                self._decode_iteration(active)
        elif self._inflight is not None:
            # Every rider of the iteration in flight ended under it (an
            # ``eos_id``, a cancellation, a drain): nobody wants its
            # tokens.
            self._retired(self._inflight)
            self._inflight = None
        # What the pass was.  It ran ahead if it launched the next
        # iteration or enqueued a prefill; where it did neither, an
        # admission was synchronous, and without one the rule says.
        launched = self._inflight is not None
        if not (active or flying or admitted):
            kind = None
        elif spec or not (launched or fresh
                          or (not admitted and self._runs_ahead(active))):
            kind = "sync"
        elif not flying:
            kind = "start"
        elif fresh:
            kind = "admission"
        else:
            kind = "steady" if launched else "retire"
        # The prefills ran behind the iteration the pass retired: their
        # tokens come last.  Every first token is the host's now.
        self._feed_fresh()
        self._carry = None
        if kind is not None and _trace.enabled():
            # A tuple: the span buffer keeps it, and the collector does
            # not track a tuple of ints (nor the empty one).
            whole.note(kind=kind,
                       admitted=tuple(req.rid for _, req in admitted))
        return bool(admitted or active), kind

    def _admit(self, now: Optional[int]) -> List[Tuple[int, Request]]:
        """Headroom-gated admission: the scheduler prices each
        candidate's prefill against the KV page budget (free list +
        the prefix cache's reclaimable pages) BEFORE burning a slot,
        so a request can never be admitted only to fail page
        allocation mid-iteration.  ``admission_cost`` is exact about
        prefix hits: referenced shared pages are free, reclaimable
        ones cost their LRU slot.  Under the default sizing the gate
        is a structural safety net — a free slot always implies
        headroom — but it keeps overcommitted or future configs
        honest (the pricing is pure: no refcounts move here)."""
        admitted = self.scheduler.admit(
            now, page_budget=self.cache.headroom(),
            pages_needed=lambda req: self.cache.admission_need(
                req.prompt, req.max_new_tokens))
        if admitted:
            # The slot is granted: one stamp for the whole admission.
            t_admit = time.monotonic()
            for _, req in admitted:
                req.t_admit = t_admit
                req.admit_iter = self._iter
                if req.t_submit:
                    _M_QUEUE_WAIT.observe(t_admit - req.t_submit)
        return admitted

    def _ensure_block(self, active, depth: int) -> None:
        for slot, _ in active:
            ln = self.cache.length(slot)
            if ln < 0:
                continue
            self.cache.ensure(slot, min(ln + depth, self.capacity - 1))
            if depth and self.draft_cache is not None:
                self.draft_cache.ensure(
                    slot, min(ln + depth - 1,
                              self.draft_cache.capacity - 1))

    def _sample(self, req: Request, logits: np.ndarray) -> int:
        if req.temperature <= 0.0:
            return int(np.argmax(logits))
        z = (logits - logits.max()) / req.temperature
        p = np.exp(z)
        p /= p.sum()
        # Keyed on request-local state only (seed + decode position),
        # never on scheduler history (rid/slot), so a sampled rollout
        # reproduces across engines, relaunches, and batch mixes.
        rng = np.random.default_rng(
            (req.seed, len(req.prefix) + len(req.generated)))
        return int(rng.choice(len(p), p=p))

    def _free_slot(self, slot: int) -> None:
        """Release one slot's KV everywhere it exists: the target's
        pages (prefix refcounts decrement inside ``free_slot``) AND the
        draft's — a client disconnect mid-speculation must not strand
        draft pages (hvd-chaos).  Idempotent like the underlying
        frees."""
        self.cache.free_slot(slot)
        if self.draft_cache is not None:
            self.draft_cache.free_slot(slot)

    def _feed(self, slot: int, req: Request,
              token: int) -> Optional[str]:
        """Record one sampled/accepted token; returns the finish
        reason when this token ended the sequence (the speculative
        path stops feeding its block there), else None."""
        # One stamp per fed token, on the clock of the request's other
        # stamps and of its span (time.monotonic): the first is
        # t_first_token, the last t_done.
        stamp = time.monotonic()
        if not req.generated:
            req.t_first_token = stamp
            req.first_iter = self._iter
            _M_TTFT.observe(stamp - req.t_submit)
        _M_TOKENS.inc()
        # expect=req: a concurrent drain may have evicted the slot
        # mid-iteration — the token is then discarded (the exported
        # continuation reproduces it) instead of poisoning the step.
        reason = self.scheduler.feed(slot, token, expect=req, stamp=stamp,
                                     prefills=self._prefills_waited)
        if reason is not None:
            req.t_done = stamp
            self._free_slot(slot)  # idempotent vs the drain
            if _trace.enabled():
                # hvd-trace serving span: the whole request lifetime
                # (submit -> completion) from the stamps the engine
                # keeps — serving load on the shared mesh is visible
                # next to training cycles in the fleet trace.  It
                # starts in the past on another thread, so it is a
                # plain span; ``itl_ms`` are the request's inter-token
                # gaps and ``itl_admissions`` the prefills the loop
                # waited out inside each, ``queue_ms`` its wait for a
                # slot; ``rid`` with the three ``*_iter`` joins it to
                # its ``serve.prefill`` regions and to the passes that
                # served it.
                times, waited = req.token_times, req.token_prefills
                _trace.span(
                    "serving.request", "serving", req.t_submit, stamp,
                    args={"rid": req.rid,
                          "tokens": len(req.generated),
                          "reason": reason,
                          "queue_ms": round(
                              (req.t_admit - req.t_submit) * 1e3, 3)
                          if req.t_admit else None,
                          "admit_iter": req.admit_iter,
                          "first_iter": req.first_iter,
                          "last_iter": self._iter,
                          "itl_ms": [round((b - a) * 1e3, 3) for a, b
                                     in zip(times, times[1:])],
                          "itl_admissions": [b - a for a, b
                                             in zip(waited, waited[1:])]})
        else:
            self._last_token[slot] = token
        return reason

    def _prefill(self, slot: int, req: Request,
                 prompt: Optional[List[int]] = None, prev=None) -> Tuple:
        """Admission prefill: enqueues the program and returns its
        outputs on the device, ``(token, tokens, last)``: the greedy
        first token, ``prev`` (the token vector of the iteration in
        flight; None: nothing the next launch needs) with this slot's
        entry set to it, and the last real token's logits row; whoever
        fetches one waits for the program.  With a prefix-cache hit the
        shared pages map copy-free and ONLY the suffix runs through the
        model (the KV a suffix prefill derives is bitwise-identical to a cold
        full prefill's: every gemm is row-wise over M>=2 blocks, the
        same discipline the prefill+decode ≡ non-incremental contract
        already rides).  The completed prompt's full pages publish into
        the index afterwards, so the NEXT request sharing the header
        hits.  With a draft model, the draft's prefill rides its OWN
        shared-prefix index the same way (hvd-spec tail): a repeated
        header skips the draft prefill too, and the suffix-only draft
        KV is bitwise-identical to the cold full prefill's by the same
        M>=2 gemm discipline — the acceptance rule sees identical
        proposals either way."""
        prompt = list(req.prompt) if prompt is None else prompt
        n = len(prompt)
        shared = self.cache.lookup_prefix(prompt)
        n_shared = len(shared) * self.cache.page_size
        self.cache.begin_slot(slot, n, prefix_pages=shared,
                              reserve_tokens=min(
                                  n + req.max_new_tokens - len(req.generated),
                                  self.capacity))
        suffix = prompt[n_shared:]
        bucket = self._prefill_bucket = self._bucket_for(len(suffix))
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :len(suffix)] = suffix
        compiled = self._prefill_exec(bucket)
        with _oom.guard(f"serving/prefill/{bucket}"):
            token, merged, last, *pages = compiled(
                self.params, *self.cache.arrays,
                self._rep(self.cache.table_row(slot)),
                self._rep(np.asarray([n_shared], np.int32)),
                self._rep(np.asarray([len(suffix)], np.int32)),
                self._rep(tokens),
                self._no_tokens if prev is None else prev,
                self._rep(np.asarray([slot], np.int32)))
        self.cache.replace_pages(*pages)
        _M_PREFILL_TOKENS.inc(len(suffix))
        rows_of = getattr(self.model, "prefill_rows", None)
        _M_PREFILL_ROWS.inc(bucket if rows_of is None
                            else rows_of(bucket, len(suffix)))
        self.cache.publish_prefix(slot, prompt)
        if self._draft_params is not None:
            dshared = self.draft_cache.lookup_prefix(prompt)
            dn_shared = len(dshared) * self.draft_cache.page_size
            self.draft_cache.begin_slot(slot, n, prefix_pages=dshared)
            dsuffix = prompt[dn_shared:]
            dbucket = self._bucket_for(len(dsuffix))
            dtokens = np.zeros((1, dbucket), np.int32)
            dtokens[0, :len(dsuffix)] = dsuffix
            dcompiled = self._prefill_exec(dbucket, draft=True)
            with _oom.guard(f"serving/draft_prefill/{dbucket}"):
                _, *dpages = dcompiled(
                    self._draft_params, *self.draft_cache.pages,
                    self._rep(self.draft_cache.table_row(slot)),
                    self._rep(np.asarray([dn_shared], np.int32)),
                    self._rep(np.asarray([len(dsuffix)], np.int32)),
                    self._rep(dtokens))
            self.draft_cache.replace_pages(*dpages)
            self.draft_cache.publish_prefix(slot, prompt)
        self._prev_token[slot] = prompt[-1]
        _M_PREFILLS.inc()
        return token, merged, last

    def _runs_ahead(self, active) -> bool:
        """Whether a pass may launch the next iteration before the host
        holds this one's tokens, and an admission the decode behind its
        prefill.  It follows from the requests alive:
        every one greedy (a sampled token is a host draw from the
        logits row, keyed ``(seed, position)``), no draft (its greedy
        slots ride propose/verify), one process (``follow`` mirrors
        rank 0 token by token)."""
        return (self._draft_params is None
                and all(req.temperature <= 0.0 for _, req in active)
                and not self._multiprocess())

    def _continuing(self, active,
                    *unseen: Dict[int, Request]) -> Dict[int, Request]:
        """Who rides an iteration launched with tokens the host has not
        seen: each of ``unseen`` names the requests one such token is
        due (the riders of the iteration in flight; the prefills whose
        first token is on the device).  Every request alive rides but
        those these tokens finish by ``max_new_tokens`` or capacity — a
        count, which the host knows without the token.  An ``eos_id``
        it cannot know: such a request rides once more (``_retire``)."""
        riders = {}
        for slot, req in active:
            due = sum(u.get(slot) is req for u in unseen)
            if due:
                n = len(req.generated) + due
                if (n >= req.max_new_tokens
                        or len(req.prompt) + n >= self.capacity):
                    continue
            riders[slot] = req
        return riders

    def _plan(self, riders: Dict[int, Request],
              behind: Optional[Dict[int, Request]],
              fresh: Dict[int, Request]) -> Optional[_Flight]:
        """The host's half of one launch: the tables ``riders`` decode
        at, on the device.  ``behind`` is who rode the iteration whose
        tokens the host has not fetched (None: it holds every token).
        A request of ``behind`` decodes one past its cached length (its
        page mapped here, which may raise: before any launch of the
        pass) and takes its token from that program's output; a request
        of ``fresh`` (its prefill's token unfetched) decodes at its
        cached length and takes the token its prefill set in the
        launch's ``prev``; every other rider gets the host's token
        through the override.
        Whoever does not ride — finishing under ``behind``, or freed by
        a drain meanwhile — is shipped as an empty slot: length -1, row
        on the trash page.  Returns None with nobody left to ride."""
        behind = behind or {}
        cached = self.cache.lengths()
        mapped = 0
        for slot, req in riders.items():
            if behind.get(slot) is req:
                mapped += self.cache.ensure(slot, int(cached[slot]) + 1)
        table, cached = self.cache.host_tables()
        lengths = np.full_like(cached, -1)
        override = None
        riding = {}
        n_fresh = 0
        for slot, req in riders.items():
            if cached[slot] < 0:
                continue
            riding[slot] = req
            if behind.get(slot) is req:
                lengths[slot] = cached[slot] + 1
                continue
            lengths[slot] = cached[slot]
            if fresh.get(slot) is req:
                n_fresh += 1
                continue
            if override is None:
                override = np.full_like(cached, -1)
            override[slot] = self._last_token[slot]
        if not riding:
            return None
        table[lengths < 0] = 0
        t0 = time.monotonic()
        inputs = (self._rep(table), self._rep(lengths),
                  None if override is None else self._rep(override))
        copy_s = time.monotonic() - t0
        nbytes = table.nbytes + lengths.nbytes + (
            0 if override is None else override.nbytes)
        # The view the program is about to attend at ``lengths``.
        return _Flight(
            riding, self.model.decode_view(lengths, self.cache.page_size,
                                           self.cache.pages_per_slot),
            lengths, inputs, n_fresh, (nbytes, mapped, copy_s))

    @staticmethod
    def _note_sent(tables, *flights: Optional[_Flight]) -> None:
        """What the pass's plans sent to the device, on its
        ``serve.tables`` span and in the counter; a start's two plans
        add up."""
        sent = [f.sent for f in flights if f is not None]
        if not sent:
            return
        nbytes, mapped, copy_s = map(sum, zip(*sent))
        _M_TABLES_BYTES.inc(nbytes)
        if _trace.enabled():
            tables.note(h2d_bytes=nbytes, pages_mapped=mapped,
                        copy_ms=round(copy_s * 1e3, 3))

    def _launch(self, flight: _Flight, prev: Optional[_Flight]) -> _Flight:
        """Enqueue a planned iteration behind ``prev`` (None: behind
        no iteration the host has not fetched).  The first launch after
        an admission that rides takes the token vector its prefill
        returned: ``prev``'s with the first tokens set."""
        (table, lengths, override), flight.inputs = flight.inputs, None
        tokens, self._carry = self._carry, None
        if tokens is None:
            tokens = self._no_tokens if prev is None else prev.tokens
        compiled = self._decode_exec()
        with _oom.guard("serving/decode"):
            out = compiled(
                self.params, *self.cache.arrays, table, lengths, tokens,
                self._no_override if override is None else override)
        stores = len(self.cache.arrays)
        self.cache.replace_pages(*out[-stores:])
        flight.tokens, flight.logits = out[0], out[1]
        flight.extras = out[2:-stores]
        if prev is not None:
            _M_AHEAD.inc()
        if flight.fresh:
            _M_PREFILL_AHEAD.inc(flight.fresh)
        return flight

    def _decode_iteration(self, active):
        """One decode pass over ``active``; the caller (step) has
        already run ``cache.ensure`` for every slot.  The loop is
        pipelined one deep: with an iteration in flight, the pass
        plans and launches the NEXT one (``serve.tables``,
        ``serve.launch``) and only then fetches and feeds the tokens of
        the one in flight (``serve.logits_wait``, ``serve.sample``), so
        the host's share of an iteration runs under the device's.  A
        pass with nothing in flight launches two and feeds the first;
        a pass that may not run ahead (:meth:`_runs_ahead`) launches at
        most the one it feeds.  Either way every rider of the retired
        iteration is fed exactly one token: ``step()``'s contract.

        The pass's admissions ride the first launch, their first tokens
        unfetched (:meth:`_admit_prefill`), and the host takes results
        in the order the device gives them: behind an iteration in
        flight the prefills ran after it, so their tokens are fed after
        its retirement (``_step``); with nothing in flight they ran
        before the iteration the pass retires, so they are fed here,
        before it, and that iteration is timed from its wait: its
        tables and launch ran under the prefill.

        What a launch needs of the iteration before it is one integer a
        slot, and that stays on the device (:meth:`_decode_step`).
        What the host cannot know ahead it handles late: a request
        that ends under the iteration in flight by ``eos_id`` (or a
        cancellation, or a drain) has ridden it; its token is dropped
        and its slot was freed at its end, which is safe because the
        device runs programs in launch order — a freed page is only
        written again by a program enqueued after the stale one.

        Returns the retired iteration's logits, still on the device:
        rows for whoever asks."""
        it = self._iter
        fresh = {slot: req for slot, req, _, _ in self._fresh}
        flight, self._inflight = self._inflight, None
        starts = flight is None
        behind = (self._continuing(active, fresh) if starts
                  else flight.riders)
        ahead = (self._continuing(active, behind, fresh)
                 if self._runs_ahead(active) else {})
        first = None
        if starts or ahead:
            with _R_TABLES(iter=it) as first:
                start = self._plan(behind, None, fresh) if starts else None
                ahead = (self._plan(ahead, behind, {} if starts else fresh)
                         if ahead else None)
                self._note_sent(first, start, ahead)
            with _R_LAUNCH(iter=it):
                if start is not None:
                    flight = self._launch(start, None)
                if ahead is not None and flight is not None:
                    self._inflight = self._launch(ahead, flight)
        if starts and fresh:
            self._feed_fresh()
            first = None
        if flight is None:
            return None     # a drain freed every slot under the pass
        return self._retire(flight, first)

    def _retire(self, flight: _Flight, first):
        """Fetch ``flight``'s tokens and feed them.  ``first`` is the
        pass's ``serve.tables`` region, None where it launched
        nothing."""
        it = self._iter
        with _R_LOGITS_WAIT(iter=it) as wait:
            # The host blocked on the device: ``[slots] int32``, and
            # the logits only for a slot that samples (its pass
            # launched nothing ahead).
            tokens = np.asarray(flight.tokens)
            rows = None
            if any(req.temperature > 0.0
                   for req in flight.riders.values()):
                rows = np.asarray(flight.logits)
        with _R_SAMPLE(iter=it, slots=len(flight.riders)) as last:
            if flight.extras:
                # What the program returned beside the logits (a model's
                # own counts).
                self.model.observe_decode(flight.extras)
            fed = {}
            evicted = []
            for slot, req in flight.riders.items():
                if req.finish_reason is not None:
                    # Ended while the iteration flew: the token is
                    # dropped, and the slot may be another request's.
                    continue
                self.cache.advance(slot)  # the input token's KV landed
                token = (int(tokens[slot]) if req.temperature <= 0.0
                         else self._sample(req, rows[slot]))
                fed[slot] = token
                self._feed(slot, req, token)
                if self.cache.length(slot) < 0:
                    evicted.append(slot)
            if self._multiprocess():
                self._bcast({"tokens": fed, "evict": evicted})
        self._retired(flight)
        _M_TOKEN_LAT.observe(last.t1 - (wait if first is None else first).t0)
        return flight.logits

    def _retired(self, flight: _Flight) -> None:
        """Count an iteration the device ran, fed or dropped."""
        _M_DECODES.inc()
        _M_VIEW_TOKENS.inc(flight.view)
        observe = getattr(self.model, "observe_launch", None)
        if observe is not None:
            # What the model itself counts of an iteration, from the
            # lengths it was launched with.
            observe(flight.lengths)

    def _admit_prefill(self, slot: int, req: Request, ahead: bool) -> None:
        """One admission's prefill.  ``ahead``: the program is only
        enqueued, behind the iteration in flight and the pass's earlier
        admissions; its token stays on the device, set in the vector
        the pass's first launch takes (:meth:`_prefill_step`), and the
        host feeds it when it has launched (:meth:`_feed_fresh`).
        Else the host waits for the logits row here and draws from
        it."""
        with _R_PREFILL(iter=self._iter, rid=req.rid,
                        prompt_tokens=len(req.prompt)) as r:
            if ahead:
                prev = self._carry
                if prev is None and self._inflight is not None:
                    prev = self._inflight.tokens
                token, self._carry, _ = self._prefill(slot, req, prev=prev)
                self._fresh.append((slot, req, token,
                                    self._prefill_bucket))
            else:
                last = np.asarray(self._prefill(slot, req)[2])
                self._prefills_waited += 1
                self._feed(slot, req, self._sample(req, last))
            r.note(bucket=self._prefill_bucket)

    def _feed_fresh(self) -> None:
        """Fetch and feed the first tokens the pass's prefills left on
        the device, in admission order; here the host first holds them,
        so here they are stamped.  A request that ended meanwhile (a
        cancellation, a drain) has its token dropped, as ``_retire``
        drops a decode's; an error of the prefill program surfaces at
        the fetch, under its executable's name."""
        while self._fresh:
            slot, req, token, bucket = self._fresh.pop(0)
            # Dropped or fed, its program ran before whatever the loop
            # fetches next.
            self._prefills_waited += 1
            if req.finish_reason is not None:
                continue
            with _R_PREFILL(iter=self._iter, rid=req.rid,
                            prompt_tokens=len(req.prompt), bucket=bucket):
                with _oom.guard(f"serving/prefill/{bucket}"):
                    token = int(np.asarray(token))
                self._feed(slot, req, token)

    # -- speculative decoding ---------------------------------------------
    def _spec_dispatch(self, slots: Sequence[int]):
        """The speculative iteration's two dispatches — draft propose,
        then target verify — shared verbatim by rank 0 and
        :meth:`follow` so the fleet's page arrays stay identical.
        Returns ``(proposals [B, spec_tokens], logits [B, spec_tokens
        + 1, vocab])`` as numpy."""
        B = self.max_slots
        prev = np.zeros((B,), np.int32)
        pending = np.zeros((B,), np.int32)
        for s in slots:
            prev[s] = self._prev_token[s]
            pending[s] = self._last_token[s]
        dtable, dlengths = self.draft_cache.device_tables()
        compiled = self._propose_exec()
        with _oom.guard(f"serving/draft_propose/{self.spec_tokens}"):
            proposals, *dpages = compiled(
                self._draft_params, *self.draft_cache.pages, dtable,
                dlengths, self._rep(prev), self._rep(pending))
        self.draft_cache.replace_pages(*dpages)
        props = np.asarray(proposals)
        W = self.spec_tokens + 1
        blocks = np.zeros((B, W), np.int32)
        for s in slots:
            blocks[s, 0] = pending[s]
            blocks[s, 1:] = props[s]
        table, lengths = self.cache.device_tables()
        compiled = self._verify_exec()
        with _oom.guard(f"serving/verify/{W}"):
            logits, *pages = compiled(
                self.params, *self.cache.pages, table, lengths,
                self._rep(blocks))
        self.cache.replace_pages(*pages)
        return props, np.asarray(logits)

    def _speculative_iteration(self, active) -> None:
        """One speculative iteration over ``active``: propose + verify
        (two dispatches total — the draft's and the target's), then the
        host-side bitwise-greedy acceptance.  For a greedy slot the
        accepted tokens plus the correction/bonus token are EXACTLY the
        tokens non-speculative greedy decode would emit (the verify
        logits are bitwise-equal to the decode executable's at every
        position — the M>=2 gemm discipline — and the acceptance rule
        is the same float32 argmax), so the engine's bitwise contract
        survives any draft, any acceptance pattern, any batch mix.  A
        temperature slot samples from the block's first position only —
        bitwise what the decode path would sample.  Rejected tail:
        the write cursor (cache lengths) just does not advance over it;
        the pages stay masked and the next block overwrites them."""
        it = self._iter
        m = self.spec_tokens
        # The boundaries this iteration shares with plain decode carry
        # the same names: its two dispatches (with their host copies)
        # are the launch, the acceptance loop is the sampling.
        with _R_LAUNCH(iter=it, spec=m) as first:
            props, logits_np = self._spec_dispatch(
                [s for s, _ in active])
        with _R_SAMPLE(iter=it, slots=len(active)) as last:
            fed: Dict[int, int] = {}
            prev: Dict[int, int] = {}
            advance: Dict[int, int] = {}
            evicted: List[int] = []
            for slot, req in active:
                if req.temperature <= 0.0:
                    greedy = np.argmax(logits_np[slot], axis=-1)
                    accept = 0
                    while (accept < m
                           and int(props[slot, accept])
                           == int(greedy[accept])):
                        accept += 1
                    emitted = [int(props[slot, j]) for j in range(accept)]
                    emitted.append(int(greedy[accept]))
                    # Greedy slots only: a temperature slot never consults
                    # the proposals (accept == 0 by construction), so
                    # counting it would dilute spec_acceptance_rate — the
                    # gauge operators size spec_tokens by.
                    self._spec_proposed += m
                    self._spec_accepted += accept
                    _M_SPEC_PROPOSED.inc(m)
                    if accept:
                        _M_SPEC_ACCEPTED.inc(accept)
                else:
                    emitted = [self._sample(req, logits_np[slot, 0])]
                    accept = 0
                last_before = int(self._last_token[slot])
                finished = False
                for t in emitted:
                    if self._feed(slot, req, t) is not None:
                        finished = True
                        break
                if finished or self.cache.length(slot) < 0:
                    evicted.append(slot)
                    continue
                # The accepted inputs' KV is now valid: pending plus the
                # accepted drafts (the bonus token is the new pending — its
                # KV lands next iteration).
                n_adv = 1 + accept
                for _ in range(n_adv):
                    self.cache.advance(slot)
                    self.draft_cache.advance(slot)
                self._prev_token[slot] = (emitted[-2] if len(emitted) >= 2
                                          else last_before)
                fed[slot] = int(self._last_token[slot])
                prev[slot] = int(self._prev_token[slot])
                advance[slot] = n_adv
            if self._spec_proposed:
                _M_SPEC_RATE.set(self._spec_accepted / self._spec_proposed)
            if self._multiprocess():
                self._bcast({"tokens": fed, "prev": prev,
                             "advance": advance, "evict": evicted})
        _M_DECODES.inc()
        _M_TOKEN_LAT.observe(last.t1 - first.t0)

    @property
    def spec_acceptance_rate(self) -> Optional[float]:
        """Cumulative accepted/proposed draft-token ratio (None before
        the first speculative iteration)."""
        if not self._spec_proposed:
            return None
        return self._spec_accepted / self._spec_proposed

    def set_spec_tokens(self, n: int) -> None:
        """hvd-tune live retune (tuning/actuation.py): change the
        speculative depth between iterations.  The propose/verify
        programs are keyed by depth, so the next iteration compiles (or
        reuses) the executables for the new block size — no flush."""
        n = int(n)
        if n < 1:
            raise ValueError(f"spec_tokens must be >= 1, got {n}")
        self.spec_tokens = n

    def spec_token_bytes(self) -> int:
        """Per-spec-token byte cost for the hvd-mem pricing of
        spec_tokens retunes: one target + one draft KV token column per
        slot (the verify writes target KV for every proposed token)."""
        per_tok = 0
        for cache in (self.cache, self.draft_cache):
            if cache is not None:
                per_tok += cache.page_global_bytes // cache.page_size
        return per_tok * self.max_slots

    # -- multi-host mirroring ---------------------------------------------
    def _multiprocess(self) -> bool:
        try:
            from ..core import state as _state

            return (_state.is_initialized()
                    and _state.global_state().multiprocess
                    and _state.global_state().process_count > 1)
        except Exception:  # noqa: BLE001 — serving works without init
            return False

    def _bcast(self, obj):
        from ..ops.objects import broadcast_object

        return broadcast_object(obj, root_rank=0, name="hvd-serve-plan")

    def follow(self) -> bool:
        """Worker-rank iteration mirroring ONE rank-0 :meth:`step`:
        receive the admission plan (prefill those slots), the
        post-prefill sync (first tokens + decode batch + early
        evictions), run the identical decode executable when rank 0
        does, then apply its sampled tokens/evictions to the local
        cache mirror.  Returns False when rank 0 announced shutdown
        (:meth:`stop_followers`).  Worker ranks have no scheduler —
        rank 0 decides, the data plane stays SPMD.

        Any of the three receptions may instead carry rank 0's
        ``abort`` marker (:meth:`abort_all` after a poisoned step died
        mid-iteration): the worker mirrors the recovery by freeing
        every cache slot and returning, keeping the fleet's caches
        identical for the next iteration."""
        plan = self._bcast(None)
        if plan.get("stop"):
            return False
        if plan.get("abort"):
            self._free_all_slots()
            return True
        for slot, prompt in plan.get("admit", ()):
            self._prefill(slot, Request(prompt=list(prompt)),
                          prompt=list(prompt))
        sync = self._bcast(None)
        if sync.get("abort"):
            self._free_all_slots()
            return True
        for slot, token in sync.get("last", {}).items():
            self._last_token[int(slot)] = int(token)
        for slot in sync.get("evict", ()):
            if self.cache.length(int(slot)) >= 0:
                self._free_slot(int(slot))
        decode = [int(s) for s in sync.get("decode", ())]
        if decode:
            spec = bool(sync.get("spec")) \
                and self._draft_params is not None
            self._ensure_block([(s, None) for s in decode],
                               self.spec_tokens if spec else 0)
            if spec:
                # Same two dispatches as rank 0 (_spec_dispatch), then
                # apply ITS acceptance results — host argmax is
                # deterministic, but the broadcast keeps the mirror
                # trivially exact.
                self._spec_dispatch(decode)
            else:
                table, lengths = self.cache.device_tables()
                tokens = np.zeros((self.max_slots,), np.int32)
                for slot in decode:
                    tokens[slot] = self._last_token[slot]
                compiled = self._decode_exec()
                with _oom.guard("serving/decode"):
                    out = compiled(self.params, *self.cache.arrays,
                                   table, lengths, self._rep(tokens),
                                   self._no_override)
                self.cache.replace_pages(*out[-len(self.cache.arrays):])
            fed = self._bcast(None)
            if fed.get("abort"):
                # Rank 0's decode/speculative iteration died before
                # broadcasting the sampled tokens; it freed everything
                # — mirror that (and skip the advance: rank 0 never
                # advanced).
                self._free_all_slots()
                return True
            if spec:
                for slot, n_adv in fed.get("advance", {}).items():
                    for _ in range(int(n_adv)):
                        self.cache.advance(int(slot))
                        self.draft_cache.advance(int(slot))
                for slot, token in fed.get("prev", {}).items():
                    self._prev_token[int(slot)] = int(token)
            else:
                for slot in decode:
                    self.cache.advance(slot)
            for slot, token in fed.get("tokens", {}).items():
                self._last_token[int(slot)] = int(token)
            for slot in fed.get("evict", ()):
                if self.cache.length(int(slot)) >= 0:
                    self._free_slot(int(slot))
        return True

    def stop_followers(self) -> None:
        if self._multiprocess():
            self._bcast({"stop": True})

    # -- elastic drain / resume -------------------------------------------
    @staticmethod
    def _export_request(req: Request) -> dict:
        """A request as a resubmittable continuation: prompt extended
        by what it generated so far (the bitwise prefill≡decode
        contract makes the continuation reproduce the uninterrupted
        greedy rollout).  A queued request has ``generated == []``, so
        this reduces to its original submission.  ``generated`` is read
        ONCE: export_requests() can run concurrently with the serve
        loop's feed(), and deriving the three fields from different
        generation states would commit an internally inconsistent
        continuation."""
        gen = list(req.generated)
        return {
            "prompt": list(req.prompt) + gen,
            "generated_prefix": list(req.prefix) + gen,
            "max_new_tokens": req.max_new_tokens - len(gen),
            "eos_id": req.eos_id, "temperature": req.temperature,
            "seed": req.seed,
        }

    def export_requests(self) -> List[dict]:
        """Queued + in-flight work as resubmittable dicts (one atomic
        scheduler snapshot — a request admitted concurrently cannot fall
        between the active and pending halves).  Does not stop the
        engine — pair with :meth:`drain` for the elastic resize path
        (:class:`horovod_tpu.elastic.ServingState`).
        """
        active, pending = self.scheduler.snapshot()
        return [self._export_request(req)
                for req in [r for _, r in active] + pending]

    def drain(self) -> List[dict]:
        """Serving-fleet resize, step 1: capture every queued and
        in-flight request as a continuation, then evict everything and
        stop admission.  The export is built from exactly the requests
        the scheduler's drain removed (one lock hold), so a submission
        racing the drain is either exported or rejected — never lost.
        The returned list (same format as :meth:`export_requests`) is
        what the elastic commit persists; a relaunched engine resubmits
        it via :meth:`import_requests`."""
        with self._drain_lock:
            self._drained = True
            drained, pending = self._drain_and_finish(
                FinishReason.DRAINED)
        return [self._export_request(req) for req in drained + pending]

    def _free_all_slots(self) -> None:
        for slot in range(self.max_slots):
            if self.cache.length(slot) >= 0:
                self._free_slot(slot)

    # -- shared-prefix index export / rebuild ------------------------------
    def export_prefix_index(self) -> List[List[int]]:
        """The prefix cache's maximal cached chains as token-id lists
        (hash → token ids) — what ``elastic.ServingState.drain_commit``
        persists next to the continuations so a relaunched fleet
        rebuilds the shared pages instead of re-prefilling every
        cached prefix cold."""
        return self.cache.export_prefixes()

    def seed_prefixes(self, prefixes: Sequence[Sequence[int]]) -> int:
        """Rebuild exported prefixes into this engine's cache: each
        chain prefills ONCE through a ghost page row (no decode slot
        burned) and publishes with refcount zero — immediately
        hittable, reclaimable under pressure.  Returns the number of
        pages seeded."""
        if not self.cache.prefix_enabled:
            return 0
        seeded = 0
        ps = self.cache.page_size
        for chain in prefixes:
            tokens = [int(t) for t in chain]
            n_pages = min(len(tokens) // ps,
                          self.cache.pages_per_slot)
            if n_pages <= 0:
                continue
            tokens = tokens[:n_pages * ps]
            # +[0] sentinel: lookup_prefix only matches STRICT
            # prefixes; the sentinel never reaches a full page, so
            # this checks whether all n_pages are already cached.
            if len(self.cache.lookup_prefix(tokens + [0])) >= n_pages:
                continue
            row = self.cache.alloc_ghost(n_pages)
            n = len(tokens)
            bucket = self._bucket_for(n)
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :n] = tokens
            try:
                compiled = self._prefill_exec(bucket)
                with _oom.guard(f"serving/prefill/{bucket}"):
                    # Nobody decodes behind a ghost row: the token
                    # lands in a vector nobody reads.
                    _, _, _, *pages = compiled(
                        self.params, *self.cache.pages, self._rep(row),
                        self._rep(np.zeros((1,), np.int32)),
                        self._rep(np.asarray([n], np.int32)),
                        self._rep(toks), self._no_tokens,
                        self._rep(np.zeros((1,), np.int32)))
            except Exception as e:  # noqa: BLE001 — seeding is an
                # optimization: one failed chain must neither strand
                # its ghost pages (the sizing invariant would silently
                # erode) nor abort the elastic restore that still has
                # requests to resubmit after this.
                self.cache.free_ghost(row)
                _telemetry.exception_event(
                    "serve-seed-prefix",
                    f"dropping {n_pages}-page prefix seed: "
                    f"{type(e).__name__}: {e}")
                continue
            self.cache.replace_pages(*pages)
            seeded += self.cache.publish_ghost(row, tokens)
        return seeded

    def _drain_and_finish(self, reason: str):
        """The shared eviction sequence (caller holds ``_drain_lock``):
        scheduler drain with ``reason``, free every KV slot, and finish
        the still-queued requests' Python objects with the same reason
        — their blocked /generate handlers fail fast instead of hanging
        to the client timeout (the relaunch path resubmits NEW Request
        objects from the export, so finishing these loses nothing).
        Returns ``(drained, pending)``."""
        drained, pending = self.scheduler.drain(reason)
        self._free_all_slots()
        for req in pending:
            req.finish_reason = reason
            req.done.set()
        return drained, pending

    def abort_all(self) -> List[Request]:
        """Error recovery (the serve loop's poisoned-step path):
        atomically evict and FAIL every queued and in-flight request —
        ``finish_reason`` is ``"error"`` before ``done`` is set, so a
        blocked ``/generate`` handler can never observe a stale reason —
        free the KV slots, and re-open admission.  Unlike :meth:`drain`
        nothing is exported: callers answer the failed requests
        immediately instead of requeueing them.  Returns the failed
        requests (raced submissions included).

        Admission re-opens ONLY when no elastic :meth:`drain` is
        pending (checked under the same lock the drain holds, so the
        recovery cannot interleave with a concurrent drain_commit and
        resume after it): if the loop's recovery fires after a drain
        committed, resuming here would admit requests the commit never
        captured — silently lost at relaunch.

        Multi-host: broadcasts an abort marker so blocked
        :meth:`follow` ranks (waiting for the sync/tokens of the step
        that just died) free their cache mirrors too — without it the
        fleet's caches diverge and every later decode breaks the
        bitwise contract."""
        # The class threading contract, machine-checked (hvd-race):
        # under multiprocess only the serve-loop thread may call
        # abort_all; a stamped runtime thread of any other role
        # entering here raises ThreadRoleError.  Unstamped (user/main)
        # threads pass — single-process callers may treat abort_all
        # like the rest of the drain family.
        _athreads.require("serve-loop", "InferenceEngine.abort_all")
        # Broadcast OUTSIDE the lock: a wedged control plane blocks a
        # collective forever (no timeout), and holding _drain_lock
        # across it would deadlock the elastic thread's drain/import
        # too.  Under multiprocess only the serve-loop thread may call
        # abort_all (the class threading contract), so the marker
        # cannot interleave with a concurrent step()'s broadcasts — a
        # follower consuming an abort where it expected a plan/sync
        # would silently desynchronize the fleet's caches.
        if self._multiprocess():
            try:
                self._bcast({"abort": True})
            except Exception:  # noqa: BLE001 — a dead control
                pass  # plane must not stop the LOCAL recovery
        with self._drain_lock:
            drained, pending = self._drain_and_finish(
                FinishReason.ERROR)
            # The poisoned step may be the iteration in flight or an
            # admission's prefill: their outputs are no input for the
            # next launch.
            self._inflight = None
            self._fresh.clear()
            self._carry = None
            if not self._drained:
                self.scheduler.resume()
        return drained + pending

    def abort_request(self, req: Request,
                      reason: str = FinishReason.CLIENT_DISCONNECT
                      ) -> str:
        """Clean abort of ONE request (the /generate client vanished,
        hvd-chaos hardening): a queued request finishes immediately; an
        active one is marked and evicted by the serve loop at its next
        iteration boundary — the existing eviction path, so the KV slot
        is released identically on every rank.  Returns the scheduler's
        "queued"/"active"/"gone" disposition."""
        disposition = self.scheduler.cancel(req, reason)
        _flight.record("serve_abort_request", req.rid, reason,
                       disposition)
        return disposition

    def import_requests(self, exported: List[dict]) -> List[Request]:
        """Resubmit a drained export (relaunch path).  Continuation
        requests keep their already-generated prefix, so callers see
        uninterrupted results.  The whole resume+resubmit runs under
        the drain lock: a concurrent abort_all/drain landing mid-loop
        would otherwise make ``submit`` raise and silently drop the
        not-yet-resubmitted tail of the committed export.  A
        continuation this engine cannot admit (its prompt outgrew a
        SHRUNK capacity across the resize) is skipped with a flight-
        recorder event — one oversized request must not abort the loop
        and drop the rest of the committed export with it."""
        with self._drain_lock:
            if self._drained:
                self.scheduler.resume()
                self._drained = False
            out = []
            for d in exported:
                if d.get("max_new_tokens", 0) <= 0:
                    continue
                try:
                    out.append(self.submit(
                        d["prompt"], max_new_tokens=d["max_new_tokens"],
                        eos_id=d.get("eos_id"),
                        temperature=d.get("temperature", 0.0),
                        seed=d.get("seed", 0),
                        prefix=d.get("generated_prefix", [])))
                except ValueError as e:
                    _telemetry.exception_event(
                        "serve-import",
                        f"dropping unresumable continuation "
                        f"({len(d['prompt'])} prompt tokens vs "
                        f"capacity {self.capacity}): {e}")
        return out
