"""hvd-serve: continuous-batching inference over the training mesh.

The serving runtime the north star's "heavy traffic from millions of
users" scenario needs (ROADMAP open item 4; docs/inference.md).  Five
pieces, each its own module:

* :mod:`~horovod_tpu.serving.scheduler` — request queue + iteration-
  level continuous-batching scheduler: a new request joins the decode
  batch the moment a slot frees, a finished sequence evicts
  immediately; no batch-boundary barrier.  Pure Python — unit-testable
  without XLA.
* :mod:`~horovod_tpu.serving.kv_cache` — paged KV cache: fixed-size
  pages recycled through a free list, head axis sharded with the
  ``parallel/tensor.py`` tensor-parallel layout so serving reuses the
  training partition.
* :mod:`~horovod_tpu.serving.models` — what the engine asks of a
  model (cache entry, paged decode step, what that step attends of the
  store, prefill step, fingerprint), and the dense multi-head decoder's
  answers.
* :mod:`~horovod_tpu.serving.engine` — prefill and decode compiled as
  donated AOT executables (megakernel-style: gather → forward →
  scatter in ONE program), recorded in the PR-5 persistent-cache
  manifest so :meth:`InferenceEngine.warm_start` brings a relaunched
  serving fleet back to full token rate before the first request.
* :mod:`~horovod_tpu.serving.server` — the HTTP front door: ``/generate``
  registered on the telemetry exporter's route registry, ``/healthz``
  NOT_READY until warm start completes (the load-balancer contract).

Elastic integration rides :class:`horovod_tpu.elastic.ServingState`:
drain in-flight sequences, commit the queue, relaunch, resume from the
warm cache.
"""

from __future__ import annotations

from .scheduler import (  # noqa: F401
    ContinuousBatchingScheduler,
    FinishReason,
    Request,
)
from .kv_cache import PagedKVCache  # noqa: F401
from .engine import InferenceEngine  # noqa: F401
from .server import LMServer  # noqa: F401
