"""Continuous-batching inference serving on the predictor path.

Reference: the C predict API (SURVEY §3.5) is the reference's serving
surface; this package is the server ON TOP of it — the north star's
"heavy traffic from millions of users" entry point.  Architecture
(docs/serving.md):

    clients → RequestQueue (bounded; full → ServerOverloadedError)
            → scheduler thread: group by length bucket, pad to the
              power-of-two (batch, length) grid     [bucketing.py]
            → Predictor / gluon block / llama decode engine
            → demux to per-request Futures + telemetry records

Stateless models get dynamic batching (:class:`InferenceServer`);
llama decode gets TRUE continuous batching (:class:`GenerativeServer`):
requests are admitted into free decode slots and evicted on completion
BETWEEN decode steps, so a late request joins an in-flight batch
without restarting anyone.  Since r11 the generative path is
mesh-native and disaggregated: ``GenerativeServer(net, mesh=...)``
places weights tensor-parallel (a ``dp`` axis → independent replicas
behind one queue, least-loaded routed), K/V lives in a paged block
pool (``kv_cache.PagedKVCacheManager`` — capacity bounded by tokens in
flight, not ``max_len × slots``), and prefill/decode run as separate
lanes with explicit KV handoff (``lanes.py``).

Observability (r12, docs/observability.md): every request can carry a
span context (``telemetry.tracing``) yielding one connected trace per
request across the queue → prefill → handoff → decode thread hops;
``ServerConfig(http_port=0)`` starts a live stdlib-HTTP endpoint
(``metrics.MetricsServer``) exposing ``/metrics`` (Prometheus text),
``/healthz`` (lane liveness + KV occupancy) and ``/requests``; and
``ServerConfig(slo={...})`` turns on per-tenant TTFT/TPOT goodput
accounting (``metrics.SLOTracker``).

Speed multipliers (r19): ``ServerConfig(draft_net=...)``
turns on greedy speculative decoding — a small draft llama proposes
``spec_k`` tokens per slot, the target scores the whole window in ONE
batched multi-position forward, and rejected suffixes roll back via
``PagedKVCacheManager.truncate`` (token-exact vs. plain decode by
construction).  ``ServerConfig(radix_cache=True)`` adds the radix
prefix cache (``radix.RadixPrefixCache``): block-aligned prompt
prefixes map to refcounted paged blocks, so requests sharing a system
prompt prefill only their novel suffix.

Quick start::

    from mxnet_tpu import serving

    srv = serving.InferenceServer(predictor,
                                  serving.ServerConfig(max_batch=8))
    with srv:
        out = srv.infer(x)          # sync
        fut = srv.submit(x2)        # async -> concurrent.futures.Future
        out2 = fut.result()
"""
from .protocol import (Request, ServerClosedError,     # noqa: F401
                       ServerOverloadedError)
from .bucketing import BucketPolicy, pad_batch, pow2_bucket  # noqa: F401
from .kv_cache import BlockAllocator, PagedKVCacheManager  # noqa: F401
from .radix import RadixPrefixCache                    # noqa: F401
from .scheduler import BatchScheduler, RequestQueue    # noqa: F401
from .lanes import (DecodeLane, PrefillLane, Replica,  # noqa: F401
                    ReplicaDispatcher)
from .server import (GenerativeServer, InferenceServer,  # noqa: F401
                     ServerConfig)
from .metrics import (MetricsServer, SLOTracker,       # noqa: F401
                      prometheus_text)

__all__ = ["Request", "ServerOverloadedError", "ServerClosedError",
           "BucketPolicy", "pow2_bucket", "pad_batch",
           "PagedKVCacheManager", "BlockAllocator", "RadixPrefixCache",
           "RequestQueue", "BatchScheduler", "ServerConfig",
           "InferenceServer", "GenerativeServer",
           "PrefillLane", "DecodeLane", "Replica", "ReplicaDispatcher",
           "MetricsServer", "SLOTracker", "prometheus_text"]
