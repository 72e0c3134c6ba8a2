"""Inference serving: continuous batching over a paged KV cache
(docs/SERVING.md).

The production answer to "one ``Transformer.translate()`` call per
request": a fixed-slot engine whose hot loop is ONE compiled decode step
shared by ragged in-flight requests (paged KV cache + page tables, per
*Ragged Paged Attention*), a request queue with in-flight admission/
eviction between decode steps, lazy token readback at stream cadence
through the PR 4 ``InflightRing``, and ``serve_request`` SLO telemetry
on the PR 2 recorder.

The front door on top (PR 17): a multi-replica HTTP ``Router`` +
per-engine ``ReplicaServer`` (session affinity, least-outstanding
dispatch, drain/failover), a copy-on-write ``PrefixCache`` sharing
teacher-forced prefix KV pages across requests, real sampling
(temperature / top-k / top-p as traced device ops, seeded per-request
RNG), and speculative decoding (``NGramDraft`` proposes, ONE ragged
("verify", K) dispatch checks).
"""
from .paged_cache import (PagedKVCache, PagedStepCache, gather_pages,
                          page_coords, paged_attend, pages_for, write_page)
from .scheduler import (ContinuousBatchingScheduler, PrefixCache, Request,
                        TokenStream, prefix_key, queue_bound)
from .engine import (FullPrefixAdapter, ServingAdapter, ServingEngine,
                     TransformerAdapter)
from .speculative import DraftProposer, NGramDraft
from .router import (ReplicaServer, Router, discover_replicas,
                     serve_portfile_path)

__all__ = ["PagedKVCache", "PagedStepCache", "gather_pages", "page_coords",
           "paged_attend", "pages_for", "write_page",
           "ContinuousBatchingScheduler", "Request", "TokenStream",
           "queue_bound", "PrefixCache", "prefix_key",
           "ServingAdapter", "ServingEngine",
           "TransformerAdapter", "FullPrefixAdapter",
           "DraftProposer", "NGramDraft",
           "ReplicaServer", "Router", "discover_replicas",
           "serve_portfile_path"]
