"""Serving substrate: LM prefill/decode steps (serve_step), the TopoServe
batched persistence-diagram scheduler (topo_serve), the StreamServe
stateful dynamic-graph session layer (stream_serve), and the
SimilarityServe graph-similarity query path (similarity) — see
docs/ARCHITECTURE.md."""
from repro.serve.similarity import (
    SimilarityFuture,
    SimilarityResult,
    SimilarityServe,
)
from repro.serve.stream_serve import StreamFuture, StreamServe
from repro.serve.topo_serve import (
    DEFAULT_BUCKETS,
    SINGLE_PHASE_BUCKETS,
    Bucket,
    TopoFuture,
    TopoRequest,
    TopoServe,
    TopoServeConfig,
    pack_requests,
)

__all__ = [
    "Bucket",
    "DEFAULT_BUCKETS",
    "SINGLE_PHASE_BUCKETS",
    "SimilarityFuture",
    "SimilarityResult",
    "SimilarityServe",
    "StreamFuture",
    "StreamServe",
    "TopoFuture",
    "TopoRequest",
    "TopoServe",
    "TopoServeConfig",
    "pack_requests",
]
