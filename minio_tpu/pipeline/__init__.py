"""Staged streaming pipeline: composable stages connected by bounded
queues with backpressure, a recycling buffer pool, a thread-per-stage
executor with first-error cancellation and deterministic draining, and
per-stage telemetry exported through the observability metrics registry.

This is the structural backbone of the erasure hot paths: PUT
(source-read ∥ md5 ∥ encode ∥ bitrot-frame ∥ shard-write), GET's
prefetching decode/bitrot-verify path, heal reconstruction, and the
device engine's double-buffered host feed (erasure/device_engine.HostFeed).
Stages that run back-to-back cap e2e PUT far below the encoder: once
the GF kernel is fast, pipeline structure, not the codec, dominates
throughput (arXiv:2108.02692); the same staged overlap discipline feeds
the TPU path.
"""

from .admission import AdmissionGovernor, client_context, governor
from .buffers import COPY, BufferPool, copy_add, shared_pool
from .executor import Pipeline, PipelineCancelled
from .metrics import (
    get_registry,
    pool_stats_snapshot,
    set_registry,
    stage_stats_snapshot,
)
from .stage import END_OF_STREAM, SKIP, Stage

__all__ = [
    "AdmissionGovernor",
    "BufferPool",
    "COPY",
    "client_context",
    "governor",
    "copy_add",
    "END_OF_STREAM",
    "Pipeline",
    "PipelineCancelled",
    "SKIP",
    "Stage",
    "get_registry",
    "pool_stats_snapshot",
    "set_registry",
    "shared_pool",
    "stage_stats_snapshot",
]
