"""Pipeline telemetry: per-stage counters/timings flushed into the
observability metrics registry (observability/metrics.py) so they show
up on the /minio/v2/metrics endpoints next to the S3/disk/heal series.

The registry is process-global and settable (the server wires its
Metrics instance at startup; bench and tests read the module-local
snapshot instead) because the hot paths construct pipelines deep inside
the erasure layer where no registry handle is plumbed. Recording is
coarse-grained — one flush per pipeline RUN — so telemetry never adds
per-item cost.
"""

from __future__ import annotations

import threading

_mu = threading.Lock()
_registry = None

# Module-local aggregate (survives without a registry): totals per
# (pipeline, stage) — what bench/tests read back cheaply.
_stage_totals: dict[tuple[str, str], dict] = {}
_pool_totals: dict[str, dict] = {}

# Descriptors contributed to observability/metrics_v2.DESCRIPTORS.
PIPELINE_DESCRIPTORS: list[tuple[str, str, str]] = [
    ("pipeline_runs_total", "counter", "Pipeline runs by pipeline"),
    ("pipeline_stage_items_total", "counter",
     "Items processed by pipeline stage"),
    ("pipeline_stage_bytes_total", "counter",
     "Bytes produced by pipeline stage"),
    ("pipeline_stage_busy_seconds_total", "counter",
     "Seconds spent inside the stage function"),
    ("pipeline_stage_wait_seconds_total", "counter",
     "Seconds the stage starved on its input queue"),
    ("pipeline_stage_stall_seconds_total", "counter",
     "Seconds the stage blocked on downstream backpressure"),
]


def set_registry(registry) -> None:
    """Install the process metrics registry (server startup)."""
    global _registry
    with _mu:
        _registry = registry


def get_registry():
    with _mu:
        return _registry


def record_run(pipeline_name: str, stages) -> None:
    """Flush one finished run's per-stage stats (executor calls this
    exactly once per run, success or cancellation)."""
    reg = get_registry()
    if reg is not None:
        reg.inc("pipeline_runs_total", pipeline=pipeline_name)
    with _mu:
        for st in stages:
            s = st.stats
            key = (pipeline_name, st.name)
            tot = _stage_totals.setdefault(key, {
                "items": 0, "bytes": 0, "busy_s": 0.0, "wait_s": 0.0,
                "stall_s": 0.0, "errors": 0, "runs": 0,
            })
            tot["items"] += s.items
            tot["bytes"] += s.bytes
            tot["busy_s"] += s.busy_s
            tot["wait_s"] += s.wait_s
            tot["stall_s"] += s.stall_s
            tot["errors"] += s.errors
            tot["runs"] += 1
    if reg is None:
        return
    for st in stages:
        s = st.stats
        labels = {"pipeline": pipeline_name, "stage": st.name}
        if s.items:
            reg.inc("pipeline_stage_items_total", s.items, **labels)
        if s.bytes:
            reg.inc("pipeline_stage_bytes_total", s.bytes, **labels)
        reg.inc("pipeline_stage_busy_seconds_total", s.busy_s, **labels)
        reg.inc("pipeline_stage_wait_seconds_total", s.wait_s, **labels)
        reg.inc("pipeline_stage_stall_seconds_total", s.stall_s, **labels)


def record_pool(pool) -> None:
    """Keep a BufferPool's counters (executor flushes per run)."""
    stats = pool.stats()
    with _mu:
        _pool_totals[pool.name] = stats


def stage_stats_snapshot(pipeline_name: str | None = None) -> dict:
    """Aggregated per-(pipeline, stage) totals since process start —
    keyed "pipeline/stage". Bench and tests read this; the metrics
    endpoint renders the registry copy."""
    with _mu:
        return {
            f"{p}/{s}": dict(v) for (p, s), v in _stage_totals.items()
            if pipeline_name is None or p == pipeline_name
        }


def pool_stats_snapshot() -> dict:
    with _mu:
        return {k: dict(v) for k, v in _pool_totals.items()}
