"""Per-lane telemetry for the mesh serving engine.

Same two-tier pattern as erasure/streaming and pipeline/metrics: module
counters ALWAYS tick (tests and the STATS guards read them directly, no
registry required), and a registry handle installed at server boot
mirrors them onto the /minio/v2/metrics endpoints.

The counters answer the three operational questions DEPLOYMENT.md's
"Mesh engine" section teaches operators to ask:

- is the fused-dispatch invariant holding?  dispatches_per_batch =
  mesh_dispatches_total / mesh_batches_total must stay 1.0 and
  mesh_retraces_total must stay flat in steady state (a climb means
  geometry/batch-shape churn is recompiling the pjit program);
- how busy are the lanes?  mesh_lane_shard_bytes_total{lane=i} is the
  shard bytes each lane column owned — equal across lanes when the
  geometry divides evenly (mesh_lane_utilization gauge = n_shards /
  (lanes * ceil(n_shards/lanes)));
- what does the collective plane cost?  mesh_collective_bytes_total
  estimates the bytes crossing the lane axis per dispatch (data
  scatter + parity/digest gather), the ICI/DCN budget of SURVEY §5.7.

This module must stay importable WITHOUT jax (metrics_v2 pulls the
descriptor list at server boot; backend init is the mesh engine's
decision, never the metrics plane's).
"""

from __future__ import annotations

import threading

MESH_DESCRIPTORS: list[tuple[str, str, str]] = [
    ("mesh_dispatches_total", "counter",
     "Fused mesh collective dispatches (one per batch when healthy)"),
    ("mesh_batches_total", "counter",
     "dp-group batches shipped through the mesh engine"),
    ("mesh_blocks_total", "counter",
     "Erasure blocks encoded/reconstructed on the mesh"),
    ("mesh_padded_blocks_total", "counter",
     "Rows of zero padding staged beside them (ragged host batches)"),
    ("mesh_retraces_total", "counter",
     "XLA (re)traces of mesh programs — flat in steady state"),
    ("mesh_collective_bytes_total", "counter",
     "Estimated bytes crossing the lane axis (scatter + gather)"),
    ("mesh_lane_shard_bytes_total", "counter",
     "Shard bytes owned per lane column (label: lane)"),
    ("mesh_lanes", "gauge", "Lane dim of the active mesh shape"),
    ("mesh_dp", "gauge", "dp dim of the active mesh shape"),
    ("mesh_lane_utilization", "gauge",
     "Shard balance across lanes: 1.0 when k+m divides evenly"),
    ("mesh_output_devices", "gauge",
     "Devices holding the last mesh dispatch's output array"),
]

STATS = {
    "mesh_dispatches_total": 0,
    "mesh_batches_total": 0,
    "mesh_blocks_total": 0,
    "mesh_padded_blocks_total": 0,
    "mesh_retraces_total": 0,
    "mesh_collective_bytes_total": 0,
}

_lane_bytes: dict[int, int] = {}
_stats_lock = threading.Lock()
_metrics = None


def set_metrics(registry) -> None:
    global _metrics
    _metrics = registry


def record(name: str, n: int = 1) -> None:
    with _stats_lock:
        STATS[name] += n
    if _metrics is not None:
        _metrics.inc(name, n)


def record_lane_bytes(lane: int, n: int) -> None:
    with _stats_lock:
        _lane_bytes[lane] = _lane_bytes.get(lane, 0) + n
    if _metrics is not None:
        _metrics.inc("mesh_lane_shard_bytes_total", n, lane=str(lane))


def record_shape(dp: int, lanes: int, n_shards: int) -> None:
    """Gauge the active mesh shape + lane balance (called when a codec
    binds a mesh — the most recent geometry wins, like the reference's
    per-pool gauges)."""
    if _metrics is not None:
        _metrics.set_gauge("mesh_dp", dp)
        _metrics.set_gauge("mesh_lanes", lanes)
        per_lane = -(-n_shards // lanes)  # ceil
        _metrics.set_gauge("mesh_lane_utilization",
                           n_shards / (lanes * per_lane))


def record_output_devices(n: int) -> None:
    """Devices holding the last dispatch's output, read from the array's
    own sharding: where the program ran, which the lane-byte counters
    (host arithmetic over shapes) cannot say."""
    if _metrics is not None:
        _metrics.set_gauge("mesh_output_devices", n)


def stats_snapshot() -> dict:
    with _stats_lock:
        out = dict(STATS)
        out["lane_bytes"] = dict(_lane_bytes)
    return out


def reset_stats() -> None:
    with _stats_lock:
        for k in STATS:
            STATS[k] = 0
        _lane_bytes.clear()
