"""Mesh discovery and (dp, lane) shape selection for the mesh serving
engine.

The mesh engine (parallel/mesh_engine.py) serves PUT/GET/heal only when
a usable device mesh exists AND the erasure geometry maps onto it: the
stripe's k+m shards shard over the 'lane' axis, so k+m must be
divisible by the lane dim. This module owns both decisions:

- **discovery** — how many local devices exist. A forced mesh
  (MTPU_ENCODE_ENGINE=mesh) asks jax outright; the 'auto' probe asks
  only in a process that already imported jax, so jax-free
  interpreters (the worker-pool children) stay jax-free.
- **shape selection** — MTPU_MESH_SHAPE="DPxLANE" pins the split
  (e.g. "2x4"); otherwise the largest power-of-two lane group that
  divides both the device count and k+m wins (lane-maximal: encode is
  embarrassingly lane-parallel, so wider lanes beat deeper dp until
  the geometry stops dividing).

Meshes are cached per shape — `jax.sharding.Mesh` is hashable and the
compiled-function caches key on it, so repeated selections of one shape
must return the identical object.
"""

from __future__ import annotations

import os
import sys
import threading

_mesh_lock = threading.Lock()
_mesh_cache: dict = {}


def device_count(initialize: bool = False) -> int:
    """Local device count. initialize=False (the 'auto' engine probe)
    answers 0 in a process that has not imported jax; initialize=True
    (the operator said MTPU_ENCODE_ENGINE=mesh) always asks jax."""
    if not initialize and "jax" not in sys.modules:
        return 0
    import jax

    return jax.local_device_count()


def backend_is_accelerator() -> bool:
    """True when the default backend is a real accelerator. The 'auto'
    policy only self-selects the mesh there: CPU virtual device meshes
    (tests, XLA_FLAGS force) add per-batch dispatch cost with no real
    parallel hardware, so they must opt in via
    MTPU_ENCODE_ENGINE=mesh."""
    import jax

    return jax.default_backend() != "cpu"


def parse_shape_env() -> tuple[int, int] | None:
    """MTPU_MESH_SHAPE="DPxLANE" -> (dp, lanes), or None when unset or
    malformed (malformed falls back to auto selection rather than
    killing the PUT path)."""
    raw = os.environ.get("MTPU_MESH_SHAPE", "")
    if not raw:
        return None
    try:
        dp_s, _, lane_s = raw.lower().partition("x")
        dp, lanes = int(dp_s), int(lane_s)
        if dp >= 1 and lanes >= 1:
            return dp, lanes
    except ValueError:
        pass
    return None


def lane_maximal(n_devices: int, total_shards: int) -> int:
    """The largest power-of-two lane dim dividing both the device count
    and k+m (1 when none fits). THE shape-fit rule: select_shape and
    the sweep enumerations (meshcheck.shapes_for) both derive from it,
    so the shapes proven by the sweep are exactly the shapes the
    serving engine can select."""
    lanes = 1
    while (lanes * 2 <= min(n_devices, total_shards)
           and n_devices % (lanes * 2) == 0
           and total_shards % (lanes * 2) == 0):
        lanes *= 2
    return lanes


def select_shape(total_shards: int,
                 n_devices: int | None = None) -> tuple[int, int] | None:
    """Pick the (dp, lanes) split for one erasure geometry, or None when
    no mesh shape fits (single device, or k+m shares no lane divisor
    with the device count).

    MTPU_MESH_SHAPE pins the shape; it is still validated (lanes must
    divide k+m, dp*lanes must not exceed the device count) so a stale
    env var degrades to auto selection instead of a crash."""
    if n_devices is None:
        n_devices = device_count(initialize=True)
    if n_devices < 2 or total_shards < 2:
        return None
    pinned = parse_shape_env()
    if pinned is not None:
        dp, lanes = pinned
        if (lanes >= 2 and total_shards % lanes == 0
                and dp * lanes <= n_devices):
            return dp, lanes
    # Lane-maximal power-of-two split that the geometry accepts.
    lanes = lane_maximal(n_devices, total_shards)
    if lanes < 2:
        return None
    return n_devices // lanes, lanes


def mesh_fit(total_shards: int | None, explicit: bool = False) -> bool:
    """Can this geometry serve on a mesh right now?  `explicit` means
    the operator forced MTPU_ENCODE_ENGINE=mesh: CPU virtual meshes
    count. The 'auto' probe (explicit=False) requires a multi-device
    accelerator backend — it never flips host-fed CPU deployments onto
    collective dispatch."""
    if not total_shards:
        return False
    n = device_count(initialize=explicit)
    if n < 2:
        return False
    if not explicit and not backend_is_accelerator():
        return False
    return select_shape(total_shards, n) is not None


def get_mesh(total_shards: int):
    """The cached Mesh for this geometry's active shape, or None.

    One Mesh object per (dp, lanes): ShardedErasure/MeshCodec caches and
    jit in_shardings key on Mesh identity, so handing out fresh ones
    would recompile per call."""
    shape = select_shape(total_shards)
    if shape is None:
        return None
    dp, lanes = shape
    with _mesh_lock:
        mesh = _mesh_cache.get((dp, lanes))
    if mesh is not None:
        return mesh
    from .sharded import make_mesh

    mesh = make_mesh(dp * lanes, lanes=lanes)
    with _mesh_lock:
        return _mesh_cache.setdefault((dp, lanes), mesh)
