"""Codec registry: the pluggable erasure-codec subsystem (ROADMAP item
1). Every codec is a CodecEntry declaring

- **identity** — a stable string id persisted per object in xl.meta
  (storage/fileinfo.ErasureInfo.codec, wire key "cid") plus the wire
  `algo` string, so decode/heal always reconstruct with the codec that
  encoded;
- **capability** — the matrix constructors (coding / parity /
  reconstruct), the host-side numpy realization, and the engine
  substrates the codec can serve on (native / device / mesh /
  worker-shm / numpy);
- **geometry** — a predicate over (k, m);
- **measured throughput** — a tiny min-of-N encode probe per host
  engine (device/mesh carry declared host-feed rate bounds: two
  constants, unmeasured on this attachment and ROADMAP S2's to
  replace).

Engine selection (`select_engine`) replaces the four-way if-chain that
used to live in erasure/codec.py: candidates are gated by availability
(native lib present, mesh fit, device-sized shards) intersected with
the entry's substrates, then ranked by throughput — measured for host
engines, the declared feed bound for device/mesh. `MTPU_ENCODE_ENGINE`
remains the forced override with the legacy fallback ladder (a forced
engine that is unavailable degrades to native, then numpy).

Codec selection (`select_codec`) picks the codec id a PUT stamps into
xl.meta: `MTPU_CODEC` forces one; `auto` keeps the dense incumbent
unless a challenger's measured encode beats it by the hysteresis margin
on that geometry (both ship the same native kernel today, so dense
stays the default and golden vectors are untouched).

This module must stay importable without jax: metrics_v2 imports
CODEC_DESCRIPTORS at catalog build, and the worker-pool children
resolve codec matrices through it in jax-free interpreters.
"""

from __future__ import annotations

import functools
import threading
import time
from dataclasses import dataclass, field
from typing import Callable

import numpy as np

from ..ops import cauchy, gf, regen

# Stable per-object codec identities — PERSISTED in xl.meta; renaming
# one orphans every object written under it.
DENSE_GF8 = "dense-gf8"
CAUCHY_XOR = "cauchy-xor"
# Regenerating codec (ops/regen.py): the roadmap's msr-pm id, served by
# the coupled-layer/piggyback constructions (see that module's honest
# naming note).
MSR_PM = "msr-pm"

# Default codec: what an absent "cid" field in pre-registry metadata
# means, and the auto-selection incumbent.
DEFAULT_CODEC = DENSE_GF8

# Below this shard size the fixed JAX dispatch cost dominates; stay on
# the host engines. Above it, device/mesh candidates become available.
DEVICE_SHARD_THRESHOLD = 4096

# A challenger codec must beat the incumbent's measured encode by this
# factor to win auto-selection — both current entries ride the same
# native kernel, so the margin keeps the default stable against
# measurement noise on a shared 1-core container.
AUTO_HYSTERESIS = 1.25

CODEC_DESCRIPTORS: list[tuple[str, str, str]] = [
    ("mtpu_codec_selected_total", "counter",
     "Codec selections at write time, labeled codec + geometry (k+m)"),
    ("mtpu_codec_dispatch_total", "counter",
     "Erasure batch dispatches, labeled codec + engine substrate"),
    ("codec_dispatch_kind_total", "counter",
     "The same dispatches by what they do, labeled engine + kind: "
     "encode (a PUT's fused batch), reconstruct (a fused rebuild of a "
     "heal or a batched degraded GET), apply (the unfused per-block "
     "call of codec._apply: a degraded GET's block, a tail block)"),
    ("bitrot_verified_bytes_total", "counter",
     "Shard bytes read from a drive and verified against their frame "
     "digests, labeled path (get/heal); counted by the bitrot readers "
     "after the verify passed and published when the read stream ends, "
     "so a read that went out unverified shows as a shortfall"),
    ("get_reconstructed_blocks_total", "counter",
     "Erasure blocks of GETs that had a data shard missing and were "
     "rebuilt from parity before they were written to the client"),
    ("get_mrf_queued_total", "counter",
     "GETs whose read saw a missing or corrupt shard and queued an "
     "MRF heal of their object"),
    ("codec_trace_total", "counter",
     "Traces of a fused device function (jax.jit building a new one), "
     "labeled codec + engine; the mesh engine counts its own under "
     "mesh_retraces_total"),
    ("mtpu_codec_probe_gbps", "gauge",
     "Measured codec probe throughput (GB/s), labeled codec + engine"),
    ("backend_info", "gauge",
     "JAX backend the device/mesh engines dispatch to, labeled "
     "platform + device_kind + devices"),
]

# what a dispatch does: the `kind` label of codec_dispatch_kind_total
DISPATCH_KINDS = ("encode", "reconstruct", "apply")

_metrics = None  # guarded-by: _metrics_mu
_metrics_mu = threading.Lock()


def set_metrics(registry) -> None:
    global _metrics
    with _metrics_mu:
        _metrics = registry
    if registry is not None:
        # present at 0 from the start: a scrape that finds the series
        # reads "none rebuilt", one that finds none reads nothing
        for engine in _FORCED_ENGINES:
            if engine == "auto":
                continue
            for kind in DISPATCH_KINDS:
                registry.inc("codec_dispatch_kind_total", 0,
                             engine=engine, kind=kind)
        for path in ("get", "heal"):
            registry.inc("bitrot_verified_bytes_total", 0, path=path)
        registry.inc("get_reconstructed_blocks_total", 0)
        registry.inc("get_mrf_queued_total", 0)


def _reg():
    with _metrics_mu:
        return _metrics


@dataclass(frozen=True)
class CodecEntry:
    """One registered codec: identity + capabilities + matrix algebra +
    host realization + throughput model. Matrix constructors return the
    same shapes as the ops/gf dense helpers ((k+m, k) full, (m, k)
    parity, (targets, k) reconstruct) so every engine substrate consumes
    any registered codec through the existing any-matrix kernels."""

    codec_id: str
    wire_algorithm: str
    substrates: frozenset[str]
    coding_matrix: Callable[[int, int], np.ndarray]
    parity_matrix: Callable[[int, int], np.ndarray]
    reconstruct_matrix: Callable[[int, int, list, list], np.ndarray]
    # Host numpy realization: (byte matrix [R, K], shards [K, S]) ->
    # [R, S]. The no-native fallback AND the byte oracle per codec.
    host_apply: Callable[[np.ndarray, np.ndarray], np.ndarray]
    # Declared host-feed throughput bounds (GB/s) for engines whose
    # kernel rate is not the binding constraint on host-sourced streams.
    feed_bounds: dict = field(default_factory=dict)
    # Optional schedule accounting (XOR-schedule codecs) for bench/probe.
    schedule_stats: Callable[[np.ndarray], dict] | None = None
    max_shards: int = gf.MAX_SHARDS
    # Sub-packetization α(k, m): shard byte-lengths must be multiples of
    # it and the matrix constructors address sub-shards (codecs whose
    # matrices are expanded ×α). None == 1 == plain shard granularity.
    subshards: Callable[[int, int], int] | None = None
    # Bandwidth-optimal repair capability: (k, m, target) -> RepairPlan
    # (ops/regen.RepairPlan) or None when the target has no β-plan.
    repair_plan: Callable[[int, int, int], object] | None = None
    # Declared mean bytes READ per byte healed for a 1-shard repair
    # (dense RS reads k) — what heal-heavy auto-selection ranks by.
    repair_read_fraction: Callable[[int, int], float] | None = None
    # Extra geometry predicate beyond the max_shards envelope (codecs
    # with construction constraints, e.g. sub-packetization caps).
    geometry: Callable[[int, int], bool] | None = None

    def geometry_ok(self, data_blocks: int, parity_blocks: int) -> bool:
        if not (data_blocks > 0 and parity_blocks > 0
                and data_blocks + parity_blocks <= self.max_shards):
            return False
        if self.geometry is not None:
            return bool(self.geometry(data_blocks, parity_blocks))
        return True

    def alpha(self, data_blocks: int, parity_blocks: int) -> int:
        if self.subshards is None:
            return 1
        return int(self.subshards(data_blocks, parity_blocks))

    def declared_repair_fraction(self, data_blocks: int,
                                 parity_blocks: int) -> float:
        """Bytes read per byte healed for a single-shard repair — the
        dense k-survivor cost unless the codec declares better."""
        if self.repair_read_fraction is None:
            return float(data_blocks)
        return float(self.repair_read_fraction(data_blocks, parity_blocks))


def _dense_host_apply(mat: np.ndarray, shards: np.ndarray) -> np.ndarray:
    from ..ops import rs

    return rs.gf_matmul_shards_np(gf.bit_matrix_for(mat), shards)


def _cauchy_host_apply(mat: np.ndarray, shards: np.ndarray) -> np.ndarray:
    if np.asarray(shards).ndim == 3:
        return cauchy.apply_schedule_batch(mat, shards)
    return cauchy.apply_schedule(mat, shards)


def _dense_reconstruct(k: int, m: int, present, targets) -> np.ndarray:
    return gf.reconstruct_matrix(k, m, list(present), list(targets))


def _cauchy_reconstruct(k: int, m: int, present, targets) -> np.ndarray:
    return cauchy.cauchy_reconstruct_matrix(
        k, m, list(present), list(targets)
    )


_ALL_SUBSTRATES = frozenset(
    {"native", "device", "mesh", "worker", "numpy"}
)

_REGISTRY: dict[str, CodecEntry] = {}


def register(entry: CodecEntry) -> CodecEntry:
    if entry.codec_id in _REGISTRY:
        raise ValueError(f"codec {entry.codec_id!r} already registered")
    _REGISTRY[entry.codec_id] = entry
    return entry


register(CodecEntry(
    codec_id=DENSE_GF8,
    # Matches storage/fileinfo.ERASURE_ALGORITHM — the algo string every
    # pre-registry object carries.
    wire_algorithm="rs-vandermonde",
    substrates=_ALL_SUBSTRATES,
    coding_matrix=gf.rs_matrix,
    parity_matrix=gf.parity_matrix,
    reconstruct_matrix=_dense_reconstruct,
    host_apply=_dense_host_apply,
    feed_bounds={"mesh": 0.60, "device": 0.50},
))

register(CodecEntry(
    codec_id=CAUCHY_XOR,
    wire_algorithm="rs-cauchy-xor",
    substrates=_ALL_SUBSTRATES,
    coding_matrix=cauchy.cauchy_matrix,
    parity_matrix=cauchy.cauchy_parity_matrix,
    reconstruct_matrix=_cauchy_reconstruct,
    host_apply=_cauchy_host_apply,
    feed_bounds={"mesh": 0.60, "device": 0.50},
    schedule_stats=cauchy.schedule_stats,
))

register(CodecEntry(
    codec_id=MSR_PM,
    wire_algorithm="rs-msr-pm",
    # Host substrates only: the expanded sub-shard matrices ride the
    # native any-matrix kernel (or the numpy bit-matmul oracle); the
    # worker-pool children and device/mesh engines do not carry the
    # sub-shard reshape, and repair-bandwidth heal needs host-side
    # β-slice reads anyway.
    substrates=frozenset({"native", "numpy"}),
    coding_matrix=regen.coding_matrix,
    parity_matrix=regen.parity_matrix,
    reconstruct_matrix=regen.reconstruct_matrix,
    host_apply=_dense_host_apply,
    subshards=regen.subshards,
    repair_plan=regen.repair_plan,
    repair_read_fraction=regen.repair_read_fraction,
    geometry=regen.geometry_ok,
))


def codec_ids() -> tuple[str, ...]:
    return tuple(_REGISTRY)


def get(codec_id: str) -> CodecEntry:
    """Resolve a codec id — LOUD on unknown ids: an object stamped with
    a codec this build does not know must never silently decode dense."""
    entry = _REGISTRY.get(codec_id)
    if entry is None:
        raise KeyError(
            f"unknown erasure codec {codec_id!r} "
            f"(registered: {', '.join(_REGISTRY)})"
        )
    return entry


def wire_algorithm_to_codec(algorithm: str) -> str | None:
    """Codec id for a wire `algo` string, or None when no registered
    codec claims it (the metadata layer fails loud on those)."""
    for entry in _REGISTRY.values():
        if entry.wire_algorithm == algorithm:
            return entry.codec_id
    return None


def supports(codec_id: str, substrate: str) -> bool:
    return substrate in get(codec_id).substrates


# --- measured-throughput probes ---------------------------------------

_PROBE_SHARD = 16384
_PROBE_GEOMETRY = (4, 2)
_PROBE_RUNS = 3
# A fast call is sampled at least this long: three readings of a 25 µs
# native call rank how cold the caches were for whichever codec was
# probed first (1.6x apart in fresh processes), not the codecs.
_PROBE_MIN_S = 0.005


def _measure(fn, nbytes: int, runs: int = _PROBE_RUNS) -> float:
    """Best-of-N wall-clock GB/s for one probe callable (min time, the
    same dispersion-resistant protocol bench.py uses); N is at least
    `runs` and as many as fit in _PROBE_MIN_S."""
    fn()  # warm caches (matrix derivations, kernel tables)
    best = float("inf")
    done = 0
    until = time.perf_counter() + _PROBE_MIN_S
    while done < runs or time.perf_counter() < until:
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
        done += 1
    if best <= 0:
        return 0.0
    return nbytes / best / 1e9


@functools.lru_cache(maxsize=32)
def probe_gbps(codec_id: str, engine: str) -> float:
    """Measured encode throughput of one (codec, host engine) pair on a
    tiny canonical geometry; lru-cached — the probe runs once per
    process. Device/mesh rates are declared feed bounds, not probed (a
    probe would drag jax into every selection path)."""
    entry = get(codec_id)
    if engine in entry.feed_bounds:
        value = float(entry.feed_bounds[engine])
        _note_probe(codec_id, engine, value)
        return value
    k, m = _PROBE_GEOMETRY
    mat = entry.parity_matrix(k, m)
    alpha = entry.alpha(k, m)
    rng = np.random.default_rng(0x5EED)
    blocks = rng.integers(0, 256, size=(2, k * alpha,
                                        _PROBE_SHARD // alpha),
                          dtype=np.uint8)
    nbytes = blocks.nbytes
    if engine == "native":
        from ..ops import gf_native

        if not gf_native.available():
            return 0.0
        value = _measure(
            lambda: gf_native.apply_matrix_batch(mat, blocks), nbytes
        )
    elif engine == "numpy":
        shards = blocks[0]
        value = _measure(
            lambda: entry.host_apply(mat, shards), shards.nbytes
        )
    else:
        return 0.0
    _note_probe(codec_id, engine, value)
    return value


def _note_probe(codec_id: str, engine: str, gbps: float) -> None:
    reg = _reg()
    if reg is not None:
        reg.set_gauge("mtpu_codec_probe_gbps", round(gbps, 3),
                      codec=codec_id, engine=engine)


@functools.lru_cache(maxsize=32)
def probe_geometry_gbps(codec_id: str, data_blocks: int,
                        parity_blocks: int) -> float:
    """Measured encode throughput of one codec on one geometry through
    its best available host engine — the number codec auto-selection
    compares."""
    entry = get(codec_id)
    mat = entry.parity_matrix(data_blocks, parity_blocks)
    alpha = entry.alpha(data_blocks, parity_blocks)
    rng = np.random.default_rng(0x5EED)
    blocks = rng.integers(
        0, 256,
        size=(2, data_blocks * alpha, _PROBE_SHARD // alpha),
        dtype=np.uint8,
    )
    from ..ops import gf_native

    if gf_native.available() and "native" in entry.substrates:
        return _measure(
            lambda: gf_native.apply_matrix_batch(mat, blocks),
            blocks.nbytes,
        )
    shards = blocks[0]
    return _measure(lambda: entry.host_apply(mat, shards), shards.nbytes)


# --- engine selection --------------------------------------------------

_FORCED_ENGINES = ("auto", "device", "mesh", "native", "numpy")


def select_engine(shard_len: int, total_shards: int | None = None,
                  codec_id: str = DEFAULT_CODEC) -> str:
    """Pick the GF engine for one application:
    'native' | 'device' | 'mesh' | 'numpy'.

    MTPU_ENCODE_ENGINE forces it (auto|device|mesh|native|numpy); a
    forced engine that is unavailable for this call degrades down the
    host ladder (native, then numpy) exactly as the pre-registry policy
    did. 'auto' ranks the available candidates by throughput: measured
    probes for the host engines, the codec's declared host-feed bounds
    for device/mesh.

    The mesh candidate exists only when the caller names the geometry
    (`total_shards`) and placement.mesh_fit accepts it — forced mesh
    admits virtual CPU meshes (the CI path), auto only real multi-device
    accelerator backends. The env/mesh probes are re-read per call
    (tests flip them); the resolution itself is memoized.

    THE one place the device and mesh engines are resolved: a 'device'
    or 'mesh' answer has read jax.devices() (utils/jaxenv.backend), so
    it names its backend on the metrics endpoint and raises instead of
    serving from a CPU that JAX fell back to.
    """
    import os

    from ..ops import gf_native

    eng = os.environ.get("MTPU_ENCODE_ENGINE", "auto")
    if eng == "mesh" or (eng == "auto" and total_shards):
        from ..parallel import placement

        mesh_fit = placement.mesh_fit(total_shards, explicit=eng == "mesh")
    else:
        mesh_fit = False
    engine = _resolve_engine(
        eng,
        shard_len >= DEVICE_SHARD_THRESHOLD,
        gf_native.available(),
        mesh_fit,
        codec_id,
    )
    if engine in ("device", "mesh"):
        _announce_backend()
    return engine


@functools.lru_cache(maxsize=64)
def _resolve_engine(eng: str, device_sized: bool, native_ok: bool,
                    mesh_fit: bool, codec_id: str) -> str:
    entry = get(codec_id)
    available = {
        "native": native_ok and "native" in entry.substrates,
        "mesh": (mesh_fit and device_sized
                 and "mesh" in entry.substrates),
        "device": device_sized and "device" in entry.substrates,
        "numpy": "numpy" in entry.substrates,
    }
    if eng != "auto" and eng in _FORCED_ENGINES:
        if available.get(eng):
            return eng
        return "native" if available["native"] else "numpy"
    ranked = sorted(
        (name for name, ok in available.items() if ok),
        key=lambda name: _engine_rank(codec_id, name),
        reverse=True,
    )
    return ranked[0] if ranked else "numpy"


def forced_engine_backend():
    """The backend behind a forced device/mesh engine
    (MTPU_ENCODE_ENGINE), read NOW, or None when no accelerator engine
    is forced. The server calls this at boot: its banner names the
    backend, and a missing chip stops it before it accepts a request
    instead of at the first large PUT."""
    import os

    if os.environ.get("MTPU_ENCODE_ENGINE", "auto") in ("device", "mesh"):
        return _announce_backend()
    return None


def _announce_backend():
    """Read the backend the device/mesh engines dispatch to (cached per
    process; raises on a CPU nobody asked for) and publish its identity
    on the metrics endpoint."""
    from ..utils import jaxenv

    found = jaxenv.backend()
    reg = _reg()
    if reg is not None:
        reg.set_gauge("backend_info", 1, platform=found.platform,
                      device_kind=found.device_kind,
                      devices=str(found.count))
    return found


def _engine_rank(codec_id: str, engine: str) -> tuple:
    """(throughput GB/s, stable tiebreak) — measured for host engines,
    declared feed bound for device/mesh. The tiebreak pins the order
    when two engines measure identically (mesh outranks device: it
    subsumes the single-chip path when both fit)."""
    tiebreak = {"native": 3, "mesh": 2, "device": 1, "numpy": 0}
    return (probe_gbps(codec_id, engine), tiebreak[engine])


# --- codec selection ---------------------------------------------------

# Selection profiles: "throughput" (default) ranks auto-candidates by
# measured encode rate; "heal-heavy" ranks by the entry's declared
# repair-read fraction (bytes read per byte healed — exact, derived
# from the codec's verified repair plans), encode rate as tiebreak.
_CODEC_PROFILES = ("throughput", "heal-heavy")


def _codec_profile() -> str:
    import os

    # MTPU_CODEC_PROFILE: "throughput" | "heal-heavy" (call-site
    # default "throughput"); re-read per selection so operators can
    # repoint a running server's storage class.
    prof = os.environ.get("MTPU_CODEC_PROFILE", "throughput")
    return prof if prof in _CODEC_PROFILES else "throughput"


def select_codec(data_blocks: int, parity_blocks: int,
                 forced: str = "") -> str:
    """Codec id a write should stamp for this geometry. Precedence:
    `forced` (per-request, e.g. the x-mtpu-codec header) > MTPU_CODEC
    env (a codec id, or 'auto' — the documented default) > auto-
    selection with the dense incumbent favored by AUTO_HYSTERESIS.
    Under MTPU_CODEC_PROFILE=heal-heavy the auto rank flips from
    measured encode rate to declared repair bandwidth (a challenger
    must cut bytes-read-per-byte-healed by the same hysteresis factor
    to displace the incumbent — deterministic, so no flapping).
    Unknown forced ids raise KeyError (the API layer maps it to
    InvalidArgument); geometry misfits raise ValueError."""
    import os

    want = forced or os.environ.get("MTPU_CODEC", "auto")
    if want and want != "auto":
        entry = get(want)
        if not entry.geometry_ok(data_blocks, parity_blocks):
            raise ValueError(
                f"codec {want!r} does not support geometry "
                f"{data_blocks}+{parity_blocks}"
            )
        chosen = entry.codec_id
    else:
        chosen = _auto_codec(data_blocks, parity_blocks, _codec_profile())
    reg = _reg()
    if reg is not None:
        reg.inc("mtpu_codec_selected_total", codec=chosen,
                geometry=f"{data_blocks}+{parity_blocks}")
    return chosen


@functools.lru_cache(maxsize=64)
def _auto_codec(data_blocks: int, parity_blocks: int,
                profile: str = "throughput") -> str:
    incumbent = DEFAULT_CODEC
    if not get(incumbent).geometry_ok(data_blocks, parity_blocks):
        for cid, entry in _REGISTRY.items():
            if entry.geometry_ok(data_blocks, parity_blocks):
                return cid
        return incumbent
    if profile == "heal-heavy":
        return _auto_codec_heal_heavy(data_blocks, parity_blocks)
    best, best_gbps = incumbent, probe_geometry_gbps(
        incumbent, data_blocks, parity_blocks
    )
    floor = best_gbps * AUTO_HYSTERESIS
    for cid, entry in _REGISTRY.items():
        if cid == incumbent:
            continue
        if not entry.geometry_ok(data_blocks, parity_blocks):
            continue
        gbps = probe_geometry_gbps(cid, data_blocks, parity_blocks)
        if gbps > floor and gbps > best_gbps:
            best, best_gbps = cid, gbps
    return best


def _auto_codec_heal_heavy(data_blocks: int, parity_blocks: int) -> str:
    """Heal-heavy rank: a challenger displaces the incumbent only when
    its declared repair-read fraction (from its verified repair plans)
    beats the incumbent's by AUTO_HYSTERESIS — declared fractions are
    deterministic per geometry, so the pick cannot flap with probe
    noise. Measured encode rate breaks fraction ties."""
    incumbent = DEFAULT_CODEC
    best = incumbent
    best_frac = get(incumbent).declared_repair_fraction(
        data_blocks, parity_blocks
    )
    ceiling = best_frac / AUTO_HYSTERESIS
    for cid, entry in _REGISTRY.items():
        if cid == incumbent:
            continue
        if not entry.geometry_ok(data_blocks, parity_blocks):
            continue
        frac = entry.declared_repair_fraction(data_blocks, parity_blocks)
        if frac >= ceiling:
            continue
        if frac < best_frac or (
            frac == best_frac
            and probe_geometry_gbps(cid, data_blocks, parity_blocks)
            > probe_geometry_gbps(best, data_blocks, parity_blocks)
        ):
            best, best_frac = cid, frac
    return best


def note_trace(codec_id: str, engine: str) -> None:
    """One trace of a fused device function: runs inside the traced
    Python, so once per function jax.jit builds and never per call."""
    reg = _reg()
    if reg is not None:
        reg.inc("codec_trace_total", codec=codec_id, engine=engine)


def note_dispatch(codec_id: str, engine: str, kind: str) -> None:
    """Per-batch dispatch accounting (codec x engine substrate) — wired
    from the codec core's engine dispatch points. `kind` (one of
    DISPATCH_KINDS) says what the dispatch does, on a series of its own,
    so a GET's per-block calls read apart from a PUT's fused batches."""
    reg = _reg()
    if reg is not None:
        reg.inc("mtpu_codec_dispatch_total", codec=codec_id,
                engine=engine)
        reg.inc("codec_dispatch_kind_total", engine=engine, kind=kind)


def note_read(name: str, n: float = 1, **labels) -> None:
    """Read-side accounting (bitrot_verified_bytes_total,
    get_reconstructed_blocks_total, get_mrf_queued_total): raised once
    a stream by decode_stream / heal_stream and by the object layer."""
    reg = _reg()
    if reg is not None:
        reg.inc(name, n, **labels)
