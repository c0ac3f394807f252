"""Codec registry unit tests + the xl.meta back-compat regression gate.

Registry half: identity/capability lookups, loud failure on unknown
ids, selection precedence (forced > MTPU_CODEC env > auto), the
preserved MTPU_ENCODE_ENGINE forced-override-with-fallback-ladder
semantics, probes, and the metrics wiring.

Back-compat half (ISSUE 16 satellite): pre-registry metadata — no
"cid" key, legacy rs-vandermonde algo — must decode to the dense
default unchanged, end-to-end through a real object set whose on-disk
xl.meta has been rewritten to the pre-registry shape. And a
registry-written non-dense object must fail LOUD on any reader that
lost the codec field, never silently misdecode dense: the wire algo
string is the tripwire, and a verbatim frozen copy of the pre-registry
from_dict demonstrates what the old reader would have produced so the
new strict path can be shown to reject exactly that shape.
"""

import io
import os

import numpy as np
import pytest

from minio_tpu.erasure import registry
from minio_tpu.storage.fileinfo import (
    ERASURE_ALGORITHM,
    ChecksumInfo,
    ErasureInfo,
    FileInfo,
)

from test_object_layer import make_pools


# --- identity / capability --------------------------------------------

def test_codec_ids_and_loud_get():
    ids = registry.codec_ids()
    assert registry.DENSE_GF8 in ids
    assert registry.CAUCHY_XOR in ids
    assert registry.DEFAULT_CODEC == registry.DENSE_GF8
    with pytest.raises(KeyError, match="unknown erasure codec"):
        registry.get("rs-lrc-imaginary")


def test_wire_algorithm_mapping():
    assert registry.wire_algorithm_to_codec("rs-vandermonde") \
        == registry.DENSE_GF8
    assert registry.wire_algorithm_to_codec("rs-cauchy-xor") \
        == registry.CAUCHY_XOR
    assert registry.wire_algorithm_to_codec("not-a-wire-algo") is None
    # The dense entry's wire algo IS the legacy constant — that identity
    # is what makes absent-cid metadata resolvable.
    assert registry.get(registry.DENSE_GF8).wire_algorithm \
        == ERASURE_ALGORITHM


def test_duplicate_registration_rejected():
    entry = registry.get(registry.DENSE_GF8)
    with pytest.raises(ValueError, match="already registered"):
        registry.register(entry)


def test_supports_and_geometry():
    for cid in (registry.DENSE_GF8, registry.CAUCHY_XOR):
        for sub in ("native", "device", "mesh", "worker", "numpy"):
            assert registry.supports(cid, sub)
        entry = registry.get(cid)
        assert entry.geometry_ok(12, 4)
        assert not entry.geometry_ok(0, 4)
        assert not entry.geometry_ok(12, 0)
        assert not entry.geometry_ok(entry.max_shards, 1)


# --- codec selection precedence ---------------------------------------

def test_select_codec_precedence(monkeypatch):
    # auto (no env, no forced) -> dense incumbent.
    monkeypatch.delenv("MTPU_CODEC", raising=False)
    assert registry.select_codec(4, 2) == registry.DENSE_GF8
    # env forces a codec id.
    monkeypatch.setenv("MTPU_CODEC", registry.CAUCHY_XOR)
    assert registry.select_codec(4, 2) == registry.CAUCHY_XOR
    # per-request forced beats the env.
    assert registry.select_codec(4, 2, forced=registry.DENSE_GF8) \
        == registry.DENSE_GF8
    # env 'auto' is the documented default spelling.
    monkeypatch.setenv("MTPU_CODEC", "auto")
    assert registry.select_codec(4, 2) == registry.DENSE_GF8


def test_select_codec_rejects_unknown_and_misfit():
    with pytest.raises(KeyError, match="unknown erasure codec"):
        registry.select_codec(4, 2, forced="rs-lrc-imaginary")
    with pytest.raises(ValueError, match="does not support geometry"):
        registry.select_codec(200, 200, forced=registry.CAUCHY_XOR)


# --- engine selection: preserved MTPU_ENCODE_ENGINE semantics ---------

def test_select_engine_forced_and_ladder(monkeypatch):
    from minio_tpu.ops import gf_native

    assert gf_native.available(), "container should carry the native lib"
    big = registry.DEVICE_SHARD_THRESHOLD
    # Forced native/numpy are honored verbatim.
    monkeypatch.setenv("MTPU_ENCODE_ENGINE", "native")
    assert registry.select_engine(big, 16) == "native"
    monkeypatch.setenv("MTPU_ENCODE_ENGINE", "numpy")
    assert registry.select_engine(big, 16) == "numpy"
    # A forced engine that is unavailable for this call degrades down
    # the host ladder: device forced + sub-threshold shard -> native.
    monkeypatch.setenv("MTPU_ENCODE_ENGINE", "device")
    assert registry.select_engine(big - 1, 16) == "native"
    # auto on a small shard stays on the measured host champion.
    monkeypatch.setenv("MTPU_ENCODE_ENGINE", "auto")
    assert registry.select_engine(64, 16) == "native"


def test_select_engine_per_codec(monkeypatch):
    # Both registered codecs resolve an engine; cauchy rides the same
    # native kernel (the matrices differ, the substrate does not).
    monkeypatch.setenv("MTPU_ENCODE_ENGINE", "auto")
    for cid in (registry.DENSE_GF8, registry.CAUCHY_XOR):
        assert registry.select_engine(64, 16, codec_id=cid) == "native"


# --- probes ------------------------------------------------------------

def test_probe_gbps_measures_and_declares():
    assert registry.probe_gbps(registry.DENSE_GF8, "native") > 0
    assert registry.probe_gbps(registry.CAUCHY_XOR, "numpy") > 0
    # Device-class rates are declared feed bounds, not probed.
    entry = registry.get(registry.DENSE_GF8)
    assert registry.probe_gbps(registry.DENSE_GF8, "mesh") \
        == entry.feed_bounds["mesh"]


# --- metrics wiring ----------------------------------------------------

class _MetricsStub:
    def __init__(self):
        self.incs = []
        self.gauges = []

    def inc(self, name, value=1, **labels):
        self.incs.append((name, labels))

    def set_gauge(self, name, value, **labels):
        self.gauges.append((name, value, labels))


def test_selection_and_dispatch_counters():
    stub = _MetricsStub()
    registry.set_metrics(stub)
    try:
        registry.select_codec(4, 2, forced=registry.CAUCHY_XOR)
        registry.note_dispatch(registry.CAUCHY_XOR, "native", "apply")
    finally:
        registry.set_metrics(None)
    assert ("mtpu_codec_selected_total",
            {"codec": registry.CAUCHY_XOR, "geometry": "4+2"}) in stub.incs
    assert ("mtpu_codec_dispatch_total",
            {"codec": registry.CAUCHY_XOR, "engine": "native"}) in stub.incs


def test_read_side_series_stand_at_zero_from_set_metrics():
    """A scrape that finds a series reads "none", one that finds no
    series reads nothing: the four series of the read side are there
    from the moment the registry is set, and a dispatch raises the old
    series and its kind's, each by one."""
    from minio_tpu.observability.metrics import Metrics

    m = Metrics()
    registry.set_metrics(m)
    try:
        page = m.render_prometheus()
        for line in (
                'mtpu_codec_dispatch_kind_total{engine="device",kind="apply"} 0',
                'mtpu_codec_dispatch_kind_total{engine="mesh",kind="reconstruct"} 0',
                'mtpu_codec_dispatch_kind_total{engine="native",kind="encode"} 0',
                'mtpu_bitrot_verified_bytes_total{path="get"} 0',
                'mtpu_bitrot_verified_bytes_total{path="heal"} 0',
                "mtpu_get_reconstructed_blocks_total 0",
                "mtpu_get_mrf_queued_total 0"):
            assert any(ln.startswith(line) for ln in page.splitlines()), line
        assert "codec_dispatch_total{" not in page
        for kind in registry.DISPATCH_KINDS:
            registry.note_dispatch(registry.DENSE_GF8, "device", kind)
        registry.note_read("bitrot_verified_bytes_total", 131072, path="get")
    finally:
        registry.set_metrics(None)
    assert m.counter_value("mtpu_codec_dispatch_total",
                           codec=registry.DENSE_GF8, engine="device") == 3
    for kind in registry.DISPATCH_KINDS:
        assert m.counter_value("codec_dispatch_kind_total", engine="device",
                               kind=kind) == 1
    assert m.counter_value("bitrot_verified_bytes_total", path="get") == 131072
    assert m.counter_value("bitrot_verified_bytes_total", path="heal") == 0


def test_codec_descriptors_in_catalog():
    from minio_tpu.observability import metrics_v2

    names = {name for name, _t, _h in metrics_v2.DESCRIPTORS}
    for name, _t, _h in registry.CODEC_DESCRIPTORS:
        assert name in names


# --- xl.meta codec identity: round-trip + strictness ------------------

def _erasure_dict(codec_id: str | None) -> dict:
    entry = registry.get(codec_id) if codec_id else None
    ei = ErasureInfo(
        algorithm=entry.wire_algorithm if entry else ERASURE_ALGORITHM,
        data_blocks=4, parity_blocks=2, block_size=1 << 20, index=1,
        distribution=[1, 2, 3, 4, 5, 6],
        checksums=[ChecksumInfo(part_number=1, algorithm="highwayhash256S",
                                hash=b"")],
        codec=codec_id or "",
    )
    return ei.to_dict()


def test_cid_round_trips_and_absent_means_dense():
    # Registry-written metadata round-trips the codec id.
    for cid in (registry.DENSE_GF8, registry.CAUCHY_XOR, registry.MSR_PM):
        d = _erasure_dict(cid)
        assert d["cid"] == cid
        back = ErasureInfo.from_dict(d)
        assert back.codec == cid
        assert back.algorithm == registry.get(cid).wire_algorithm
    # Pre-registry shape: no cid key at all, legacy algo -> dense.
    legacy = _erasure_dict(None)
    assert "cid" not in legacy
    assert ErasureInfo.from_dict(legacy).codec == registry.DEFAULT_CODEC


def test_strict_from_dict_fails_loud():
    # Unknown codec id: never decode with the wrong matrices.
    d = _erasure_dict(registry.CAUCHY_XOR)
    d["cid"] = "rs-lrc-imaginary"
    with pytest.raises(ValueError, match="unknown erasure codec"):
        ErasureInfo.from_dict(d)
    # cid/algo disagreement is corruption, not a preference.
    d = _erasure_dict(registry.CAUCHY_XOR)
    d["algo"] = ERASURE_ALGORITHM
    with pytest.raises(ValueError, match="mismatch"):
        ErasureInfo.from_dict(d)
    # Non-legacy algo with NO cid (a reader/rewriter dropped the
    # unknown field): refuse to guess.
    d = _erasure_dict(registry.CAUCHY_XOR)
    del d["cid"]
    with pytest.raises(ValueError, match="refusing to guess"):
        ErasureInfo.from_dict(d)
    # Same strictness for the regenerating codec: a cid/algo split or a
    # dropped cid must never resolve to dense matrices over α-packed
    # sub-shards.
    d = _erasure_dict(registry.MSR_PM)
    d["algo"] = ERASURE_ALGORITHM
    with pytest.raises(ValueError, match="mismatch"):
        ErasureInfo.from_dict(d)
    d = _erasure_dict(registry.MSR_PM)
    del d["cid"]
    with pytest.raises(ValueError, match="refusing to guess"):
        ErasureInfo.from_dict(d)


def _frozen_pre_registry_from_dict(d: dict) -> ErasureInfo:
    """VERBATIM copy of ErasureInfo.from_dict as it shipped before the
    registry existed — the 'old reader'. Kept frozen here so the
    regression below keeps meaning something after the live from_dict
    evolves further."""
    return ErasureInfo(
        algorithm=d["algo"],
        data_blocks=d["k"],
        parity_blocks=d["m"],
        block_size=d["bs"],
        index=d["idx"],
        distribution=list(d["dist"]),
        checksums=[ChecksumInfo.from_dict(c) for c in d["cs"]],
    )


def test_old_reader_cannot_silently_dense_decode_cauchy():
    """A registry-written cauchy object handed to the pre-registry
    reader: the old from_dict accepts the dict (it validated nothing),
    but what it produces carries algorithm='rs-cauchy-xor' and no codec
    — and BOTH exits from that state fail loud instead of decoding
    dense. That non-legacy wire algo is the deliberate tripwire: dense
    misdecode requires algo == rs-vandermonde somewhere, and a cauchy
    object never carries it."""
    d = _erasure_dict(registry.CAUCHY_XOR)
    old = _frozen_pre_registry_from_dict(d)
    assert old.algorithm == "rs-cauchy-xor" and old.codec == ""
    # Exit 1: the old reader re-serializes (a heal/rewrite) — the codec
    # field is lost, and the strict reader refuses the result.
    with pytest.raises(ValueError, match="refusing to guess"):
        ErasureInfo.from_dict(old.to_dict())
    # Exit 2: code resolves the old-shaped algo to a codec — the mapping
    # is exact, never a dense fallback.
    assert registry.wire_algorithm_to_codec(old.algorithm) \
        == registry.CAUCHY_XOR
    # And the legacy absent-cid default is keyed to the legacy algo
    # ONLY — the dict that legitimately takes the dense default is
    # byte-shaped exactly like pre-registry metadata.
    legacy = _erasure_dict(None)
    assert ErasureInfo.from_dict(legacy).algorithm == ERASURE_ALGORITHM


def test_old_reader_cannot_silently_dense_decode_msr(tmp_path):
    """The msr-pm tripwire is double-walled: the wire algo is non-legacy
    (same loud exits as cauchy), AND the shard files are α-packed —
    shard_file_size under the dense reader's α=1 assumption would not
    even match the bytes on disk for payloads the α-rounding padded."""
    d = _erasure_dict(registry.MSR_PM)
    old = _frozen_pre_registry_from_dict(d)
    assert old.algorithm == "rs-msr-pm" and old.codec == ""
    # Exit 1: old reader re-serializes, cid lost -> strict reader refuses.
    with pytest.raises(ValueError, match="refusing to guess"):
        ErasureInfo.from_dict(old.to_dict())
    # Exit 2: algo resolves exactly, never to dense.
    assert registry.wire_algorithm_to_codec("rs-msr-pm") == registry.MSR_PM
    # The α wall: the same geometry disagrees on shard sizing between
    # the stamped codec and the dense default, so even a reader that
    # somehow bypassed the algo tripwire reads misaligned frames.
    msr = ErasureInfo.from_dict(_erasure_dict(registry.MSR_PM))
    dense = ErasureInfo.from_dict(_erasure_dict(registry.DENSE_GF8))
    odd = (1 << 20) + 13  # tail chunk not a multiple of k*α
    assert msr.shard_file_size(odd) != dense.shard_file_size(odd)


def test_meta_hash_covers_codec():
    from minio_tpu.object.metadata import _meta_hash

    def fi(codec):
        f = FileInfo(volume="b", name="o")
        f.erasure = ErasureInfo(
            data_blocks=4, parity_blocks=2, block_size=1 << 20,
            distribution=[1, 2, 3, 4, 5, 6], codec=codec,
        )
        return f

    # Disks disagreeing on codec must never merge into one version.
    hashes = {
        _meta_hash(fi(cid))
        for cid in (registry.DENSE_GF8, registry.CAUCHY_XOR,
                    registry.MSR_PM)
    }
    assert len(hashes) == 3


# --- end-to-end: pre-registry on-disk metadata stays readable ---------

def test_pre_registry_object_decodes_heals_unchanged(tmp_path):
    """Write an object, then rewrite every disk's xl.meta to the
    pre-registry shape (codec field stripped -> the 'cid' key is not
    emitted). GET, list, and heal must behave exactly as before the
    registry existed."""
    z, disks_all = make_pools(tmp_path, n_disks=6, parity=2)
    disks = disks_all[0]
    z.make_bucket("bkt")
    payload = np.random.default_rng(7).integers(
        0, 256, 3 * (1 << 20) + 999, np.uint8).tobytes()
    z.put_object("bkt", "old-world", io.BytesIO(payload), len(payload))

    # Strip the codec stamp on every disk: update_metadata re-serializes
    # the version, and to_dict omits "cid" when codec is empty.
    for d in disks:
        fi = d.read_version("bkt", "old-world", "", False)
        assert fi.erasure.codec == registry.DENSE_GF8
        fi.erasure.codec = ""
        d.update_metadata("bkt", "old-world", fi)

    # The strict reader resolves the absent field to dense.
    fi = disks[0].read_version("bkt", "old-world", "", False)
    assert fi.erasure.codec == registry.DEFAULT_CODEC
    assert fi.erasure.algorithm == ERASURE_ALGORITHM

    # Healthy GET.
    assert z.get_object_bytes("bkt", "old-world") == payload

    # Degraded GET + heal: destroy two data-shard part files.
    from minio_tpu.object.metadata import hash_order

    order = hash_order("bkt/old-world", len(disks))
    kill = [i for i in range(len(disks)) if order[i] in (1, 2)]
    for i in kill:
        obj_dir = os.path.join(disks[i].root, "bkt", "old-world")
        for dirpath, _dirs, files in os.walk(obj_dir):
            for f in files:
                if f.startswith("part."):
                    os.remove(os.path.join(dirpath, f))
    assert z.get_object_bytes("bkt", "old-world") == payload
    res = z.heal_object("bkt", "old-world")
    assert res["healed"], res
    assert z.get_object_bytes("bkt", "old-world") == payload


def test_mixed_codec_bucket_heals_per_object(tmp_path):
    """One bucket, one object per registered codec, one dead disk: heal
    must resolve EACH object's codec from its own xl.meta — matrices,
    α-packed shard sizing, and (for msr-pm) the repair plan all differ
    per object — and every GET must round-trip afterward."""
    from minio_tpu.object.types import ObjectOptions

    z, disks_all = make_pools(tmp_path, n_disks=6, parity=2)
    disks = disks_all[0]
    z.make_bucket("bkt")
    rng = np.random.default_rng(11)
    payloads = {}
    for cid in registry.codec_ids():
        payloads[cid] = rng.integers(
            0, 256, (1 << 20) + 17 * len(cid), np.uint8).tobytes()
        z.put_object("bkt", f"obj-{cid}", io.BytesIO(payloads[cid]),
                     len(payloads[cid]), ObjectOptions(codec=cid))

    # One disk loses everything it held for the bucket.
    victim = disks[2]
    import shutil
    shutil.rmtree(os.path.join(victim.root, "bkt"), ignore_errors=True)

    for cid in registry.codec_ids():
        res = z.heal_object("bkt", f"obj-{cid}")
        assert res["healed"], (cid, res)
        fi = victim.read_version("bkt", f"obj-{cid}", "", False)
        assert fi.erasure.codec == cid
        assert z.get_object_bytes("bkt", f"obj-{cid}") == payloads[cid]
