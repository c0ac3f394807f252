"""Mesh serving engine tests (parallel/mesh_engine + placement):
in-process unit proofs on the 8-device CPU mesh conftest forces, plus
the `mesh`-marked subprocess proofs that drive the ObjectLayer
(PutObject -> GetObject(degraded) -> HealObject) exactly as CI must see
them — one collective dispatch per batch, zero steady-state retraces,
shard files byte-identical to the native engine."""

import io
import json

import numpy as np
import pytest

from minio_tpu.erasure.bitrot import (
    BitrotAlgorithm,
    StreamingBitrotReader,
    StreamingBitrotWriter,
)
from minio_tpu.erasure import registry
from minio_tpu.erasure.codec import Erasure
from minio_tpu.erasure.streaming import (
    decode_stream,
    encode_stream,
    heal_stream,
)
from minio_tpu.ops import highwayhash as hh
from minio_tpu.parallel import mesh_engine, placement
from minio_tpu.parallel import metrics as mesh_metrics

BLOCK = 1 << 16  # 4+4 @ 64 KiB -> 16 KiB shards (mesh-eligible size)


# ---------------------------------------------------------------------------
# placement / engine selection


def test_placement_shape_selection(monkeypatch):
    monkeypatch.delenv("MTPU_MESH_SHAPE", raising=False)
    assert placement.select_shape(16, 8) == (1, 8)
    assert placement.select_shape(8, 8) == (1, 8)
    assert placement.select_shape(4, 8) == (2, 4)
    assert placement.select_shape(12, 8) == (2, 4)  # 12 % 8 != 0
    assert placement.select_shape(5, 8) is None     # odd shard count
    assert placement.select_shape(16, 1) is None    # single device
    monkeypatch.setenv("MTPU_MESH_SHAPE", "2x4")
    assert placement.select_shape(16, 8) == (2, 4)
    # Invalid pins degrade to auto selection, never crash the PUT path.
    monkeypatch.setenv("MTPU_MESH_SHAPE", "2x3")    # 16 % 3 != 0
    assert placement.select_shape(16, 8) == (1, 8)
    monkeypatch.setenv("MTPU_MESH_SHAPE", "garbage")
    assert placement.select_shape(16, 8) == (1, 8)
    monkeypatch.setenv("MTPU_MESH_SHAPE", "4x4")    # 16 devices wanted
    assert placement.select_shape(16, 8) == (1, 8)


def test_engine_selection_mesh_and_fallbacks(monkeypatch):
    monkeypatch.delenv("MTPU_MESH_SHAPE", raising=False)
    shard = 1 << 14
    monkeypatch.setenv("MTPU_ENCODE_ENGINE", "mesh")
    assert registry.select_engine(shard, 16) == "mesh"
    # No geometry -> the one-shot host helpers never route to the mesh.
    assert registry.select_engine(shard) != "mesh"
    # Geometry that shares no lane divisor with 8 devices -> fallback.
    assert registry.select_engine(shard, 5) in ("native", "numpy")
    # Tiny shards stay on the host engines (dispatch cost dominates).
    assert registry.select_engine(64, 16) in ("native", "numpy")
    # 'auto' on a CPU virtual mesh must NOT self-select collectives.
    monkeypatch.setenv("MTPU_ENCODE_ENGINE", "auto")
    assert registry.select_engine(shard, 16) != "mesh"


# ---------------------------------------------------------------------------
# MeshCodec vs host oracles


def _host_digests(shards: np.ndarray) -> np.ndarray:
    out = np.empty(shards.shape[:-1] + (32,), dtype=np.uint8)
    for idx in np.ndindex(shards.shape[:-1]):
        h = hh.HighwayHash256(hh.MAGIC_KEY)
        h.update(shards[idx].tobytes())
        out[idx] = np.frombuffer(h.digest(), dtype=np.uint8)
    return out


def test_mesh_encode_matches_host_oracle(monkeypatch):
    monkeypatch.delenv("MTPU_MESH_SHAPE", raising=False)
    er = Erasure(4, 4, BLOCK)
    s = er.shard_size()
    codec = mesh_engine.for_geometry(4, 4)
    assert (codec.dp, codec.lanes) == (1, 8)
    blocks = np.random.default_rng(0).integers(
        0, 256, size=(4, 4, s), dtype=np.uint8
    )
    parity, digests = codec.encode_async(blocks, with_hashes=True)
    parity, digests = np.asarray(parity), np.asarray(digests)
    exp = er.encode_batch(blocks)
    np.testing.assert_array_equal(parity, exp)
    full = np.concatenate([blocks, exp], axis=1)
    np.testing.assert_array_equal(digests, _host_digests(full))


def test_mesh_ragged_batch_pads_and_slices(monkeypatch):
    # dp=4: a 3-row batch zero-pads to 4 and the outputs slice back.
    monkeypatch.setenv("MTPU_MESH_SHAPE", "4x2")
    er = Erasure(4, 4, BLOCK)
    s = er.shard_size()
    codec = mesh_engine.for_geometry(4, 4)
    assert (codec.dp, codec.lanes) == (4, 2)
    blocks = np.random.default_rng(1).integers(
        0, 256, size=(3, 4, s), dtype=np.uint8
    )
    parity, digests = codec.encode_async(blocks, with_hashes=True)
    assert np.asarray(parity).shape == (3, 4, s)
    assert np.asarray(digests).shape == (3, 8, 32)
    np.testing.assert_array_equal(np.asarray(parity),
                                  er.encode_batch(blocks))


def test_mesh_reconstruct_matches_host(monkeypatch):
    monkeypatch.delenv("MTPU_MESH_SHAPE", raising=False)
    er = Erasure(4, 4, BLOCK)
    s = er.shard_size()
    codec = mesh_engine.for_geometry(4, 4)
    blocks = np.random.default_rng(2).integers(
        0, 256, size=(2, 4, s), dtype=np.uint8
    )
    full = np.concatenate([blocks, er.encode_batch(blocks)], axis=1)
    dead = (1, 6)
    present = tuple(i for i in range(8) if i not in dead)
    src = full[:, list(present[:4])]
    rebuilt, digs = codec.reconstruct_async(src, present, dead,
                                            with_hashes=True)
    rebuilt, digs = np.asarray(rebuilt), np.asarray(digs)
    np.testing.assert_array_equal(rebuilt[:, 0], full[:, 1])
    np.testing.assert_array_equal(rebuilt[:, 1], full[:, 6])
    np.testing.assert_array_equal(digs, _host_digests(rebuilt))


@pytest.mark.parametrize("rows,padded", [(8, 0), (2, 6)])
def test_mesh_at_the_benchmark_cells_geometry(monkeypatch, rows, padded):
    """`n16mesh4-put10m`'s own geometry and shape (12+4, dp=1 x lane=4:
    4 of the 16 shards a chip) at the two batches a 10 MiB PUT makes, the
    full one of 8 rows and the tail of 2 that is padded to 8: parity and
    all 16 digests are the benchmark's reference's and the one-chip
    engine's, byte for byte."""
    from benchmark.harness import reference

    from minio_tpu.erasure import device_engine

    monkeypatch.setenv("MTPU_MESH_SHAPE", "1x4")
    s = 2731 + 19           # whole 32-byte packets and a remainder
    codec = mesh_engine.for_geometry(12, 4)
    assert (codec.dp, codec.lanes, codec._pad_rows) == (1, 4, 8)
    blocks = np.random.default_rng(rows).integers(
        0, 256, size=(rows, 12, s), dtype=np.uint8
    )
    before = mesh_metrics.stats_snapshot()
    parity, digests = codec.encode_async(blocks.copy(), with_hashes=True)
    parity, digests = np.asarray(parity), np.asarray(digests)
    after = mesh_metrics.stats_snapshot()
    assert parity.shape == (rows, 4, s) and digests.shape == (rows, 16, 32)
    assert (after["mesh_padded_blocks_total"]
            - before["mesh_padded_blocks_total"]) == padded
    assert after["mesh_blocks_total"] - before["mesh_blocks_total"] == rows
    assert (after["mesh_collective_bytes_total"]
            - before["mesh_collective_bytes_total"]) == 8 * (4 * s + 16 * 32)
    exp = reference.apply_matrix(
        reference.parity_matrix("dense-gf8", 12, 4), blocks)
    np.testing.assert_array_equal(parity, exp)
    np.testing.assert_array_equal(
        digests,
        reference.highwayhash256(np.concatenate([blocks, exp], axis=1)))
    one = device_engine.for_geometry(12, 4)
    parity1, digests1 = one.encode_async(blocks.copy(), with_hashes=True)
    np.testing.assert_array_equal(parity, np.asarray(parity1))
    np.testing.assert_array_equal(digests, np.asarray(digests1))


def test_mesh_digests_stay_lane_local(monkeypatch):
    """The compiled 12+4 encode of the 1x4 mesh holds the collectives it
    held before the digest scan was rewritten (PR 30) and no more: parity
    from lane 3 to the others and back (collective-permute, all-gather)
    and one all-gather of the 32-byte digests. The stripe is never
    gathered: each chip hashes its own 4 of the 16 shards."""
    import re
    from collections import Counter

    monkeypatch.setenv("MTPU_MESH_SHAPE", "1x4")
    s = 2731 + 19
    codec = mesh_engine.for_geometry(12, 4)
    blocks = np.random.default_rng(3).integers(
        0, 256, size=(8, 12, s), dtype=np.uint8)
    _, digests = codec.encode_async(blocks.copy(), with_hashes=True)
    np.asarray(digests)
    fn = codec._fns[("enc", True, blocks.shape)]
    dev, _ = codec._stage(blocks.copy())
    hlo = fn.lower(codec._dev_mat("parity", codec._parity_bits_np),
                   dev).compile().as_text()
    found = re.findall(
        r"= (\S+) (all-gather|all-reduce|collective-permute|all-to-all"
        r"|reduce-scatter)(?:-start)?\(", hlo)
    assert Counter(op for _, op in found) == {
        "all-gather": 3, "collective-permute": 3}
    shapes = sorted(shape.split("{")[0] for shape, _ in found)
    assert shapes == ["u8[8,1,2750]"] * 3 + ["u8[8,16,32]"] + [
        "u8[8,4,2750]"] * 2


# ---------------------------------------------------------------------------
# streaming drivers on the mesh engine


class MemShard:
    def __init__(self, shard_size):
        self.sink = io.BytesIO()
        self.writer = StreamingBitrotWriter(
            self.sink, BitrotAlgorithm.HIGHWAYHASH256S
        )
        self.shard_size = shard_size

    def reader(self, data_len: int):
        buf = self.sink.getvalue()
        return StreamingBitrotReader(
            lambda off, ln: io.BytesIO(buf[off: off + ln]),
            till_offset=data_len, shard_size=self.shard_size,
        )


def _encode(engine: str, er: Erasure, data: bytes, monkeypatch):
    monkeypatch.setenv("MTPU_ENCODE_ENGINE", engine)
    shards = [MemShard(er.shard_size()) for _ in range(er.total_shards)]
    n = encode_stream(er, io.BytesIO(data), [s.writer for s in shards],
                      quorum=er.data_blocks + 1)
    assert n == len(data)
    return shards


def test_mesh_encode_stream_byte_identical_to_native(monkeypatch):
    monkeypatch.delenv("MTPU_MESH_SHAPE", raising=False)
    er = Erasure(4, 4, BLOCK)
    # 8 full blocks = exactly one steady-state [8, k, S] batch (a second
    # batch shape would only buy another ~10s XLA compile; ragged batch
    # coverage lives in test_mesh_ragged_batch_pads_and_slices) plus a
    # short tail block on the host path.
    data = np.random.default_rng(3).integers(
        0, 256, 8 * BLOCK + 777, np.uint8
    ).tobytes()
    mesh_metrics.reset_stats()
    s0 = mesh_metrics.stats_snapshot()
    mesh_shards = _encode("mesh", er, data, monkeypatch)
    s1 = mesh_metrics.stats_snapshot()
    # One fused collective dispatch per dp-group batch, and a second
    # identical stream must add ZERO retraces (steady state).
    d1 = s1["mesh_dispatches_total"] - s0["mesh_dispatches_total"]
    b1 = s1["mesh_batches_total"] - s0["mesh_batches_total"]
    assert d1 == b1 > 0
    _encode("mesh", er, data, monkeypatch)
    s2 = mesh_metrics.stats_snapshot()
    assert s2["mesh_retraces_total"] == s1["mesh_retraces_total"]
    native_shards = _encode("native", er, data, monkeypatch)
    assert [s.sink.getvalue() for s in mesh_shards] == \
        [s.sink.getvalue() for s in native_shards]


def test_mesh_decode_stream_degraded(monkeypatch):
    monkeypatch.delenv("MTPU_MESH_SHAPE", raising=False)
    er = Erasure(4, 4, BLOCK)
    size = 8 * BLOCK + 123  # one full reconstruct batch + ragged tail
    data = np.random.default_rng(4).integers(
        0, 256, size, np.uint8
    ).tobytes()
    shards = _encode("mesh", er, data, monkeypatch)
    shard_len = er.shard_file_size(size)
    readers = [s.reader(shard_len) for s in shards]
    readers[0] = readers[2] = None  # two dead data shards
    before = mesh_metrics.stats_snapshot()
    out = io.BytesIO()
    written, _ = decode_stream(er, out, readers, 0, size, size)
    after = mesh_metrics.stats_snapshot()
    assert written == size
    assert out.getvalue() == data
    assert (after["mesh_dispatches_total"]
            > before["mesh_dispatches_total"]), "decode skipped the mesh"
    # Range read through the same driver (offset inside block 1).
    readers = [s.reader(shard_len) for s in shards]
    readers[1] = None
    out = io.BytesIO()
    off, ln = BLOCK + 17, 3 * BLOCK
    written, _ = decode_stream(er, out, readers, off, ln, size)
    assert written == ln
    assert out.getvalue() == data[off: off + ln]


def test_mesh_heal_stream_restores_framing(monkeypatch):
    monkeypatch.delenv("MTPU_MESH_SHAPE", raising=False)
    er = Erasure(4, 4, BLOCK)
    # One full [8, k, S] heal batch + a ragged tail block exercising the
    # host fallback (an extra partial batch would recompile for B=1).
    size = 8 * BLOCK + 123
    data = np.random.default_rng(5).integers(
        0, 256, size, np.uint8
    ).tobytes()
    shards = _encode("mesh", er, data, monkeypatch)
    shard_len = er.shard_file_size(size)
    stale = (2, 5)
    readers = [
        None if i in stale else s.reader(shard_len)
        for i, s in enumerate(shards)
    ]
    sinks = {i: io.BytesIO() for i in stale}
    writers: list = [None] * er.total_shards
    for i in stale:
        writers[i] = StreamingBitrotWriter(
            sinks[i], BitrotAlgorithm.HIGHWAYHASH256S
        )
    heal_stream(er, writers, readers, size)
    for i in stale:
        assert sinks[i].getvalue() == shards[i].sink.getvalue(), (
            f"healed shard {i} not byte-identical"
        )


# ---------------------------------------------------------------------------
# the serving path, as CI must prove it: ObjectLayer APIs in an 8-device
# host-platform subprocess (see conftest.mesh_subprocess)


@pytest.mark.mesh
@pytest.mark.parametrize("shape", ["2x4"])
def test_mesh_serving_object_layer(mesh_subprocess, shape):
    """One subprocess proof in tier-1, on the richest shape (dp>1 AND
    multi-lane), forced to the NON-DEFAULT cauchy codec end to end — so
    the one child proves both the mesh serving path (PutObject ->
    degraded GetObject -> HealObject through ObjectLayer) and the codec
    registry's mesh substrate (the codec id stamped at PUT drives the
    mesh reconstruction, and the in-child native-ref comparison shows
    mesh-cauchy bytes == native-cauchy bytes). Dense mesh math is
    byte-proven in-process above against the host oracle; the full
    dense shape sweep — 1x8, 2x4, 4x2, same ObjectLayer verification —
    runs in __graft_entry__.dryrun_multichip (the MULTICHIP evidence
    artifact). One subprocess total: a second child for the default
    codec would re-pay the jax init + mesh compile (~70 s) the tier-1
    budget does not have."""
    from minio_tpu.erasure import registry

    out = mesh_subprocess(shape, payload_mib=4,
                          extra_env={"MTPU_CODEC": registry.CAUCHY_XOR})
    line = next(
        ln for ln in out.splitlines() if ln.startswith("MESH_EVIDENCE ")
    )
    ev = json.loads(line[len("MESH_EVIDENCE "):])
    dp, _, lanes = shape.partition("x")
    assert ev["shape"] == {"dp": int(dp), "lanes": int(lanes)}
    assert ev["codec"] == registry.CAUCHY_XOR
    assert ev["dispatches_per_batch"] == 1.0
    assert ev["steady_state_retraces"] == 0
    assert ev["degraded_get_dispatches"] > 0
    assert ev["healed_disks"] == 2
    assert ev["native_byte_identical"] is True
