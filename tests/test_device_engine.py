"""Fused device-engine conformance: the single-dispatch encode+digest
and reconstruct+digest paths (erasure/device_engine.DeviceCodec) must be
bit-exact against the host oracles — gf_matmul_shards_ref for parity and
the numpy/native HighwayHash for digests — and must hold the dispatch
invariants (one dispatch per batch, zero steady-state retraces, donated
inputs leaving host buffers intact). Runs entirely on CPU: tier-1
exercises the exact code the TPU backend compiles.
"""

import io

import numpy as np
import pytest

from minio_tpu.erasure import device_engine
from minio_tpu.erasure.bitrot import BitrotAlgorithm, StreamingBitrotWriter
from minio_tpu.erasure.codec import Erasure
from minio_tpu.erasure.streaming import encode_stream, heal_stream
from minio_tpu.ops import gf
from minio_tpu.ops.gf import gf_matmul_shards_ref
from minio_tpu.ops.highwayhash import hash256
from minio_tpu.observability import spans

GEOMETRIES = [(2, 2), (8, 4), (12, 4)]


@pytest.mark.parametrize("k,m", GEOMETRIES)
def test_fused_encode_digest_matches_host_oracle(k, m):
    """One fused dispatch == host parity matmul + host HighwayHash of
    every data and parity shard, for a ragged (non-multiple-of-32)
    shard length."""
    rng = np.random.default_rng(k * 31 + m)
    s = 333  # exercises the hash tail-packet path
    blocks = rng.integers(0, 256, size=(3, k, s), dtype=np.uint8)
    codec = device_engine.for_geometry(k, m)
    parity_f, digests_f = codec.encode_async(blocks, with_hashes=True)
    parity = np.asarray(parity_f)
    digests = np.asarray(digests_f)
    assert parity.shape == (3, m, s)
    assert digests.shape == (3, k + m, 32)
    mat = gf.parity_matrix(k, m)
    for bi in range(3):
        want_parity = gf_matmul_shards_ref(mat, blocks[bi])
        assert np.array_equal(parity[bi], want_parity)
        all_shards = np.concatenate([blocks[bi], want_parity], axis=0)
        for j in range(k + m):
            assert digests[bi, j].tobytes() == hash256(
                all_shards[j].tobytes()
            )


def test_one_dispatch_per_batch_and_no_steady_state_retrace():
    k, m, s = 4, 2, 512
    codec = device_engine.for_geometry(k, m)
    rng = np.random.default_rng(0)
    blocks = rng.integers(0, 256, size=(2, k, s), dtype=np.uint8)
    codec.encode_async(blocks, with_hashes=True)  # warm/compile
    device_engine.reset_stats()
    for _ in range(4):
        p, d = codec.encode_async(blocks.copy(), with_hashes=True)
        np.asarray(p), np.asarray(d)
    stats = device_engine.stats_snapshot()
    assert stats["dispatches"] == 4  # ONE fused dispatch per batch
    assert stats["traces"] == 0  # steady state never recompiles
    # A new batch shape traces exactly once more.
    bigger = rng.integers(0, 256, size=(5, k, s), dtype=np.uint8)
    codec.encode_async(bigger, with_hashes=True)
    assert device_engine.stats_snapshot()["traces"] == 1


def test_donated_input_leaves_host_buffer_intact():
    """Donation recycles the DEVICE staging buffer; the host copy (the
    pooled strip buffer the data-shard writes come from) must never be
    touched."""
    k, m, s = 2, 2, 4096
    codec = device_engine.for_geometry(k, m)
    blocks = np.random.default_rng(1).integers(
        0, 256, size=(2, k, s), dtype=np.uint8
    )
    before = blocks.copy()
    device_engine.reset_stats()
    p, d = codec.encode_async(blocks, with_hashes=True)
    np.asarray(p), np.asarray(d)
    assert np.array_equal(blocks, before)
    assert device_engine.stats_snapshot()["donated_batches"] == 1


@pytest.mark.parametrize("k,m", GEOMETRIES)
def test_encode_stream_device_matches_numpy_engine(monkeypatch, k, m):
    """End-to-end engine equivalence: the fused device PUT stream writes
    byte-identical bitrot-framed shard files to the numpy host oracle,
    including ragged tail blocks."""
    block_size = k * 4096  # shard 4096 == device engine threshold
    e = Erasure(k, m, block_size)
    rng = np.random.default_rng(7)
    data = rng.integers(
        0, 256, size=3 * block_size + 1234, dtype=np.uint8
    ).tobytes()

    def run(engine):
        monkeypatch.setenv("MTPU_ENCODE_ENGINE", engine)
        sinks = [io.BytesIO() for _ in range(k + m)]
        writers = [StreamingBitrotWriter(s) for s in sinks]
        n = encode_stream(e, io.BytesIO(data), writers, quorum=k + 1,
                          batch_blocks=2)
        assert n == len(data)
        return [s.getvalue() for s in sinks]

    got = run("device")
    want = run("numpy")
    for i, (a, b) in enumerate(zip(got, want)):
        assert a == b, f"shard {i} differs between device and numpy engines"


@pytest.mark.parametrize("k,m", GEOMETRIES)
def test_reconstruct_async_matches_oracle(k, m):
    """Fused reconstruct + digests of the rebuilt shards, at the cells'
    geometries too (12+4, 2+2), over whole packets and a remainder."""
    s = 500
    codec = device_engine.for_geometry(k, m)
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, size=(k, s), dtype=np.uint8)
    full = gf.rs_matrix(k, m)
    all_shards = gf_matmul_shards_ref(full, data)  # [k+m, s]
    # data and parity shards lost, as many as the geometry rebuilds
    targets = (0, 5, k + 1) if m > 2 else (0, k + 1)
    present = tuple(i for i in range(k + m) if i not in targets)
    src = np.stack([all_shards[list(present[:k])]] * 2)  # batch of 2
    rebuilt_f, digests_f = codec.reconstruct_async(
        src, present, targets, with_hashes=True
    )
    rebuilt = np.asarray(rebuilt_f)
    digests = np.asarray(digests_f)
    for bi in range(2):
        for t_i, t in enumerate(targets):
            assert np.array_equal(rebuilt[bi, t_i], all_shards[t])
            assert digests[bi, t_i].tobytes() == hash256(
                all_shards[t].tobytes()
            )


def test_reconstruct_async_pattern_cache_no_retrace():
    k, m, s = 4, 2, 256
    codec = device_engine.for_geometry(k, m)
    rng = np.random.default_rng(9)
    src = rng.integers(0, 256, size=(1, k, s), dtype=np.uint8)
    present, targets = (1, 2, 3, 4, 5), (0,)
    codec.reconstruct_async(src, present, targets)  # warm
    device_engine.reset_stats()
    for _ in range(3):
        r, _ = codec.reconstruct_async(src.copy(), present, targets)
        np.asarray(r)
    stats = device_engine.stats_snapshot()
    assert stats["dispatches"] == 3
    assert stats["traces"] == 0
    # a second pattern, never seen: another matrix into the same
    # compiled function, rightly applied
    full = np.concatenate(
        [src, Erasure(k, m, k * s).encode_batch(src)], axis=1)
    present, targets = (0, 1, 3, 5), (2,)
    r, _ = codec.reconstruct_async(
        np.ascontiguousarray(full[:, list(present)]), present, targets)
    np.testing.assert_array_equal(np.asarray(r), full[:, [2]])
    stats = device_engine.stats_snapshot()
    assert stats["dispatches"] == 4
    assert stats["traces"] == 0


class _MemShard:
    """In-memory bitrot-framed shard file (test_bitrot_streaming idiom)."""

    def __init__(self, shard_size):
        self.sink = io.BytesIO()
        self.writer = StreamingBitrotWriter(
            self.sink, BitrotAlgorithm.HIGHWAYHASH256S
        )
        self.shard_size = shard_size

    def reader(self, data_len: int):
        from minio_tpu.erasure.bitrot import StreamingBitrotReader

        buf = self.sink.getvalue()
        return StreamingBitrotReader(
            lambda off, ln: io.BytesIO(buf[off: off + ln]),
            till_offset=data_len, shard_size=self.shard_size,
        )


def test_heal_stream_device_matches_host(monkeypatch):
    """Device heal: fused batched reconstruction (+ fused digests via
    write_with_digest) must regenerate byte-identical framed shard
    files, ragged tail block included."""
    k, m = 8, 4
    block_size = k * 4096  # shard 4096 >= device threshold
    e = Erasure(k, m, block_size)
    rng = np.random.default_rng(21)
    size = 2 * block_size + 999  # 2 full blocks + ragged tail
    data = rng.integers(0, 256, size=size, dtype=np.uint8).tobytes()
    shards = [_MemShard(e.shard_size()) for _ in range(k + m)]
    monkeypatch.setenv("MTPU_ENCODE_ENGINE", "numpy")
    encode_stream(e, io.BytesIO(data), [s.writer for s in shards],
                  quorum=k + 1)
    shard_len = e.shard_file_size(size)

    stale = [1, 7, 11]
    monkeypatch.setenv("MTPU_ENCODE_ENGINE", "device")
    healed = {i: _MemShard(e.shard_size()) for i in stale}
    writers = [healed[i].writer if i in healed else None
               for i in range(k + m)]
    readers = [None if i in stale else shards[i].reader(shard_len)
               for i in range(k + m)]
    device_engine.reset_stats()
    heal_stream(e, writers, readers, size)
    for i in stale:
        assert healed[i].sink.getvalue() == shards[i].sink.getvalue(), (
            f"healed shard {i} differs from original"
        )
    # The two full blocks rode the fused device path (>= 1 dispatch).
    assert device_engine.stats_snapshot()["dispatches"] >= 1


# --- HostFeed, the H2D staging stage of both engines ---


@pytest.fixture
def captured(monkeypatch):
    monkeypatch.setenv("MTPU_TRACE_SLOW_MS", "0")
    monkeypatch.delenv("MTPU_TRACE", raising=False)
    spans.reset()
    yield
    spans.reset()


def _fed(feed, batch):
    """-> (what the feed returned, its device-h2d spans' labels)."""
    with spans.request_trace("put_object"):
        out = feed(batch)
    tree = spans.slow_requests()[-1]
    return out, [s["label"] for s in tree["spans"]
                 if s["kind"] == "device-h2d"]


def test_host_feed_declines_what_accept_refuses(captured):
    """A declined batch passes through on the host, the same object, and
    no transfer is recorded: the codec downstream stages it itself."""
    feed = device_engine.HostFeed(accept=lambda b: b.shape[0] % 4 == 0)
    ragged = np.zeros((3, 4, 128), dtype=np.uint8)
    out, labels = _fed(feed, ragged)
    assert out is ragged
    assert labels == []


def test_host_feed_stages_and_completes_the_transfer(captured):
    """An accepted batch comes back as a device array whose transfer is
    done inside the stage, byte for byte the host batch."""
    feed = device_engine.HostFeed()
    assert feed.name == "h2d"
    batch = np.random.default_rng(5).integers(
        0, 256, size=(4, 4, 128), dtype=np.uint8)
    out, labels = _fed(feed, batch)
    assert device_engine._is_device_array(out)
    assert out.is_ready()
    assert np.array_equal(np.asarray(out), batch)
    assert len(labels) == 1


def test_host_feed_labels_the_span_by_engine(captured):
    """`device_h2d_ms_per_op.*` reads the span by kind; its label says
    which engine staged: `mesh` with a sharding, `device` without."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    batch = np.ones((4, 4, 128), dtype=np.uint8)
    _, labels = _fed(device_engine.HostFeed(), batch)
    assert labels == ["device"]
    sharding = NamedSharding(
        Mesh(np.array(jax.devices()[:4]), ("dp",)), PartitionSpec("dp"))
    out, labels = _fed(
        device_engine.HostFeed("h2d-mesh", sharding=sharding), batch)
    assert labels == ["mesh"]
    assert out.sharding == sharding
    assert np.array_equal(np.asarray(out), batch)
