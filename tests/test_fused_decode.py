"""The fused decode driver (`erasure/streaming._decode_stream_fused`,
ISSUE 37): on the device engine and on the virtual mesh a degraded GET
rebuilds a reader batch in one dispatch, a healthy GET touches no
device, a failure pattern is an argument of the one compiled function
and not its key, and the bytes are the reference's. Everything on the
CPU; nothing here is timed."""

from __future__ import annotations

import io

import numpy as np
import pytest

from benchmark.harness import reference, reference_decode
from minio_tpu.erasure import registry, streaming
from minio_tpu.erasure.bitrot import (BitrotAlgorithm,
                                      StreamingBitrotReader,
                                      StreamingBitrotWriter)
from minio_tpu.erasure.codec import Erasure
from minio_tpu.erasure.streaming import decode_stream, encode_stream
from minio_tpu.observability import spans
from minio_tpu.observability.metrics import Metrics
from minio_tpu.parallel import metrics as mesh_metrics
from minio_tpu.pipeline.buffers import COPY
from minio_tpu.utils.errors import ErrFileNotFound

K, M = 8, 4
MIB = 1 << 20
SMALL = 64 << 10            # 8,192-byte shards: above the device threshold
CODEC = "dense-gf8"
ENGINES = ("device", "mesh")


class Shards:
    """An object's twelve bitrot-framed shard files, in memory."""

    def __init__(self, er: Erasure, body: bytes, monkeypatch):
        monkeypatch.setenv("MTPU_ENCODE_ENGINE", "native")
        self.er, self.body = er, body
        self.sinks = [io.BytesIO() for _ in range(er.total_shards)]
        writers = [StreamingBitrotWriter(s, BitrotAlgorithm.HIGHWAYHASH256S)
                   for s in self.sinks]
        assert encode_stream(er, io.BytesIO(body), writers,
                             quorum=er.data_blocks + 1) == len(body)

    def readers(self, lost=()) -> list:
        till = self.er.shard_file_size(len(self.body))

        def one(i):
            buf = self.sinks[i].getvalue()
            return StreamingBitrotReader(
                lambda off, ln: io.BytesIO(buf[off: off + ln]),
                till_offset=till, shard_size=self.er.shard_size())

        return [None if i in lost else one(i)
                for i in range(self.er.total_shards)]

    def files(self, lost=()) -> dict[int, bytes]:
        return {i + 1: s.getvalue() for i, s in enumerate(self.sinks)
                if i not in lost}


@pytest.fixture
def reg(monkeypatch):
    monkeypatch.setenv("MTPU_CODEC", CODEC)
    monkeypatch.delenv("MTPU_MESH_SHAPE", raising=False)
    monkeypatch.setenv("MTPU_TRACE_SLOW_MS", "0")
    monkeypatch.delenv("MTPU_TRACE", raising=False)
    old = registry._reg()
    m = Metrics()
    registry.set_metrics(m)
    spans.reset()
    spans.set_metrics(m)
    yield m
    spans.set_metrics(None)
    spans.reset()
    registry.set_metrics(old)


def _kind(reg: Metrics, engine: str, kind: str) -> float:
    return reg.counter_value("codec_dispatch_kind_total", engine=engine,
                             kind=kind)


def _traces(reg: Metrics, engine: str) -> float:
    if engine == "mesh":
        return mesh_metrics.stats_snapshot()["mesh_retraces_total"]
    return reg.counter_value("codec_trace_total", codec=CODEC,
                             engine="device")


def _get(shards: Shards, readers: list, offset: int = 0,
         length: int | None = None) -> tuple[bytes, dict]:
    """One GET under a request root -> (the body, its span tree)."""
    length = len(shards.body) - offset if length is None else length
    out = io.BytesIO()
    spans.clear_slow_requests()
    with spans.request_trace("get_object"):
        n, _ = decode_stream(shards.er, out, readers, offset, length,
                             len(shards.body))
    assert n == length
    return out.getvalue(), spans.slow_requests()[-1]


def _spans_of(tree: dict, kind: str) -> list:
    return [s for s in tree["spans"] if s["kind"] == kind]


@pytest.mark.parametrize("engine", ENGINES)
def test_a_degraded_10_block_get_is_two_dispatches(reg, monkeypatch,
                                                   engine):
    """The deployment's geometry, 8+4 at 1 MiB: ten blocks are two
    reader batches, 8 and 2, and each is one `reconstruct`; no block
    goes through the unfused `apply`; the body is the reference's."""
    er = Erasure(K, M, MIB)
    body = reference.payload(37, "fused", 10 * MIB)
    shards = Shards(er, body, monkeypatch)
    monkeypatch.setenv("MTPU_ENCODE_ENGINE", engine)
    rec0, app0 = _kind(reg, engine, "reconstruct"), _kind(reg, engine,
                                                          "apply")
    blocks0 = reg.counter_value("get_reconstructed_blocks_total")
    COPY.reset()
    got, tree = _get(shards, shards.readers(lost=(2,)))
    ref = reference_decode.decode(shards.files(lost=(2,)), K, M, MIB,
                                  len(body), CODEC)
    assert got == ref.body == body and sorted(ref.rebuilt) == [3]
    assert _kind(reg, engine, "reconstruct") - rec0 == 2
    assert _kind(reg, engine, "apply") - app0 == 0
    assert reg.counter_value("get_reconstructed_blocks_total") \
        - blocks0 == 10
    assert [s["label"] for s in _spans_of(tree, "stream")] == ["fused"]
    # a batch: one copy to the chip, one call, one wait
    for kind, label in (("device-h2d", engine), ("device-call", "rec"),
                        ("device-wait", "")):
        found = _spans_of(tree, kind)
        assert len(found) == 2, (kind, found)
        assert {s["label"] for s in found} == {label}
    # the survivors are copied once, into the staging array
    assert COPY.snapshot().get("get.fused_gather", 0) == \
        10 * K * er.shard_size()


@pytest.mark.parametrize("engine", ENGINES)
def test_a_healthy_get_opens_no_device_span(reg, monkeypatch, engine):
    er = Erasure(K, M, SMALL)
    body = reference.payload(38, "healthy", 20 * SMALL)
    shards = Shards(er, body, monkeypatch)
    monkeypatch.setenv("MTPU_ENCODE_ENGINE", engine)
    before = {kind: _kind(reg, engine, kind)
              for kind in registry.DISPATCH_KINDS}
    COPY.reset()
    got, tree = _get(shards, shards.readers())
    assert got == body
    assert [s["label"] for s in _spans_of(tree, "stream")] == ["fused"]
    assert not [s for s in tree["spans"] if s["kind"].startswith("device-")]
    assert {kind: _kind(reg, engine, kind)
            for kind in registry.DISPATCH_KINDS} == before
    assert COPY.snapshot().get("get.fused_gather", 0) == 0
    # three reader batches went through both stages
    stages = [s["label"] for s in _spans_of(tree, "stage")]
    assert stages.count("get/shard-read") == 3
    assert stages.count("get/rebuild") == 3


class _Hooked:
    """A shard reader that calls `hook(n)` before its n-th fan-out."""

    def __init__(self, reader, hook):
        self._reader, self._hook, self._n = reader, hook, 0

    def read_chunks(self, offset, lengths):
        self._n += 1
        self._hook(self._n)
        return self._reader.read_chunks(offset, lengths)

    def __getattr__(self, name):
        return getattr(self._reader, name)


@pytest.mark.parametrize("engine", ENGINES)
def test_a_healthy_get_fetches_ahead_of_the_client_write(reg, monkeypatch,
                                                         engine):
    """The overlap `pipelined` gave a healthy GET stays: the client's
    first write does not return before the second reader batch is being
    fetched, which a driver that reads, rebuilds and writes on one
    thread could never satisfy."""
    import threading

    er = Erasure(K, M, SMALL)
    body = reference.payload(43, "ahead", 20 * SMALL)
    shards = Shards(er, body, monkeypatch)
    monkeypatch.setenv("MTPU_ENCODE_ENGINE", engine)
    second_fetch = threading.Event()
    readers = shards.readers()
    readers[0] = _Hooked(
        readers[0], lambda n: second_fetch.set() if n == 2 else None)
    seen = []

    class Client(io.BytesIO):
        def write(self, chunk):
            if not seen:
                seen.append(second_fetch.wait(30))
            return super().write(chunk)

    out = Client()
    n, _ = decode_stream(er, out, readers, 0, len(body), len(body))
    assert n == len(body) and out.getvalue() == body
    assert seen == [True]


@pytest.mark.parametrize("engine", ENGINES)
def test_eight_lost_positions_trace_two_batch_shapes(reg, monkeypatch,
                                                     engine):
    """What `n12dev1-get10m` does as the key's rotation turns: each of
    the 8 data positions lost in turn. The pattern is a matrix, so all
    eight run the two programs the first one built (or found built)."""
    er = Erasure(K, M, SMALL)
    body = reference.payload(39, "turn", 10 * SMALL)
    shards = Shards(er, body, monkeypatch)
    monkeypatch.setenv("MTPU_ENCODE_ENGINE", engine)
    t0, rec0 = _traces(reg, engine), _kind(reg, engine, "reconstruct")
    for lost in range(K):
        got, _ = _get(shards, shards.readers(lost=(lost,)))
        assert got == body, lost
        assert _traces(reg, engine) - t0 <= 2, lost
    assert _kind(reg, engine, "reconstruct") - rec0 == 2 * K
    # a pattern never seen, in a batch shape that is known: no trace
    t1 = _traces(reg, engine)
    got, _ = _get(shards, shards.readers(lost=(1, 6)))
    assert got == body
    assert _traces(reg, engine) - t1 <= 2     # T = 2: another matrix shape
    t2 = _traces(reg, engine)
    got, _ = _get(shards, shards.readers(lost=(0, 7)))
    assert got == body
    assert _traces(reg, engine) == t2


@pytest.mark.parametrize("engine", ENGINES)
def test_an_unseen_pattern_of_a_known_shape_traces_nothing(reg, monkeypatch,
                                                           engine):
    monkeypatch.setenv("MTPU_ENCODE_ENGINE", engine)
    if engine == "mesh":
        from minio_tpu.parallel.mesh_engine import for_geometry
    else:
        from minio_tpu.erasure.device_engine import for_geometry
    codec = for_geometry(K, M, CODEC)
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, size=(3, K, 8192), dtype=np.uint8)
    full = np.concatenate([data, Erasure(K, M, SMALL).encode_batch(data)],
                          axis=1)

    def rebuild(lost: tuple, with_hashes: bool) -> float:
        present = tuple(i for i in range(K + M) if i not in lost)
        t0 = _traces(reg, engine)
        out, digs = codec.reconstruct_async(
            np.ascontiguousarray(full[:, list(present[:K])]), present,
            lost, with_hashes=with_hashes)
        assert np.array_equal(np.asarray(out), full[:, list(lost)]), lost
        assert (digs is not None) == with_hashes
        return _traces(reg, engine) - t0

    rebuild((0,), False)                        # builds, or finds built
    assert rebuild((5,), False) == 0            # another lost shard
    assert rebuild((0,), False) == 0
    assert rebuild((3, 9), False) <= 1          # two rows: jit's own key
    assert rebuild((2, 11), False) == 0
    rebuild((4,), True)                         # `with_hashes` is the key
    assert rebuild((6,), True) == 0


@pytest.mark.parametrize("engine", ENGINES)
def test_a_reader_that_dies_after_the_first_batch(reg, monkeypatch, engine):
    """Twenty blocks: the first reader batch is healthy, the second
    loses a data shard mid-fetch and a parity shard is read in its
    place, the third starts degraded. Two rebuilds, of 8 and of 4
    blocks, the bytes still the object's, and the caller is told to
    heal."""
    er = Erasure(K, M, SMALL)
    body = reference.payload(40, "dies", 20 * SMALL)
    shards = Shards(er, body, monkeypatch)
    monkeypatch.setenv("MTPU_ENCODE_ENGINE", engine)
    def gone_after_one(n: int) -> None:
        if n > 1:
            raise ErrFileNotFound("the drive went away")

    readers = shards.readers()
    readers[4] = _Hooked(readers[4], gone_after_one)
    rec0 = _kind(reg, engine, "reconstruct")
    blocks0 = reg.counter_value("get_reconstructed_blocks_total")
    out = io.BytesIO()
    n, hint = decode_stream(er, out, readers, 0, len(body), len(body))
    assert n == len(body) and out.getvalue() == body
    assert isinstance(hint, ErrFileNotFound)
    assert _kind(reg, engine, "reconstruct") - rec0 == 2
    assert reg.counter_value("get_reconstructed_blocks_total") \
        - blocks0 == 12


class _Scripted:
    """What the driver needs of a ParallelReader, with the shards that
    are missing scripted a block: patterns that change inside a reader
    batch, which a real fan-out never hands out."""

    saw_missing = saw_corrupt = False

    def __init__(self, er: Erasure, body: bytes, lost_by_block: list):
        self.blocks = []
        for b, lost in enumerate(lost_by_block):
            shards = er.encode_data(
                body[b * er.block_size: (b + 1) * er.block_size])
            self.blocks.append([None if i in lost else s.tobytes()
                                for i, s in enumerate(shards)])

    def read(self) -> list:
        return self.blocks.pop(0)


class _Codec:
    """Passes reconstruct_async through and keeps what it was asked."""

    def __init__(self, codec):
        self._codec, self.calls = codec, []

    def reconstruct_async(self, src, present, targets, with_hashes=False):
        self.calls.append((src.shape[0], tuple(present), tuple(targets)))
        return self._codec.reconstruct_async(src, present, targets,
                                             with_hashes=with_hashes)


@pytest.mark.parametrize("engine", ENGINES)
def test_patterns_that_change_inside_a_batch(reg, monkeypatch, engine):
    """Healthy blocks between degraded ones are written in stream order;
    a run ends where the pattern does; the ragged tail is the host's."""
    monkeypatch.setenv("MTPU_ENCODE_ENGINE", engine)
    if engine == "mesh":
        from minio_tpu.parallel.mesh_engine import for_geometry
    else:
        from minio_tpu.erasure.device_engine import for_geometry
    er = Erasure(K, M, SMALL)
    tail = 40_000                               # 5,000-byte shards
    body = reference.payload(41, "script", 10 * SMALL + tail)
    lost = [(), (1,), (1,), (), (1,), (0, 3), (0, 3), (9,),     # batch 1
            (2,), (2,), (2,)]                   # batch 2, the tail last
    geoms = [(0, SMALL)] * 10 + [(0, tail)]
    codec = _Codec(for_geometry(K, M, CODEC))
    reader = _Scripted(er, body, lost)
    out = io.BytesIO()
    app0 = sum(_kind(reg, e, "apply") for e in ("device", "native", "numpy"))
    n = streaming._decode_stream_fused(er, out, reader, geoms, lambda: None,
                                       codec, "get")
    assert n == len(body) and out.getvalue() == body

    def surv(gone: tuple) -> tuple:
        return tuple(i for i in range(K + M) if i not in gone)[:K]

    assert codec.calls == [
        (2, surv((1,)), (1,)), (1, surv((1,)), (1,)),
        (2, surv((0, 3)), (0, 3)), (2, surv((2,)), (2,))]
    # the tail block alone went through the unfused call
    assert sum(_kind(reg, e, "apply")
               for e in ("device", "native", "numpy")) - app0 == 1


def test_a_range_get_of_three_blocks_is_one_dispatch(reg, monkeypatch):
    er = Erasure(K, M, SMALL)
    body = reference.payload(42, "range", 10 * SMALL)
    shards = Shards(er, body, monkeypatch)
    monkeypatch.setenv("MTPU_ENCODE_ENGINE", "device")
    rec0 = _kind(reg, "device", "reconstruct")
    off, ln = 4 * SMALL + 17, 2 * SMALL + 100
    got, tree = _get(shards, shards.readers(lost=(0,)), off, ln)
    assert got == body[off: off + ln]
    assert _kind(reg, "device", "reconstruct") - rec0 == 1
    assert [s["label"] for s in _spans_of(tree, "stream")] == ["fused"]
    # one or two blocks stay serial, on every engine
    got, tree = _get(shards, shards.readers(lost=(0,)), off, SMALL)
    assert got == body[off: off + SMALL]
    assert [s["label"] for s in _spans_of(tree, "stream")] == ["serial"]
