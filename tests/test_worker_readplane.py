"""Worker-pool read plane (ISSUE 11): GET decode, bitrot verify, and
heal reconstruction offloaded to the GIL-free pool must be
byte-identical to the in-process paths (including crash-fallback
mid-stream), keep the zero-payload-over-pipe copy floor, arm by
default on capable hosts (and provably never on 1-core/no-native
ones), and shut down without shm litter."""

import io
import os

import numpy as np
import pytest

from minio_tpu.erasure import streaming
from minio_tpu.erasure.bitrot import (
    BitrotAlgorithm,
    StreamingBitrotReader,
    StreamingBitrotWriter,
)
from minio_tpu.erasure.codec import Erasure
from minio_tpu.ops import gf_native
from minio_tpu.pipeline import workers
from minio_tpu.pipeline.buffers import COPY, _shared

needs_pool = pytest.mark.skipif(
    (os.cpu_count() or 1) < 2 or not gf_native.available(),
    reason="worker pool needs >=2 cores and the native engine",
)

BLOCK = 1 << 18


def test_single_core_hosts_never_arm(monkeypatch):
    """Default-on must be provably inert where it cannot help: on a
    1-core host armed() stays None (reason 'cores') regardless of the
    env knob, and the serial drivers never touch the shm pools. Runs
    everywhere — cpu_count is pinned to 1 for the probe."""
    monkeypatch.setattr(os, "cpu_count", lambda: 1)
    monkeypatch.setattr(workers, "_unsupported", None)  # re-probe
    monkeypatch.delenv("MTPU_WORKER_POOL", raising=False)
    if workers.get_pool() is not None:
        pytest.skip("pool already armed by an earlier multicore test")
    assert workers.armed() is None
    assert workers.arm_reason() == "cores"
    monkeypatch.setenv("MTPU_WORKER_POOL", "1")
    assert workers.armed() is None, "explicit opt-in must not override"


@pytest.fixture
def armed(monkeypatch):
    monkeypatch.setenv("MTPU_WORKER_POOL", "1")
    pool = workers.ensure_pool()
    assert pool is not None, "pool failed to start on a capable host"
    yield pool


def _payload(n: int, seed: int = 0) -> bytes:
    return np.random.default_rng(seed).integers(
        0, 256, n, np.uint8
    ).tobytes()


def _encode(er: Erasure, data: bytes) -> list[bytes]:
    sinks = [io.BytesIO() for _ in range(er.total_shards)]
    ws = [StreamingBitrotWriter(s, BitrotAlgorithm.HIGHWAYHASH256S)
          for s in sinks]
    n = streaming.encode_stream(er, io.BytesIO(data), ws,
                                er.data_blocks + 1)
    assert n == len(data)
    return [s.getvalue() for s in sinks]


def _readers(er: Erasure, shard_files: list, total: int, kill=()):
    rs: list = []
    for i, sf in enumerate(shard_files):
        if i in kill:
            rs.append(None)
            continue

        def open_stream(off, ln, b=sf):
            return io.BytesIO(b[off: off + ln])

        rs.append(StreamingBitrotReader(
            open_stream, er.shard_file_size(total), er.shard_size()))
    return rs


def _get(er: Erasure, shard_files: list, total: int, kill=()) -> bytes:
    out = io.BytesIO()
    n, _ = streaming.decode_stream(
        er, out, _readers(er, shard_files, total, kill), 0, total, total
    )
    assert n == total
    return out.getvalue()


def _heal(er: Erasure, shard_files: list, total: int, kill) -> dict:
    sinks = {t: io.BytesIO() for t in kill}
    ws: list = [None] * er.total_shards
    for t in kill:
        ws[t] = StreamingBitrotWriter(sinks[t],
                                      BitrotAlgorithm.HIGHWAYHASH256S)
    streaming.heal_stream(er, ws, _readers(er, shard_files, total, kill),
                          total)
    return {t: sinks[t].getvalue() for t in kill}


@pytest.mark.parametrize("k,m", [(2, 2), (8, 4), (12, 4)])
@needs_pool
def test_degraded_get_and_heal_byte_identical(armed, monkeypatch, k, m):
    """Worker-pool degraded GET (2 data shards destroyed) and heal must
    equal the in-process paths bit for bit — multi-batch and ragged-
    tail shapes, across the production geometries."""
    er = Erasure(k, m, BLOCK)
    kill = (0, 1) if k > 1 else (0,)
    for size in (BLOCK * 20 + 777, BLOCK * 3):
        data = _payload(size, seed=size % 97)
        monkeypatch.setenv("MTPU_WORKER_POOL", "off")
        shards = _encode(er, data)
        want_get = _get(er, shards, size, kill)
        want_heal = _heal(er, shards, size, kill)
        monkeypatch.setenv("MTPU_WORKER_POOL", "1")
        assert _get(er, shards, size, kill) == want_get == data, (
            f"degraded GET diverged at {k}+{m} size {size}"
        )
        assert _heal(er, shards, size, kill) == want_heal, (
            f"heal diverged at {k}+{m} size {size}"
        )


@needs_pool
def test_read_ops_actually_offload(armed, monkeypatch):
    """The read path must USE the pool: a large degraded GET counts
    decode (and, above the phys threshold, verify) worker tasks, and a
    heal counts heal tasks — not silently fall back in-process."""
    er = Erasure(2, 2, BLOCK)  # shard 128K: batch phys > WORKER_VERIFY_MIN
    size = BLOCK * 24
    monkeypatch.setenv("MTPU_WORKER_POOL", "off")
    data = _payload(size, seed=5)
    shards = _encode(er, data)
    monkeypatch.setenv("MTPU_WORKER_POOL", "1")
    before = dict(armed.tasks_by_op)
    assert _get(er, shards, size, kill=(0,)) == data
    _heal(er, shards, size, kill=(0,))
    after = armed.tasks_by_op
    for op in ("decode", "verify", "heal"):
        assert after.get(op, 0) > before.get(op, 0), (op, before, after)


@needs_pool
def test_armed_degraded_get_copy_floor(armed, monkeypatch):
    """Zero payload over the pipe: the armed degraded-GET's only copy
    sites are the framed source read and the survivor gather into the
    shm strip (get.worker_hold — the worker-plane dual of
    get.fused_gather)."""
    er = Erasure(4, 2, BLOCK)
    size = BLOCK * 20
    monkeypatch.setenv("MTPU_WORKER_POOL", "off")
    data = _payload(size, seed=17)
    shards = _encode(er, data)
    monkeypatch.setenv("MTPU_WORKER_POOL", "1")
    COPY.reset()
    assert _get(er, shards, size, kill=(0, 1)) == data
    cc = COPY.snapshot()
    assert cc.get("get.worker_hold", 0) == size, cc
    allowed = {"get.source_read", "get.worker_hold", "get.reassemble"}
    extra = {kk: v for kk, v in cc.items() if kk not in allowed and v > 0}
    assert not extra, f"armed GET grew copy sites: {extra}"


@pytest.mark.parametrize("op", ["decode", "verify", "heal"])
@needs_pool
def test_crash_midstream_falls_back_byte_identical(armed, monkeypatch, op):
    """A worker dying mid-task on ANY read op must not fail (or
    corrupt) the stream: the driver recomputes from the intact shm
    data/ring, counts a per-op fallback, and the output stays
    byte-identical."""
    er = Erasure(2, 2, BLOCK)
    size = BLOCK * 24
    monkeypatch.setenv("MTPU_WORKER_POOL", "off")
    data = _payload(size, seed=23)
    shards = _encode(er, data)
    want_heal = _heal(er, shards, size, kill=(0,))
    monkeypatch.setenv("MTPU_WORKER_POOL", "1")

    calls = {"n": 0}
    real = workers.WorkerPool._dispatch

    def flaky(self, kind, msg, wait_s=None, _test_crash=False):
        if kind == op:
            calls["n"] += 1
            if calls["n"] == 1:
                raise workers.WorkerCrashed("injected mid-stream crash")
        return real(self, kind, msg, wait_s=wait_s,
                    _test_crash=_test_crash)

    monkeypatch.setattr(workers.WorkerPool, "_dispatch", flaky)
    before = armed.fallbacks_by_op.get(op, 0)
    if op == "heal":
        assert _heal(er, shards, size, kill=(0,)) == want_heal
    else:
        assert _get(er, shards, size, kill=(0,)) == data
    assert calls["n"] >= 1, f"{op} never dispatched"
    assert armed.fallbacks_by_op.get(op, 0) == before + 1


@needs_pool
def test_shutdown_leaves_no_shm_litter(monkeypatch):
    """After read-plane traffic, shutdown must leave in_use == 0 on
    every shm strip AND ring pool, zero orphan workers, and no leaked
    /dev/shm segments from this process's pools."""
    monkeypatch.setenv("MTPU_WORKER_POOL", "1")
    pool = workers.ensure_pool()
    assert pool is not None
    er = Erasure(2, 2, BLOCK)
    size = BLOCK * 24
    data = _payload(size, seed=31)
    shards = _encode(er, data)
    assert _get(er, shards, size, kill=(0,)) == data
    _heal(er, shards, size, kill=(0,))
    pids = pool.live_pids()
    assert pids
    workers.shutdown()
    for pid in pids:
        if os.path.exists(f"/proc/{pid}"):
            with open(f"/proc/{pid}/stat") as f:
                assert f.read().split()[2] == "Z", f"orphan worker {pid}"
    for key, p in list(_shared.items()):
        if key and key[0] in ("shm-strips", "shm-rings"):
            assert p.stats()["in_use"] == 0, (key, p.stats())
    # Re-arming builds a fresh working pool (read path included).
    pool2 = workers.ensure_pool()
    assert pool2 is not None and pool2 is not pool
    assert _get(er, shards, size, kill=(0,)) == data


@needs_pool
def test_default_on_and_opt_out(monkeypatch):
    """The pool is DEFAULT-ON: with MTPU_WORKER_POOL unset, armed()
    returns a live pool on a capable host; =0 restores the PR7 opt-in
    off state without touching the running pool's streams."""
    monkeypatch.delenv("MTPU_WORKER_POOL", raising=False)
    pool = workers.armed()
    assert pool is not None and workers.arm_reason() == "armed"
    monkeypatch.setenv("MTPU_WORKER_POOL", "0")
    assert workers.armed() is None
    assert workers.arm_reason() == "env"
    monkeypatch.delenv("MTPU_WORKER_POOL", raising=False)
    assert workers.armed() is pool
