"""Fault injection over the minio_tpu/faults subsystem (promoted from
the old tests/_naughty.py; ref naughtyDisk, cmd/naughty-disk_test.go).

Scripted scenarios: a disk dying MID-STREAM between blocks of one
encode, quorum loss exactly at commit time, degraded reads under
flapping disks with ParallelReader escalation — plus the hung-drive
scenarios: a drive hanging indefinitely mid-PUT (quorum-wait fan-out
returns within deadline+grace), a slow shard beaten by a hedged parity
read, and the health circuit breaker latching then re-admitting."""

import io
import os
import time

import pytest

from minio_tpu.erasure import streaming as _streaming
from minio_tpu.faults import FaultDisk, NaughtyDisk
from minio_tpu.object.erasure_objects import ErasureObjects
from minio_tpu.storage.diskcheck import (
    DiskHealth,
    MetricsDisk,
    robust_overrides,
)
from minio_tpu.storage.local import LocalStorage
from minio_tpu.utils.errors import (
    ErrDiskFaulty,
    ErrDiskNotFound,
    ErrDiskOpTimeout,
    ErrErasureWriteQuorum,
    ErrFileNotFound,
    ErrObjectNotFound,
    StorageError,
)

MIB = 1 << 20


def _disks(tmp_path, n):
    out = [LocalStorage(str(tmp_path / f"d{i}"), endpoint=f"d{i}")
           for i in range(n)]
    for d in out:
        d.make_vol(".minio.sys")
    return out


def _get(es, bucket, obj):
    sink = io.BytesIO()
    es.get_object(bucket, obj, sink)
    return sink.getvalue()


def test_disk_dies_mid_stream_put_succeeds_on_quorum(tmp_path):
    """One disk's writer fails between block 1 and block 2 of a 3-block
    encode: the put must finish on quorum, remember the partial write in
    MRF, and heal back to full redundancy."""
    disks = _disks(tmp_path, 4)
    # Call 1 = create_file_writer, call 2 = first block write; die on the
    # second block write and every call after (disk gone for the commit).
    naughty = NaughtyDisk(
        disks[1], errors={3: ErrDiskNotFound("mid-stream death")},
        default=ErrDiskNotFound("still dead"),
    )
    es = ErasureObjects([disks[0], naughty, disks[2], disks[3]])
    es.make_bucket("flt")
    body = bytes(range(256)) * (3 * MIB // 256)  # 3 erasure blocks
    es.put_object("flt", "survivor", io.BytesIO(body), len(body))
    assert _get(es, "flt", "survivor") == body
    # partial write recorded for heal
    with es._mrf_lock:
        assert ("flt", "survivor", "") in [
            (b, o, v) for b, o, v in es._mrf
        ]
    # heal with the REAL disk back in place restores the 4th copy
    es2 = ErasureObjects(disks)
    res = es2.heal_object("flt", "survivor")
    assert res["healed"]
    ok = sum(1 for d in disks
             if _readable(d, "flt", "survivor"))
    assert ok == 4


def _readable(disk, bucket, obj) -> bool:
    try:
        disk.read_version(bucket, obj)
        return True
    except StorageError:
        return False


def test_quorum_loss_at_commit_leaves_nothing(tmp_path):
    """Shards stream fine everywhere, but rename_data fails on 2 of 4
    disks at commit: the put must fail with a write-quorum error and no
    committed object (write quorum 2+2 -> 3)."""
    disks = _disks(tmp_path, 4)

    class FailRename(NaughtyDisk):
        def __getattr__(self, name):
            if name == "rename_data":
                def boom(*a, **kw):
                    raise ErrDiskNotFound("commit failure")
                return boom
            return getattr(self._disk, name)

    es = ErasureObjects([
        disks[0], FailRename(disks[1]), FailRename(disks[2]), disks[3],
    ])
    es.make_bucket("flt")
    body = b"q" * MIB
    with pytest.raises(ErrErasureWriteQuorum):
        es.put_object("flt", "ghost", io.BytesIO(body), len(body))
    es_clean = ErasureObjects(disks)
    with pytest.raises(ErrObjectNotFound):
        es_clean.get_object_info("flt", "ghost")
    # staged tmp shards were cleaned up on every disk
    for d in disks:
        leftovers = [n for n, _ in d.walk_dir(".minio.sys", base_dir="tmp")]
        assert leftovers == []


def test_parallel_reader_escalates_under_flapping_disks(tmp_path):
    """Two disks fail their FIRST read of a GET (flap) — the parallel
    reader must escalate to the remaining shards, serve the object, and
    queue a heal hint."""
    disks = _disks(tmp_path, 4)
    es_plain = ErasureObjects(disks)
    es_plain.make_bucket("flt")
    body = bytes(reversed(range(256))) * (2 * MIB // 256)
    es_plain.put_object("flt", "flappy", io.BytesIO(body), len(body))

    # The parallel reader tries the first data_blocks readers in SHARD
    # order, which hash_order shuffles per object — compute which disk
    # holds shard 1 so the flap deterministically hits a tried reader.
    # Call 1 on that disk is the xl.meta read_version; call 2 is its
    # first shard read_file_stream — flap exactly there.
    from minio_tpu.object.metadata import hash_order

    distribution = hash_order("flt/flappy", 4)
    first_disk_idx = distribution.index(1)
    wrapped = list(disks)
    wrapped[first_disk_idx] = NaughtyDisk(
        disks[first_disk_idx], errors={2: ErrFileNotFound("flap")}
    )
    es = ErasureObjects(wrapped)
    assert _get(es, "flt", "flappy") == body
    # the failed sources left a heal hint in the MRF queue
    with es._mrf_lock:
        assert len(es._mrf) >= 1


def test_default_error_disk_is_dead_for_everything(tmp_path):
    disks = _disks(tmp_path, 4)
    dead = NaughtyDisk(disks[3], default=ErrDiskNotFound("doa"))
    es = ErasureObjects(disks[:3] + [dead])
    es.make_bucket("flt")
    body = b"d" * (256 * 1024)
    es.put_object("flt", "obj", io.BytesIO(body), len(body))
    assert _get(es, "flt", "obj") == body
    assert dead.calls > 0  # it was really consulted and really refused


def test_fresh_disk_heal_survives_flapping_source(tmp_path):
    """Back-filling a replaced drive keeps going when one SOURCE disk
    flaps mid-sweep: failures are counted, the rest of the namespace
    still heals, and the healed disk serves reads."""
    import shutil

    from minio_tpu.background.newdisk import FreshDiskHealer
    from minio_tpu.object.pools import ErasureServerPools
    from minio_tpu.object.sets import ErasureSets

    disks = [
        LocalStorage(str(tmp_path / f"d{i}"), endpoint=f"d{i}")
        for i in range(4)
    ]
    sets = ErasureSets(
        disks, 4,
        deployment_id="f1aff1af-1111-2222-3333-f1aff1aff1af",
        pool_index=0,
    )
    sets.init_format()
    ol = ErasureServerPools([sets])
    ol.make_bucket("flap")
    for i in range(10):
        body = bytes([i]) * 32768
        ol.put_object("flap", f"o{i:02d}", io.BytesIO(body), len(body))

    # Replace d3, then make d1 flap: every 5th call errors during the
    # sweep (reads from it fail intermittently; k=2 still satisfiable
    # from d0/d2).
    shutil.rmtree(str(tmp_path / "d3"))
    disks[3].__init__(str(tmp_path / "d3"), endpoint="d3")
    es = ol.pools[0].sets[0]
    flappy = NaughtyDisk(
        es.disks[1],
        errors={n: ErrDiskNotFound("flap") for n in range(5, 400, 5)},
    )
    es.disks[1] = flappy

    healer = FreshDiskHealer(ol)
    healed = healer.check_once()
    assert healed == ["d3"]

    # restore the real d1 and kill d0: reads must come from d2+d3,
    # proving the healed disk carries usable shards despite the flapping
    es.disks[1] = flappy._disk
    es.disks[0] = None
    for i in range(10):
        sink = io.BytesIO()
        ol.get_object("flap", f"o{i:02d}", sink)
        assert sink.getvalue() == bytes([i]) * 32768, i


# ---------------------------------------------------------------------------
# the faults subsystem itself


def test_registry_arms_faults_at_runtime(tmp_path):
    """A FaultDisk without a pinned schedule consults the process-wide
    registry by endpoint — the seam the admin `faults` endpoint uses to
    arm chaos on a live server."""
    import minio_tpu.faults as faults

    raw = LocalStorage(str(tmp_path / "d0"), endpoint="d0")
    raw.make_vol("v")
    raw.write_all("v", "x", b"ok")
    disk = FaultDisk(raw)  # no local schedule: registry-driven
    assert disk.read_all("v", "x") == b"ok"
    faults.arm("d0", {"specs": [{"kind": "error",
                                 "error": "ErrDiskNotFound"}]})
    try:
        assert "d0" in faults.status()
        with pytest.raises(ErrDiskNotFound):
            disk.read_all("v", "x")
    finally:
        assert faults.disarm("d0") == ["d0"]
    assert disk.read_all("v", "x") == b"ok"
    assert faults.status() == {}


def test_seeded_latency_and_bitrot_kinds(tmp_path):
    """Latency sleeps are interruptible and deterministic under a seed;
    bitrot flips read bytes so the verification layer must catch it."""
    raw = LocalStorage(str(tmp_path / "d0"), endpoint="d0")
    raw.make_vol("v")
    raw.write_all("v", "x", b"payload")
    disk = FaultDisk(raw)
    sched = disk.arm({"seed": 3, "specs": [
        {"kind": "latency", "ops": ["read_all"], "latency_s": 0.05},
    ]})
    t0 = time.monotonic()
    assert disk.read_all("v", "x") == b"payload"
    assert time.monotonic() - t0 >= 0.05
    sched.disarm()

    disk.arm({"specs": [{"kind": "bitrot", "ops": ["read_all"]}]})
    assert disk.read_all("v", "x") != b"payload"  # first byte flipped
    disk.disarm()
    assert disk.read_all("v", "x") == b"payload"


# ---------------------------------------------------------------------------
# hung-drive tolerance (quorum-wait fan-out, hedged reads, breaker)


def test_hung_writer_mid_put_returns_at_quorum(tmp_path):
    """One drive hangs indefinitely on shard writes: the PUT must return
    once write quorum + straggler grace pass (bounded by the knobs, not
    the hang), remember the missed shard in MRF, and serve reads."""
    disks = _disks(tmp_path, 4)
    faulty = FaultDisk(disks[1])
    sched = faulty.arm({"specs": [{"kind": "hang", "ops": ["shard_write"]}]})
    es = ErasureObjects([disks[0], faulty, disks[2], disks[3]])
    es.make_bucket("flt")
    body = bytes(range(256)) * (3 * MIB // 256)
    try:
        with robust_overrides(op_deadline_s=5.0, straggler_grace_s=0.3):
            t0 = time.monotonic()
            es.put_object("flt", "hungput", io.BytesIO(body), len(body))
            elapsed = time.monotonic() - t0
        # Bounded by (deadline + grace), nowhere near the infinite hang;
        # in practice quorum lands immediately and only the grace is paid.
        assert elapsed < 5.0 + 0.3, elapsed
        assert _get(es, "flt", "hungput") == body
        with es._mrf_lock:
            assert ("flt", "hungput", "") in list(es._mrf)
    finally:
        sched.disarm()
    # With the fault disarmed, heal restores the 4th shard.
    es2 = ErasureObjects(disks)
    assert es2.heal_object("flt", "hungput")["healed"]
    assert sum(1 for d in disks if _readable(d, "flt", "hungput")) == 4


def test_hedged_get_beats_hung_shard(tmp_path):
    """A drive hangs on read_file_stream for a shard the reader prefers:
    after the hedge delay a parity shard is dispatched instead, and the
    GET completes by reconstruction while the straggler is abandoned."""
    disks = _disks(tmp_path, 4)
    es_plain = ErasureObjects(disks)
    es_plain.make_bucket("flt")
    body = bytes(reversed(range(256))) * (2 * MIB // 256)
    es_plain.put_object("flt", "hedged", io.BytesIO(body), len(body))

    from minio_tpu.object.metadata import hash_order

    distribution = hash_order("flt/hedged", 4)
    slow_idx = distribution.index(1)  # the disk serving shard 1
    wrapped = list(disks)
    faulty = FaultDisk(disks[slow_idx])
    sched = faulty.arm(
        {"specs": [{"kind": "hang", "ops": ["read_file_stream"]}]}
    )
    wrapped[slow_idx] = faulty
    es = ErasureObjects(wrapped)
    hedges_before = _streaming.STATS["hedged_reads_total"]
    try:
        with robust_overrides(hedge_delay_s=0.05, long_op_deadline_s=10.0):
            t0 = time.monotonic()
            assert _get(es, "flt", "hedged") == body
            elapsed = time.monotonic() - t0
        assert elapsed < 5.0, elapsed  # the hang alone would exceed this
        assert _streaming.STATS["hedged_reads_total"] > hedges_before
    finally:
        sched.disarm()


def test_fanout_fails_fast_when_quorum_impossible():
    """Once enough writers have failed that write quorum is unreachable
    even if every straggler succeeded, the fan-out must raise NOW — not
    after burning the full op deadline on a hung writer."""
    import threading

    release = threading.Event()

    class W:
        def __init__(self, mode):
            self.mode = mode

        def write(self, _b):
            if self.mode == "fail":
                raise ErrFileNotFound("gone")
            if self.mode == "hang":
                release.wait(10)

    from minio_tpu.erasure.streaming import ParallelWriter

    writers = [W("ok"), W("hang"), W("fail"), W("fail")]
    pw = ParallelWriter(writers, 3, op_deadline_s=30.0,
                        straggler_grace_s=0.3)
    try:
        t0 = time.monotonic()
        with pytest.raises(StorageError):
            pw.write([b"x"] * 4)
        # Quorum-impossible pays one straggler grace (so settling tasks
        # report true outcomes for cleanup), never the 30s deadline.
        assert time.monotonic() - t0 < 2.0
    finally:
        release.set()


def test_breaker_latches_and_probe_readmits(tmp_path):
    """Consecutive op timeouts latch the disk faulty (ErrDiskFaulty,
    instantly — no more deadline waits); once the fault clears, the
    background probe re-admits it without a process restart."""
    raw = LocalStorage(str(tmp_path / "d0"), endpoint="d0")
    raw.make_vol("v")
    raw.write_all("v", "x", b"payload")
    faulty = FaultDisk(raw)
    with robust_overrides(op_deadline_s=0.1, long_op_deadline_s=0.1,
                          breaker_threshold=2, probe_interval_s=0.05):
        health = DiskHealth("d0")
        disk = MetricsDisk(faulty, health=health)
        assert disk.read_all("v", "x") == b"payload"  # healthy baseline
        sched = faulty.arm({"specs": [{"kind": "hang"}]})
        for _ in range(2):
            with pytest.raises(ErrDiskOpTimeout):
                disk.read_all("v", "x")
        assert health.is_faulty()
        assert disk.health_info()["state"] == "faulty"
        # Latched: fail-fast, no deadline wait burned per call.
        t0 = time.monotonic()
        with pytest.raises(ErrDiskFaulty):
            disk.read_all("v", "x")
        assert time.monotonic() - t0 < 0.05
        # Clear the fault: the probe must re-admit within a few
        # intervals (hung probe attempt releases on disarm).
        sched.disarm()
        deadline = time.monotonic() + 5.0
        while health.is_faulty() and time.monotonic() < deadline:
            time.sleep(0.02)
        assert not health.is_faulty()
        assert disk.read_all("v", "x") == b"payload"
        assert health.readmitted_total >= 1


def test_hung_drive_end_to_end_put_get_latch_readmit_heal(tmp_path):
    """Acceptance: one drive armed to hang indefinitely. A
    quorum-satisfiable PUT and GET both complete within
    (op deadline + straggler grace); the hung drive latches faulty and
    is re-admitted by the probe after disarm; the missed shard heals
    via MRF."""
    with robust_overrides(op_deadline_s=1.0, long_op_deadline_s=1.0,
                          straggler_grace_s=0.3, hedge_delay_s=0.05,
                          breaker_threshold=1, probe_interval_s=0.1):
        raw = _disks(tmp_path, 4)
        fds = [FaultDisk(d) for d in raw]
        wrapped = [MetricsDisk(fd, health=DiskHealth(f"d{i}"))
                   for i, fd in enumerate(fds)]
        es = ErasureObjects(wrapped)
        es.make_bucket("flt")
        sched = fds[1].arm({"specs": [{"kind": "hang"}]})  # every op hangs
        body = b"\xa5" * (2 * MIB)
        try:
            t0 = time.monotonic()
            es.put_object("flt", "e2e", io.BytesIO(body), len(body))
            put_s = time.monotonic() - t0
            # Writer open on the hung disk costs one op deadline, the
            # fan-outs at most grace past quorum — never the hang.
            assert put_s < 2 * (1.0 + 0.3) + 2.0, put_s
            with es._mrf_lock:
                assert ("flt", "e2e", "") in list(es._mrf)
            assert wrapped[1].health_info()["state"] == "faulty"

            t0 = time.monotonic()
            assert _get(es, "flt", "e2e") == body
            get_s = time.monotonic() - t0
            # Latched disk fails fast: the GET never waits on the hang.
            assert get_s < 1.0 + 0.3 + 1.0, get_s
        finally:
            sched.disarm()

        # Probe re-admits the disk once the fault is gone.
        deadline = time.monotonic() + 5.0
        while wrapped[1].health.is_faulty() and time.monotonic() < deadline:
            time.sleep(0.02)
        assert not wrapped[1].health.is_faulty()

        # MRF-driven heal restores the missed shard onto the drive.
        for bucket, obj, vid in es.drain_mrf():
            es.heal_object(bucket, obj, vid)
        assert sum(1 for d in raw if _readable(d, "flt", "e2e")) == 4
