"""The drive guard under a quorum fan-out (ISSUE 34): a guarded disk op
called from a `QuorumFanout` worker runs on that worker, because the
fan-out already waits with the deadline and detaches; every other
caller keeps the hop to the drive's own executor. On fake and
`LocalStorage` drives, with short deadlines: a drive hung in a commit
still costs the request quorum + grace, still counts one timeout,
still latches, fails fast, holds its token until the hang ends and is
re-admitted by the probe; and which ops hop is counted."""

import io
import sys
import threading
import time

import pytest

from minio_tpu.object import erasure_objects as eo
from minio_tpu.object import multipart as mp
from minio_tpu.object.erasure_objects import ErasureObjects
from minio_tpu.object.types import CompletePart
from minio_tpu.observability.metrics import Metrics
from minio_tpu.storage.diskcheck import (
    ROBUST,
    DiskHealth,
    MetricsDisk,
    robust_overrides,
)
from minio_tpu.storage.local import LocalStorage
from minio_tpu.utils import fanout
from minio_tpu.utils.errors import ErrDiskFaulty, ErrDiskOpTimeout

MIB = 1 << 20
BODY = bytes(range(256)) * (2 * MIB // 256)


class Null:
    """A drive with nothing behind it: every op returns at once."""

    def __init__(self, name: str):
        self._name = name

    def endpoint(self) -> str:
        return self._name

    def is_local(self) -> bool:
        return True

    def disk_info(self):
        return {}

    def stat_vol(self, *_a):
        return object()

    def rename_data(self, *_a):
        return None

    def delete(self, *_a):
        return None


class Hanging:
    """Any drive, with a log of (op, drive, thread name) for every call
    and a set of ops that block until `release` is set."""

    def __init__(self, inner, index: int, log: list):
        self._inner = inner
        self._index = index
        self._log = log
        self.hang: set[str] = set()
        self.slow: dict[str, float] = {}
        self.release = threading.Event()

    def __getattr__(self, name):
        attr = getattr(self._inner, name)
        if not callable(attr) or name in ("endpoint", "is_local"):
            return attr

        def call(*a, **kw):
            self._log.append(
                (name, self._index, threading.current_thread().name))
            if name in self.hang:
                assert self.release.wait(60), f"{name} never released"
            if name in self.slow:
                time.sleep(self.slow[name])
            return attr(*a, **kw)

        return call


def _guarded(inners, metrics=None):
    """-> (MetricsDisk list, Hanging list, log)."""
    log: list = []
    drives = [Hanging(d, i, log) for i, d in enumerate(inners)]
    disks = [MetricsDisk(d, metrics, health=DiskHealth(d.endpoint()))
             for d in drives]
    return disks, drives, log


def _local_set(tmp_path, n: int, parity: int, metrics=None):
    inners = [LocalStorage(str(tmp_path / f"d{i}"), endpoint=f"d{i}")
              for i in range(n)]
    disks, drives, log = _guarded(inners, metrics)
    es = ErasureObjects(disks, default_parity=parity)
    es.make_bucket("b")
    return es, disks, drives, log


def _until(cond, seconds: float) -> bool:
    end = time.monotonic() + seconds
    while time.monotonic() < end:
        if cond():
            return True
        time.sleep(0.01)
    return cond()


def _hopped(log, op=None) -> int:
    """Calls that ran on a thread of a drive's own executor."""
    return sum(1 for o, _i, t in log
               if t.startswith("mtpu-dh-") and (op is None or o == op))


# ---------------------------------------------------------------------------
# the mark


def test_no_thread_is_marked_outside_an_attempt():
    assert fanout.waited() is None


def test_a_fanout_marks_its_workers_for_the_attempt_alone():
    """attempt(i) sees the fan-out's deadline on its own thread; the
    pool's thread is unmarked again afterwards, also after a raise, so
    that `_fanout`'s `Executor.map` on the same pool finds no mark."""
    seen: dict = {}

    def attempt(i):
        seen[i] = fanout.waited().deadline_s
        if i == 1:
            raise RuntimeError("attempt failed")

    errs = [None] * 4
    eo._quorum_fanout(attempt, 4, errs, 3, op_deadline_s=7.5)
    assert seen == {i: 7.5 for i in range(4)}
    assert isinstance(errs[1], RuntimeError)
    after: list = []
    eo._fanout(lambda i: after.append(fanout.waited()), 64)
    assert after == [None] * 64


def test_a_watch_set_after_the_detach_hears_of_it_at_once():
    """An attempt goes on after its fan-out left: what it starts then
    has nobody waiting for it, and is told so as it registers."""
    mark = fanout.Waited(1.0)
    heard: list = []
    mark.watch(lambda: heard.append("first"))
    mark.unwatch()
    mark.detach()
    assert heard == []
    mark.watch(lambda: heard.append("second"))
    assert heard == ["second"]


# ---------------------------------------------------------------------------
# which ops hop


@pytest.mark.parametrize("n,parity", [(16, 4), (4, 2)])
def test_a_puts_commit_makes_no_hop(tmp_path, n, parity):
    """Bucket check and PUT are 3n guarded ops. The n of the commit run
    on the fan-out's workers; the 2n on the request's thread hop, since
    nobody else could walk away from them: 48 -> 32 at 12+4, 12 -> 8 at
    2+2. The counter on /metrics says the same."""
    m = Metrics()
    es, _disks, _drives, log = _local_set(tmp_path, n, parity, m)
    del log[:]
    assert es.bucket_exists("b")
    es.put_object("b", "k", io.BytesIO(BODY), len(BODY))
    guarded = [e for e in log
               if e[0] in ("stat_vol", "create_file_writer", "rename_data")]
    assert len(guarded) == 3 * n
    assert _hopped(log) == 2 * n
    assert _hopped(log, "rename_data") == 0
    assert {t[:8] for o, _i, t in log if o == "rename_data"} == {"mtpu-obj"}
    assert m.counter_value("disk_guard_inline_total", op="rename_data") == n
    for op in ("stat_vol", "create_file_writer"):
        assert m.counter_value("disk_guard_inline_total", op=op) == 0
        assert _hopped(log, op) == n
    # every op is still counted once, on whichever thread it ran
    for op in ("stat_vol", "create_file_writer", "rename_data"):
        assert sum(m.counter_value("disk_ops_total", op=op, disk=f"d{i}")
                   for i in range(n)) == n


def test_an_op_on_the_requests_thread_still_hops_and_times_out():
    disks, drives, log = _guarded([Null("d0")], Metrics())
    drives[0].hang.add("stat_vol")
    try:
        with robust_overrides(op_deadline_s=0.2):
            t0 = time.monotonic()
            with pytest.raises(ErrDiskOpTimeout):
                disks[0].stat_vol("b")
            assert 0.2 <= time.monotonic() - t0 < 2.0
    finally:
        drives[0].release.set()
    assert _hopped(log, "stat_vol") == 1


def test_an_op_under_the_plain_fanout_still_hops_and_times_out():
    """`Executor.map` waits for every task and has no deadline of its
    own: the hop is its deadline. The pool's threads have just served a
    quorum fan-out, and carry nothing over from it."""
    disks, drives, log = _guarded([Null(f"d{i}") for i in range(4)])
    eo._quorum_fanout(lambda i: disks[i].rename_data(), 4, [None] * 4, 3)
    assert _hopped(log) == 0
    drives[1].hang.add("stat_vol")
    errs: list = [None] * 4

    def do(i):
        try:
            disks[i].stat_vol("b")
        except Exception as exc:  # noqa: BLE001 - collected
            errs[i] = exc

    try:
        with robust_overrides(op_deadline_s=0.2):
            t0 = time.monotonic()
            eo._fanout(do, 4)
            assert 0.2 <= time.monotonic() - t0 < 2.0
    finally:
        drives[1].release.set()
    assert [type(e) for e in errs] == [type(None), ErrDiskOpTimeout,
                                       type(None), type(None)]
    assert _hopped(log, "stat_vol") == 4


@pytest.mark.parametrize("op,fan_deadline_s,hops", [
    ("rename_data", None, 0),      # the fan-out's deadline is the op's
    ("rename_data", 1.0, 0),       # shorter: the fan-out leaves first
    ("rename_data", 60.0, 4),      # longer: the hop is the sooner guard
    ("delete", None, 0),           # a long op under the usual fan-out
])
def test_the_hop_goes_only_where_the_fanout_waits_no_longer(
        op, fan_deadline_s, hops):
    m = Metrics()
    disks, _drives, log = _guarded([Null(f"d{i}") for i in range(4)], m)
    assert ROBUST.op_deadline_s == 30.0
    errs: list = [None] * 4
    eo._quorum_fanout(lambda i: getattr(disks[i], op)(), 4, errs, 3,
                      op_deadline_s=fan_deadline_s)
    assert errs == [None] * 4
    assert _hopped(log, op) == hops
    assert m.counter_value("disk_guard_inline_total", op=op) == 4 - hops


# ---------------------------------------------------------------------------
# a drive hung in a commit


def _spy_errs(monkeypatch) -> list:
    """Every errs list a commit reduces, as it stood then."""
    seen: list = []
    for mod in (eo, mp):
        real = mod.reduce_write_quorum_errs

        def spy(errs, *a, _real=real, **kw):
            seen.append(list(errs))
            return _real(errs, *a, **kw)

        monkeypatch.setattr(mod, "reduce_write_quorum_errs", spy)
    return seen


@pytest.mark.parametrize("path", ["put", "delete", "multipart"])
def test_a_drive_hung_in_a_commit(tmp_path, monkeypatch, path):
    """One of four drives hangs in the commit's op (and in the probe's
    `disk_info`: a hung drive answers nothing). The request returns
    with quorum inside deadline + grace; the drive's slot is stamped
    `ErrDiskOpTimeout`; the object is queued for heal; when the op's
    own deadline has passed one timeout is counted and the breaker
    (threshold 1) latches; the next op fails fast; the token is held
    until the hang ends; then the probe re-admits the drive."""
    m = Metrics()
    es, disks, drives, log = _local_set(tmp_path, 4, 2, m)
    seen = _spy_errs(monkeypatch)
    hung, health = drives[1], disks[1].health
    deadline_s, grace_s = 1.0, 0.2
    with robust_overrides(op_deadline_s=deadline_s,
                          straggler_grace_s=grace_s, breaker_threshold=1,
                          probe_interval_s=0.05):
        if path == "put":
            # (the process's first PUT pays for loading the codec)
            es.put_object("b", "warm", io.BytesIO(BODY), len(BODY))
            op, run = "rename_data", lambda: es.put_object(
                "b", "k", io.BytesIO(BODY), len(BODY))
        elif path == "delete":
            es.put_object("b", "k", io.BytesIO(BODY), len(BODY))
            op, run = "delete_version", lambda: es.delete_object("b", "k")
        else:
            up = es.new_multipart_upload("b", "k")
            part = es.put_object_part("b", "k", up, 1, io.BytesIO(BODY),
                                      len(BODY))
            op, run = "rename_data", lambda: es.complete_multipart_upload(
                "b", "k", up, [CompletePart(1, part.etag)])
        es.drain_mrf()
        del log[:], seen[:]
        ops_before = m.counter_value("disk_ops_total", op=op, disk="d1")
        hung.hang |= {op, "disk_info"}
        try:
            t0 = time.monotonic()
            run()
            elapsed = time.monotonic() - t0
            assert elapsed < deadline_s + grace_s, elapsed
            # the commit's ops ran on the fan-out's workers, all four
            ran = [t for o, _i, t in log if o == op]
            assert len(ran) == 4 and _hopped(log, op) == 0, ran
            # (slots are by shard for a PUT, by drive for a DELETE)
            stamped = [e for e in seen[-1] if e is not None]
            assert [type(e) for e in stamped] == [ErrDiskOpTimeout]
            assert ("b", "k", "") in es.drain_mrf()
            # a detach is no timeout yet: the op has its own deadline
            assert m.counter_value("disk_op_timeouts_total", op=op,
                                   disk="d1") == 0
            assert not health.is_faulty()
            assert health.inflight == 1
            assert _until(health.is_faulty, deadline_s + 2.0)
            assert time.monotonic() - t0 >= deadline_s
            assert m.counter_value("disk_op_timeouts_total", op=op,
                                   disk="d1") == 1
            assert m.counter_value("disk_faulty_total", disk="d1") == 1
            # latched: no deadline is waited for any more
            t1 = time.monotonic()
            with pytest.raises(ErrDiskFaulty):
                disks[1].stat_vol("b")
            assert time.monotonic() - t1 < 0.1
            # the token is the hung call's until it returns, and the
            # probe, hung too, re-admits nothing
            time.sleep(0.2)
            assert health.inflight == 1 and health.is_faulty()
        finally:
            hung.release.set()
        assert _until(lambda: health.inflight == 0, 5.0)
        assert _until(lambda: not health.is_faulty(), 5.0)
        assert m.counter_value("disk_readmit_total", disk="d1") == 1
        disks[1].stat_vol("b")
    # written off once: its late return added no second count
    assert m.counter_value("disk_op_timeouts_total", op=op, disk="d1") == 1
    assert (m.counter_value("disk_ops_total", op=op, disk="d1")
            == ops_before + 1)
    assert m.counter_value("disk_op_errors_total", op=op, disk="d1") == 1


def test_the_breaker_counts_commits_in_a_row():
    """Fake drives, threshold 3: each fan-out that leaves the hung
    drive behind counts one timeout when the op's deadline has passed;
    the third latches; the fourth finds the drive failing fast."""
    m = Metrics()
    disks, drives, _log = _guarded([Null(f"d{i}") for i in range(4)], m)
    hung, health = drives[1], disks[1].health
    hung.hang |= {"rename_data", "disk_info"}

    def timeouts():
        return m.counter_value("disk_op_timeouts_total", op="rename_data",
                               disk="d1")

    try:
        with robust_overrides(op_deadline_s=0.3, straggler_grace_s=0.05,
                              breaker_threshold=3, probe_interval_s=30.0):
            for k in (1, 2, 3):
                assert not health.is_faulty()
                errs: list = [None] * 4
                eo._quorum_fanout(lambda i: disks[i].rename_data(), 4,
                                  errs, 3)
                assert isinstance(errs[1], ErrDiskOpTimeout)
                assert _until(lambda: timeouts() == k, 3.0)
                assert health.inflight == k
            assert health.is_faulty()
            assert m.counter_value("disk_faulty_total", disk="d1") == 1
            errs = [None] * 4
            t0 = time.monotonic()
            eo._quorum_fanout(lambda i: disks[i].rename_data(), 4, errs, 3)
            assert time.monotonic() - t0 < 0.25
            assert isinstance(errs[1], ErrDiskFaulty)
            assert timeouts() == 3
    finally:
        hung.release.set()
    assert _until(lambda: health.inflight == 0, 5.0)


def test_a_straggler_that_makes_its_deadline_is_no_timeout():
    """A drive slower than quorum + grace is left behind by the
    fan-out, as before; its op still ends inside its own deadline, so
    it is an op like any other and the watch's timer is called off."""
    m = Metrics()
    disks, drives, _log = _guarded([Null(f"d{i}") for i in range(4)], m)
    drives[2].slow["rename_data"] = 0.4
    with robust_overrides(op_deadline_s=5.0, straggler_grace_s=0.05):
        errs: list = [None] * 4
        t0 = time.monotonic()
        eo._quorum_fanout(lambda i: disks[i].rename_data(), 4, errs, 3)
        assert time.monotonic() - t0 < 0.35
        assert isinstance(errs[2], ErrDiskOpTimeout)
        assert _until(lambda: disks[2].health.inflight == 0, 5.0)
    assert _until(lambda: m.counter_value(
        "disk_ops_total", op="rename_data", disk="d2") == 1, 2.0)
    assert m.counter_value("disk_op_timeouts_total", op="rename_data",
                           disk="d2") == 0
    assert m.counter_value("disk_op_errors_total", op="rename_data",
                           disk="d2") == 0
    assert disks[2].health.state()["consecutiveTimeouts"] == 0
    assert _until(lambda: not [t for t in threading.enumerate()
                               if t.name == "mtpu-dh-watch"], 2.0)


def test_detach_and_return_race_and_every_op_is_counted_once():
    """Ops that last about as long as the fan-out waits: the detach,
    the deadline's timer and the op's return race on every round, with
    more threads than cores and a short switch interval. Whoever wins,
    each op is counted once, as done or as timed out, and every token
    comes back."""
    m = Metrics()
    disks, drives, _log = _guarded([Null(f"d{i}") for i in range(4)], m)
    for d in drives:
        d.slow["rename_data"] = 0.004
    rounds, clients = 25, 12
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with robust_overrides(op_deadline_s=0.004, straggler_grace_s=0.0,
                              breaker_threshold=1 << 30):

            def client():
                for _ in range(rounds):
                    eo._quorum_fanout(lambda i: disks[i].rename_data(), 4,
                                      [None] * 4, 4)

            threads = [threading.Thread(target=client)
                       for _ in range(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert _until(lambda: all(d.health.inflight == 0 for d in disks), 10.0)
    started = sum(1 for o, _i, _t in _log if o == "rename_data")
    assert _until(lambda: sum(
        m.counter_value("disk_ops_total", op="rename_data", disk=f"d{i}")
        for i in range(4)) == started, 5.0)
    timeouts = sum(m.counter_value("disk_op_timeouts_total",
                                   op="rename_data", disk=f"d{i}")
                   for i in range(4))
    errors = sum(m.counter_value("disk_op_errors_total", op="rename_data",
                                 disk=f"d{i}") for i in range(4))
    assert errors == timeouts
    assert sum(d.health.timeouts_total for d in disks) == timeouts
