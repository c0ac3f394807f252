"""What a PUT does at its drives around the stream (ISSUE 28 left these
calls as they were: in turn, on the request's thread). On fake drives:
the bucket check's answer for every mix of drives, and that it asks
them every time; a PUT or a part whose drives fail at the open holds
write quorum or leaves nothing staged behind; every sink is closed and
synced before the first rename; the commit alone goes through the
pool; the request's tree keeps every disk record."""

import io
import os
import threading

import pytest

from minio_tpu.object.erasure_objects import ErasureObjects
from minio_tpu.observability import spans
from minio_tpu.storage import local as local_storage
from minio_tpu.storage.diskcheck import DiskHealth, MetricsDisk
from minio_tpu.storage.local import SYSTEM_META_BUCKET, LocalStorage
from minio_tpu.utils.errors import ErrDiskNotFound, ErrErasureWriteQuorum, ErrVolumeNotFound

MIB = 1 << 20


# ---------------------------------------------------------------------------
# the bucket check, on drives that are nothing but an answer


class Vol:
    """A drive that knows one thing: what it says to stat_vol."""

    def __init__(self, answer: str):
        self.answer = answer
        self.asked = 0

    def is_local(self) -> bool:
        return True

    def stat_vol(self, bucket: str):
        self.asked += 1
        if self.answer == "absent":
            raise ErrVolumeNotFound(bucket)
        if self.answer == "raising":
            raise RuntimeError("drive on fire")
        return object()


def _vols(mix: str) -> list:
    """'P' present, 'a' absent, 'r' raising, '-' no drive."""
    kinds = {"P": "present", "a": "absent", "r": "raising"}
    return [None if c == "-" else Vol(kinds[c]) for c in mix]


@pytest.mark.parametrize("mix", [
    "PPPPPPPPPPPPPPPP",      # all present
    "aaaaaaaaaaaaaaaa",      # all absent
    "----------------",      # no drive at all
    "PPPPPPPPaaaaaaaa",      # exactly n // 2
    "PPPPPPPaaaaaaaaa",      # one short of it
    "PPPPPPPPPaaaaaaa",      # one over
    "aaaaaaaaPPPPPPPP",      # the present ones answer last
    "PPPPPPPPrrrrrrrr",      # threshold met, the rest raise
    "PPPPPPPrrrrraaaa",      # one short, raising and absent mixed
    "PPPPPPPP--------",      # threshold met by every live drive
    "PPPPPPP---------",      # one short, nothing left to ask
    "P-aPr-PPaPrP-PPa",      # 8 of 16, scattered
    "P-aPr-PPaPrP-aPa",      # 7 of 16, scattered
    "PPaaa",                 # odd set: n // 2 = 2
    "Paaaa",
    "Pa",                    # the smallest set: n // 2 = 1
    "-a",
])
def test_bucket_exists_is_half_of_the_sets_drives(mix):
    """Present on `n // 2` of the set's slots; an empty slot, an absent
    volume and a drive that raises all count as absent."""
    es = ErasureObjects(_vols(mix))
    assert es.bucket_exists("b") is (mix.count("P") >= len(mix) // 2)


def test_bucket_exists_asks_the_drives_every_time():
    """No cache: a bucket that was there a moment ago and is gone now is
    refused by the very next call."""
    disks = _vols("PPPPPPPPPPPPPPPP")
    es = ErasureObjects(disks)
    assert es.bucket_exists("b")
    asked = sum(d.asked for d in disks)
    assert asked == 16
    for d in disks:
        d.answer = "absent"
    assert not es.bucket_exists("b")
    assert sum(d.asked for d in disks) == 2 * asked


# ---------------------------------------------------------------------------
# PUT and multipart, on real directories behind a drive that misbehaves


class Drive:
    """A LocalStorage that fails the named ops and logs, in order, every
    named call it gets, the closes of its sinks included."""

    LOGGED = ("stat_vol", "create_file_writer", "rename_data", "rename_file")

    def __init__(self, inner, index: int, log: list, fail=()):
        self._inner = inner
        self._index = index
        self._log = log
        self._fail = set(fail)

    def _note(self, what: str):
        self._log.append((what, self._index, threading.get_ident()))

    def __getattr__(self, name):
        attr = getattr(self._inner, name)
        if name not in self.LOGGED:
            return attr

        def call(*a, **kw):
            self._note(name)
            if name in self._fail:
                raise ErrDiskNotFound(f"drive {self._index} fails {name}")
            out = attr(*a, **kw)
            if name == "create_file_writer":
                return _Sink(out, self)
            return out

        return call


class _Sink:
    def __init__(self, inner, drive: Drive):
        self._inner = inner
        self._drive = drive
        for name in ("write", "writev", "fileno"):
            if hasattr(inner, name):
                setattr(self, name, getattr(inner, name))

    def close(self):
        self._inner.close()
        self._drive._note("closed")


def _set(tmp_path, n: int, parity: int, *, fail=(), bad=0, fsync=False,
         health=False):
    """n drives of which the first `bad` misbehave; -> (set, drives, log)."""
    log: list = []
    drives = [
        Drive(LocalStorage(str(tmp_path / f"d{i}"), endpoint=f"d{i}",
                           fsync=fsync),
              i, log, fail=fail if i < bad else ())
        for i in range(n)
    ]
    disks = ([MetricsDisk(d, health=DiskHealth(f"d{i}"))
              for i, d in enumerate(drives)] if health else drives)
    es = ErasureObjects(disks, default_parity=parity)
    es.make_bucket("b")
    return es, drives, log


def _staged(tmp_path, n: int) -> list:
    """What is left under the drives' tmp and multipart staging."""
    left = []
    for i in range(n):
        for sub in ("tmp", "multipart"):
            top = tmp_path / f"d{i}" / SYSTEM_META_BUCKET / sub
            for root, _dirs, files in os.walk(top):
                left += [os.path.join(root, f) for f in files
                         if ".tmp." in f or sub == "tmp"]
    return left


def _get(es, key: str) -> bytes:
    sink = io.BytesIO()
    es.get_object("b", key, sink)
    return sink.getvalue()


BODY = bytes(range(256)) * (2 * MIB // 256)


@pytest.mark.parametrize("api", ["put", "part"])
def test_parity_drives_failing_at_open_still_commit(tmp_path, api):
    """4+2, two drives refuse create_file_writer: the stream holds write
    quorum over the four left, and the object is queued for heal."""
    es, _drives, log = _set(tmp_path, 6, 2, fail=("create_file_writer",),
                            bad=2)
    if api == "put":
        es.put_object("b", "k", io.BytesIO(BODY), len(BODY))
        with es._mrf_lock:
            assert ("b", "k", "") in list(es._mrf)
    else:
        up = es.new_multipart_upload("b", "k")
        part = es.put_object_part("b", "k", up, 1, io.BytesIO(BODY),
                                  len(BODY))
        from minio_tpu.object.types import CompletePart

        es.complete_multipart_upload("b", "k", up,
                                     [CompletePart(1, part.etag)])
    assert _get(es, "k") == BODY
    assert sorted(i for what, i, _ in log
                  if what == "create_file_writer") == list(range(6))
    assert sorted(i for what, i, _ in log if what == "closed") == [2, 3, 4, 5]


@pytest.mark.parametrize("api", ["put", "part"])
def test_more_than_parity_failing_at_open_is_a_quorum_error(tmp_path, api):
    """Three of 4+2 refuse: write quorum is lost, and nothing staged is
    left behind on any drive."""
    es, _drives, log = _set(tmp_path, 6, 2, fail=("create_file_writer",),
                            bad=3)
    with pytest.raises(ErrErasureWriteQuorum):
        if api == "put":
            es.put_object("b", "k", io.BytesIO(BODY), len(BODY))
        else:
            up = es.new_multipart_upload("b", "k")
            es.put_object_part("b", "k", up, 1, io.BytesIO(BODY), len(BODY))
    assert _staged(tmp_path, 6) == []
    assert not [e for e in log if e[0] in ("rename_data", "rename_file")]
    # the three sinks that did open were closed on the way out
    assert sorted(i for what, i, _ in log if what == "closed") == [3, 4, 5]


@pytest.mark.parametrize("fsync", [False, True])
def test_every_sink_is_closed_before_the_first_rename(tmp_path, monkeypatch,
                                                      fsync):
    """The durability point stays where it was: all six staged shards
    are closed (and, on a drive that syncs, fsynced) before any drive's
    rename_data starts."""
    es, _drives, log = _set(tmp_path, 6, 2, fsync=fsync)
    real_fsync = os.fsync

    def fsync_logged(fd):
        log.append(("fsync", -1, threading.get_ident()))
        return real_fsync(fd)

    monkeypatch.setattr(local_storage.os, "fsync", fsync_logged)
    es.put_object("b", "k", io.BytesIO(BODY), len(BODY))
    kinds = [what for what, _i, _t in log]
    before = kinds[:kinds.index("rename_data")]
    assert before.count("closed") == 6
    assert kinds.count("closed") == 6
    assert kinds.count("rename_data") == 6
    if fsync:
        assert before.count("fsync") >= 6
    assert _get(es, "k") == BODY


def test_the_commit_alone_leaves_the_callers_thread(tmp_path):
    """The bucket check, the opens and the closes run on the caller's
    thread, one drive after the other; the commit's renames go through
    the fan-out pool, one task a drive, on any host."""
    es, _drives, log = _set(tmp_path, 6, 2)
    del log[:]
    assert es.bucket_exists("b")
    es.put_object("b", "k", io.BytesIO(BODY), len(BODY))
    me = threading.get_ident()
    for what in ("stat_vol", "create_file_writer", "closed", "rename_data"):
        calls = [(i, t) for w, i, t in log if w == what]
        assert len(calls) == 6, (what, calls)
        on_caller = {t for _i, t in calls} == {me}
        assert on_caller == (what != "rename_data"), what
    # in turn: by drive for the check, by shard for the opens and closes
    assert [i for w, i, _ in log if w == "stat_vol"] == list(range(6))
    opened = [i for w, i, _ in log if w == "create_file_writer"]
    assert [i for w, i, _ in log if w == "closed"] == opened
    assert _get(es, "k") == BODY


def test_inline_object_calls_no_drive_before_the_commit(tmp_path):
    """A small object's shards ride inside xl.meta: nothing is opened
    and nothing closed."""
    es, _drives, log = _set(tmp_path, 6, 2)
    del log[:]
    es.put_object("b", "small", io.BytesIO(b"x" * 1000), 1000)
    assert not [e for e in log if e[0] in ("create_file_writer", "closed")]
    assert _get(es, "small") == b"x" * 1000


# ---------------------------------------------------------------------------
# what the request's tree shows


@pytest.fixture
def captured(monkeypatch):
    monkeypatch.setenv("MTPU_TRACE_SLOW_MS", "0")
    monkeypatch.delenv("MTPU_TRACE", raising=False)
    spans.reset()
    yield
    spans.reset()


def test_the_tree_keeps_every_disk_record(tmp_path, captured):
    """16 drives behind the health wrapper, one request: the check and
    the PUT. Every per-drive call is a `disk` record under the root,
    whichever thread made it: the check and the opens on the request's
    own, the shard writes and the renames on the pools'."""
    es, _drives, _log = _set(tmp_path, 16, 4, health=True)
    body = bytes(range(256)) * (3 * MIB // 256)
    with spans.request_trace("put_object"):
        assert es.bucket_exists("b")
        es.put_object("b", "k", io.BytesIO(body), len(body))
    tree = spans.slow_requests()[-1]
    disk = [s["label"] for s in tree["spans"] if s["kind"] == "disk"]
    for op in ("stat_vol", "create_file_writer", "rename_data"):
        assert (sorted(n for n in disk if n.startswith(op + ":"))
                == sorted(f"{op}:d{i}" for i in range(16))), op
