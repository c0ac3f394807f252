"""The host's waits as series: the interpreter probe the span plane owns
(`interp_wait_seconds`), an object path's metadata lock on a drive
(`drive_lock_wait_seconds_total{op}`, `drive_lock_waits_total{op}`), and
`span_seconds` by label for the two span kinds whose label is a bounded
name (`rpc`, `fanout`). Nothing here is timed against a limit but the
50 ms the lock is held for."""

import io
import os
import sys
import threading
import time

import pytest

from minio_tpu.observability import spans
from minio_tpu.observability.metrics import Metrics
from minio_tpu.storage.fileinfo import ErasureInfo, FileInfo, new_uuid
from minio_tpu.storage.local import SYSTEM_TMP, LocalStorage
from minio_tpu.utils.errors import ErrFileNotFound

MIB = 1 << 20


@pytest.fixture(autouse=True)
def _clean_spans(monkeypatch):
    monkeypatch.setenv("MTPU_TRACE_SLOW_MS", "0")
    monkeypatch.delenv("MTPU_TRACE", raising=False)
    spans.reset()
    spans.set_metrics(None)
    yield
    spans.set_metrics(None)
    spans.reset()


def _probes() -> list[threading.Thread]:
    return [t for t in threading.enumerate()
            if t.name == "mtpu-interp-probe"]


def _probe_count(reg: Metrics) -> int:
    with reg._mu:
        h = reg._hists.get("interp_wait_seconds", {}).get(())
    return 0 if h is None else h[-1]


def _wait_for(cond, timeout: float = 0.5) -> bool:
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if cond():
            return True
        time.sleep(0.01)
    return cond()


# --- the interpreter probe --------------------------------------------------


def test_the_probe_observes_once_a_registry_is_installed_and_stops_with_it():
    assert not _probes()
    reg = Metrics()
    spans.set_metrics(reg)
    assert [t.daemon for t in _probes()] == [True]
    assert _wait_for(lambda: _probe_count(reg) > 0)
    text = reg.render_prometheus()
    assert "mtpu_interp_wait_seconds_count " in text
    assert "mtpu_interp_wait_seconds_sum " in text
    # a second registry takes the first one's place: still one probe
    other = Metrics()
    spans.set_metrics(other)
    assert len(_probes()) == 1
    assert _wait_for(lambda: _probe_count(other) > 0)
    spans.set_metrics(None)
    assert not _probes()
    seen = _probe_count(other)
    time.sleep(0.05)
    assert _probe_count(other) == seen


def test_the_probe_observes_nothing_while_the_plane_is_off(monkeypatch):
    monkeypatch.setenv("MTPU_TRACE", "0")
    reg = Metrics()
    spans.set_metrics(reg)
    time.sleep(0.3)
    assert _probe_count(reg) == 0
    assert "interp_wait_seconds" not in reg.render_prometheus()
    spans.set_metrics(None)
    assert not _probes()


# --- an object path's metadata lock on a drive ------------------------------


def _staged(disk: LocalStorage, key: str, body: bytes = b"shard"):
    """A version whose shard is staged under tmp, as a PUT leaves it
    before its commit; -> (tmp path, FileInfo)."""
    fi = FileInfo.new("b", key)
    fi.version_id = new_uuid()
    fi.size = len(body)
    fi.data_dir = new_uuid()
    fi.erasure = ErasureInfo(data_blocks=2, parity_blocks=2,
                             block_size=MIB, index=1,
                             distribution=[1, 2, 3, 4])
    fi.add_part(1, len(body), len(body))
    tmp = f"tmp/{new_uuid()}"
    disk.create_file(SYSTEM_TMP.split("/")[0], f"{tmp}/part.1", len(body),
                     io.BytesIO(body))
    return tmp, fi


def _lock_series(reg: Metrics, op: str) -> tuple[float, float]:
    return (reg.counter_value("drive_lock_waits_total", op=op),
            reg.counter_value("drive_lock_wait_seconds_total", op=op))


def test_the_drive_lock_counts_a_wait_and_nothing_uncontended(tmp_path):
    reg = Metrics()
    disk = LocalStorage(str(tmp_path / "d0"), endpoint="d0", metrics=reg)
    disk.make_vol("b")
    # every op's series stands at 0 from the drive's construction
    text = reg.render_prometheus()
    for op in ("rename_data", "write_metadata", "update_metadata",
               "delete_version"):
        assert f'mtpu_drive_lock_waits_total{{op="{op}"}} 0.0' in text
        assert (f'mtpu_drive_lock_wait_seconds_total{{op="{op}"}} 0.0'
                in text)
    tmp, fi = _staged(disk, "free")
    disk.rename_data(".mtpu.sys", tmp, fi, "b", "free")
    assert _lock_series(reg, "rename_data") == (0.0, 0.0)

    tmp, fi = _staged(disk, "held")
    taken = threading.Event()

    entry = os.path.join(disk.root, "b", "held")

    def hold():
        with disk._path_lock("write_metadata", "b", "held"):
            taken.set()
            # held until the commit below has come for the path's lock,
            # and 50 ms more
            _wait_for(lambda: disk._path_locks[entry][1] == 2, 5)
            time.sleep(0.05)

    holder = threading.Thread(target=hold)
    holder.start()
    assert taken.wait(5)
    disk.rename_data(".mtpu.sys", tmp, fi, "b", "held")
    holder.join(5)
    assert not holder.is_alive()
    waits, seconds = _lock_series(reg, "rename_data")
    assert waits == 1.0 and seconds >= 0.04, (waits, seconds)
    assert disk.read_version("b", "held").version_id == fi.version_id
    # the other ops did not wait
    assert _lock_series(reg, "write_metadata") == (0.0, 0.0)
    assert _lock_series(reg, "delete_version") == (0.0, 0.0)


def _hold_path(disk: LocalStorage, path: str):
    """A thread holding `path`'s lock on `disk` until the returned
    event is set (or 5 s pass); -> (thread, release event)."""
    taken, release = threading.Event(), threading.Event()

    def hold():
        with disk._path_lock("update_metadata", "b", path):
            taken.set()
            release.wait(5)

    holder = threading.Thread(target=hold)
    holder.start()
    assert taken.wait(5)
    return holder, release


def test_a_commit_to_another_path_does_not_wait_for_a_held_one(tmp_path):
    reg = Metrics()
    disk = LocalStorage(str(tmp_path / "d0"), endpoint="d0", metrics=reg)
    disk.make_vol("b")
    tmp, fi = _staged(disk, "p/B")
    holder, release = _hold_path(disk, "p/A")
    try:
        t0 = time.monotonic()
        disk.rename_data(".mtpu.sys", tmp, fi, "b", "p/B")
        took = time.monotonic() - t0
        # path A is still held: B's commit did not wait it out
        assert holder.is_alive()
    finally:
        release.set()
        holder.join(5)
    assert took < 2.5, took
    assert _lock_series(reg, "rename_data") == (0.0, 0.0)
    assert disk.read_version("b", "p/B").version_id == fi.version_id
    # the holder's entry is gone with it: the table holds what is in use
    assert disk._path_locks == {}


def test_a_late_rename_to_a_held_path_waits_for_it(tmp_path):
    """A straggler's commit that lands after its write's namespace lock
    was released, while a newer commit of the same object holds the
    path: same path, so it still waits, and both versions land in the
    one journal."""
    reg = Metrics()
    disk = LocalStorage(str(tmp_path / "d0"), endpoint="d0", metrics=reg)
    disk.make_vol("b")
    tmp, fi = _staged(disk, "k")
    holder, release = _hold_path(disk, "k")
    done = threading.Event()

    def late():
        disk.rename_data(".mtpu.sys", tmp, fi, "b", "k")
        done.set()

    straggler = threading.Thread(target=late)
    straggler.start()
    try:
        assert not done.wait(0.1)
        newer_tmp, newer = _staged(disk, "k", b"newer")
    finally:
        release.set()
        holder.join(5)
    straggler.join(5)
    assert done.is_set()
    disk.rename_data(".mtpu.sys", newer_tmp, newer, "b", "k")
    assert _lock_series(reg, "rename_data")[0] == 1.0
    assert {v.version_id for v in disk.list_versions("b", "k").versions} \
        == {fi.version_id, newer.version_id}


def test_a_prefix_s_last_delete_races_a_sibling_s_commit(tmp_path):
    """With the switch interval at 1e-5, each round deletes the last
    object under a prefix while a sibling under the same prefix is
    committed on the same drive: the delete removes the prefix's
    directory where it finds it empty, the commit creates it, and
    neither raises; every sibling reads back."""
    disk = LocalStorage(str(tmp_path / "d0"), endpoint="d0")
    disk.make_vol("b")
    rounds = 150
    last = []
    for r in range(rounds):
        tmp, fi = _staged(disk, f"p{r}/last")
        disk.rename_data(".mtpu.sys", tmp, fi, "b", f"p{r}/last")
        last.append(fi)
    sibs = [_staged(disk, f"p{r}/sib", bytes([97 + r % 26]) * 7)
            for r in range(rounds)]
    start = threading.Barrier(2)
    errors: list = []

    def deleter():
        for r, fi in enumerate(last):
            start.wait()
            try:
                disk.delete_version("b", f"p{r}/last", fi)
            except Exception as exc:  # noqa: BLE001 - for the assert
                errors.append(exc)

    def committer():
        for r, (tmp, fi) in enumerate(sibs):
            start.wait()
            try:
                disk.rename_data(".mtpu.sys", tmp, fi, "b", f"p{r}/sib")
            except Exception as exc:  # noqa: BLE001 - for the assert
                errors.append(exc)

    threads = [threading.Thread(target=deleter),
               threading.Thread(target=committer)]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(switch)
    assert not [t for t in threads if t.is_alive()]
    assert errors == []
    for r, (_, fi) in enumerate(sibs):
        got = disk.read_version("b", f"p{r}/sib")
        assert got.version_id == fi.version_id
        assert disk.read_file("b", f"p{r}/sib/{fi.data_dir}/part.1", 0,
                              fi.size) == bytes([97 + r % 26]) * 7
        with pytest.raises(ErrFileNotFound):
            disk.read_version("b", f"p{r}/last")


def test_a_prefix_removed_under_a_sibling_s_commit_is_made_again(
        tmp_path, monkeypatch):
    """The interleavings the race above can meet, forced, twice in one
    commit: the sibling's commit is about to make its own directory
    under the prefix when the delete of the prefix's last object
    removes the prefix, so the mkdir finds no parent; the commit makes
    the prefix again, and before its mkdir another object is committed
    under the prefix and deleted, which removes the prefix once more.
    The commit makes it a third time and lands."""
    disk = LocalStorage(str(tmp_path / "d0"), endpoint="d0")
    disk.make_vol("b")
    tmp, last = _staged(disk, "p/last")
    disk.rename_data(".mtpu.sys", tmp, last, "b", "p/last")
    sib_tmp, sib = _staged(disk, "p/sib")
    other_tmp, other = _staged(disk, "p/other")
    prefix = os.path.join(disk.root, "b", "p")
    sib_dir = os.path.join(prefix, "sib")
    real_mkdir, real_makedirs = os.mkdir, os.makedirs
    raced: list = []

    def mkdir(name, *args, **kwargs):
        if name == sib_dir and not raced:
            raced.append("delete")
            disk.delete_version("b", "p/last", last)
            assert not os.path.exists(prefix)
        # the parent is gone: FileNotFoundError
        return real_mkdir(name, *args, **kwargs)

    def makedirs(name, *args, **kwargs):
        real_makedirs(name, *args, **kwargs)
        if name == prefix and raced == ["delete"]:
            raced.append("commit and delete")
            disk.rename_data(".mtpu.sys", other_tmp, other, "b", "p/other")
            disk.delete_version("b", "p/other", other)
            assert not os.path.exists(prefix)

    monkeypatch.setattr(os, "mkdir", mkdir)
    monkeypatch.setattr(os, "makedirs", makedirs)
    errors: list = []

    def commit():
        try:
            disk.rename_data(".mtpu.sys", sib_tmp, sib, "b", "p/sib")
        except Exception as exc:  # noqa: BLE001 - for the assert
            errors.append(exc)

    # on a thread of its own: a delete that waited for the commit's
    # lock would wait for ever
    committer = threading.Thread(target=commit, daemon=True)
    committer.start()
    committer.join(10)
    assert not committer.is_alive()
    assert errors == []
    assert raced == ["delete", "commit and delete"]
    assert disk.read_version("b", "p/sib").version_id == sib.version_id
    assert disk.read_file("b", f"p/sib/{sib.data_dir}/part.1", 0,
                          sib.size) == b"shard"
    for gone in ("p/last", "p/other"):
        with pytest.raises(ErrFileNotFound):
            disk.read_version("b", gone)


@pytest.mark.parametrize("commit", ["rename_data", "write_metadata"])
def test_an_object_s_directory_removed_under_its_commit_is_made_again(
        tmp_path, monkeypatch, commit):
    """Objects `a` and `a/b` may both exist, so `a`'s directory can be
    another object's parent: a fresh `a` finds it there, and the delete
    of `a/b`'s last version, under `a/b`'s lock alone, removes it
    between that finding and the move of the data dir (or the write of
    the journal) into it. The commit makes the directory again and
    lands."""
    disk = LocalStorage(str(tmp_path / "d0"), endpoint="d0")
    disk.make_vol("b")
    tmp, inner = _staged(disk, "a/b")
    disk.rename_data(".mtpu.sys", tmp, inner, "b", "a/b")
    a_dir = os.path.join(disk.root, "b", "a")
    if commit == "rename_data":
        a_tmp, fi = _staged(disk, "a")
        name, into = "replace", os.path.join(a_dir, fi.data_dir)
        run = lambda: disk.rename_data(".mtpu.sys", a_tmp, fi, "b", "a")
    else:
        fi = FileInfo.new("b", "a")
        fi.version_id = new_uuid()
        fi.data = {1: b"tiny"}
        name, into = "open", os.path.join(a_dir, ".xl.meta.tmp")
        run = lambda: disk.write_metadata("b", "a", fi)
    real = getattr(os, name)
    raced: list = []

    def hooked(*args, **kwargs):
        dst = args[-1] if name == "replace" else args[0]
        if not raced and str(dst).startswith(into):
            raced.append(dst)
            disk.delete_version("b", "a/b", inner)
            assert not os.path.exists(a_dir)
        return real(*args, **kwargs)

    monkeypatch.setattr(os, name, hooked)
    errors: list = []

    def commit_a():
        try:
            run()
        except Exception as exc:  # noqa: BLE001 - for the assert
            errors.append(exc)

    # on a thread of its own: a delete that waited for the commit's
    # lock would wait for ever
    committer = threading.Thread(target=commit_a, daemon=True)
    committer.start()
    committer.join(10)
    assert not committer.is_alive()
    assert errors == []
    assert len(raced) == 1
    assert disk.read_version("b", "a").version_id == fi.version_id
    if commit == "rename_data":
        assert disk.read_file("b", f"a/{fi.data_dir}/part.1", 0,
                              fi.size) == b"shard"
    with pytest.raises(ErrFileNotFound):
        disk.read_version("b", "a/b")


def test_sixteen_committers_over_four_paths_lose_no_version(tmp_path):
    """More threads than cores commit versions of four objects on one
    drive at once with the switch interval at 1e-5: each journal holds
    every version committed to its path (a lost read-merge-write would
    drop one), and the table of path locks is empty afterwards."""
    disk = LocalStorage(str(tmp_path / "d0"), endpoint="d0")
    disk.make_vol("b")
    paths = [f"k{i}" for i in range(4)]
    work = [[(paths[(t + j) % 4], *_staged(disk, paths[(t + j) % 4]))
             for j in range(4)] for t in range(16)]
    start = threading.Barrier(len(work))
    errors: list = []

    def commit(items):
        start.wait()
        for path, tmp, fi in items:
            try:
                disk.rename_data(".mtpu.sys", tmp, fi, "b", path)
            except Exception as exc:  # noqa: BLE001 - for the assert
                errors.append(exc)

    threads = [threading.Thread(target=commit, args=(w,)) for w in work]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(switch)
    assert not [t for t in threads if t.is_alive()]
    assert errors == []
    for path in paths:
        want = {fi.version_id for items in work for p, _, fi in items
                if p == path}
        got = {v.version_id for v in disk.list_versions("b", path).versions}
        assert got == want, path
    assert disk._path_locks == {}


def test_two_writers_to_one_path_on_one_drive_leave_one_journal(tmp_path):
    """Commits of eight versions of one object on one drive at once (the
    namespace lock would keep them apart; the path's lock alone is
    what is tried here): every version lands in the one xl.meta, each
    with its data directory, and no commit raised."""
    reg = Metrics()
    disk = LocalStorage(str(tmp_path / "d0"), endpoint="d0", metrics=reg)
    disk.make_vol("b")
    staged = [_staged(disk, "same", bytes([65 + i]) * (i + 3))
              for i in range(8)]
    start = threading.Barrier(len(staged))
    errors: list = []

    def commit(tmp, fi):
        start.wait()
        try:
            disk.rename_data(".mtpu.sys", tmp, fi, "b", "same")
        except Exception as exc:  # noqa: BLE001 - collected for the assert
            errors.append(exc)

    threads = [threading.Thread(target=commit, args=s) for s in staged]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
    finally:
        sys.setswitchinterval(switch)
    assert not [t for t in threads if t.is_alive()]
    assert errors == []
    versions = disk.list_versions("b", "same").versions
    assert {v.version_id for v in versions} == \
        {fi.version_id for _, fi in staged}
    for i, (_, fi) in enumerate(staged):
        got = disk.read_version("b", "same", fi.version_id)
        assert got.size == fi.size
        part = f"same/{fi.data_dir}/part.1"
        assert disk.read_file("b", part, 0, fi.size) == \
            bytes([65 + i]) * fi.size
    # the waits that happened are counted, each one with its seconds
    waits, seconds = _lock_series(reg, "rename_data")
    assert waits <= len(staged) - 1 and (seconds > 0) == (waits > 0)


# --- span_seconds by label ---------------------------------------------------


@pytest.mark.parametrize("kind,label,want", [
    ("fanout", "hedge #3", "hedge"),
    ("fanout", "straggler-detach #11", "straggler-detach"),
    ("fanout", "all", "all"),
    ("fanout", "quorum-wait", "quorum-wait"),
    ("fanout", "shard-read-wait", "shard-read-wait"),
    ("fanout", "somewhere-else", "other"),
    ("rpc", "storage:create_file", "storage:create_file"),
    ("rpc", "storage:no_such_method", "other"),
])
def test_series_label_cuts_a_label_to_a_closed_set(kind, label, want):
    spans.name_rpc("storage", "create_file")
    assert spans.series_label(kind, label) == want


def test_a_traced_put_labels_its_fanout_series_and_no_other(tmp_path):
    from minio_tpu.api.server import LimitedReader
    from minio_tpu.object.erasure_objects import ErasureObjects
    from minio_tpu.storage.diskcheck import DiskHealth, MetricsDisk

    reg = Metrics()
    spans.set_metrics(reg)
    es = ErasureObjects(
        [MetricsDisk(LocalStorage(str(tmp_path / f"d{i}"), endpoint=f"d{i}",
                                  metrics=reg),
                     reg, health=DiskHealth(f"d{i}"))
         for i in range(4)], default_parity=2)
    es.make_bucket("b")
    body = os.urandom(3 * MIB)
    with spans.request_trace("put_object"):
        es.put_object("b", "k", LimitedReader(io.BytesIO(body), len(body)),
                      len(body))
    with spans.request_trace("get_object"):
        sink = io.BytesIO()
        es.get_object("b", "k", sink)
    assert sink.getvalue() == body
    lines = [ln for ln in reg.render_prometheus().splitlines()
             if ln.startswith("mtpu_span_seconds_count{")]
    assert ('mtpu_span_seconds_count{kind="fanout",label="quorum-wait",'
            'op="put_object"}') in "\n".join(lines)
    # the metadata quorum's wait for all of its drives
    assert ('mtpu_span_seconds_count{kind="fanout",label="all",'
            'op="get_object"}') in "\n".join(lines)
    by_kind: dict[str, list[str]] = {}
    for ln in lines:
        kind = ln.split('kind="', 1)[1].split('"', 1)[0]
        by_kind.setdefault(kind, []).append(ln)
    for kind in ("disk", "request", "object", "commit", "stream"):
        assert by_kind.get(kind), (kind, sorted(by_kind))
    for kind, rows in by_kind.items():
        labelled = [r for r in rows if "label=" in r]
        if kind in ("fanout", "rpc"):
            assert labelled == rows, kind
        else:
            assert not labelled, (kind, labelled)
