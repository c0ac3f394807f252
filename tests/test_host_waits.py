"""The host's waits as series: the interpreter probe the span plane owns
(`interp_wait_seconds`), the drive's metadata lock
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


# --- the drive's metadata lock -----------------------------------------------


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

    def hold():
        with disk._lock:
            taken.set()
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


def test_two_writers_to_one_path_on_one_drive_leave_one_journal(tmp_path):
    """Commits of eight versions of one object on one drive at once (the
    namespace lock would keep them apart; the drive's lock alone is
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
