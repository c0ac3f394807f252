"""The span plane from the S3 body reader down to the device engine
(ISSUE 27): on the CPU, with the device engine forced, one 10 MiB 12+4
PUT, one 1 MiB 2+2 PUT and one heal each yield a tree that holds every
layer-boundary kind, nested rightly; device-call spans count the
dispatches; `codec_trace_total` counts traces; `span_seconds` carries
`kind` and `op`; and the twins on the profiler's clock never nest on a
thread. Nothing here is timed."""

import io
import json
import os
import shutil
import subprocess
import sys
import threading

import pytest

from minio_tpu.api.server import LimitedReader
from minio_tpu.erasure import registry
from minio_tpu.observability import spans
from minio_tpu.observability.metrics import Metrics

MIB = 1 << 20
# what every traced PUT or heal of the device engine has to show
LAYER_KINDS = {"request", "object", "stream", "device-h2d", "device-call",
               "device-wait", "commit", "disk"}


class Set:
    """An erasure set over health-wrapped tmpfs drives, as the server
    builds them (the wrapper is what records `disk` spans)."""

    def __init__(self, root, drives: int, parity: int, reg: Metrics):
        from minio_tpu.object.erasure_objects import ErasureObjects
        from minio_tpu.storage.diskcheck import DiskHealth, MetricsDisk
        from minio_tpu.storage.local import LocalStorage

        self.root = str(root)
        self.es = ErasureObjects(
            [MetricsDisk(LocalStorage(os.path.join(self.root, f"d{i}"),
                                      endpoint=f"d{i}"),
                         reg, health=DiskHealth(f"d{i}"))
             for i in range(drives)], default_parity=parity)
        self.es.make_bucket("b")

    def put(self, key: str, size: int) -> dict:
        """One PUT under a request root, its body behind the server's own
        reader; -> the tree."""
        body = os.urandom(size)
        with spans.request_trace("put_object"):
            self.es.put_object("b", key, LimitedReader(io.BytesIO(body),
                                                       size), size)
        return spans.slow_requests()[-1]

    def wipe(self, *drives: int) -> None:
        for d in drives:
            path = os.path.join(self.root, f"d{d}", "b")
            shutil.rmtree(path)
            os.makedirs(path)


@pytest.fixture(scope="module")
def plane():
    """The device engine on the CPU, one registry behind the spans and
    the codec counters, every finished tree captured; a 16-drive 12+4
    set and a 4-drive 2+2 set, compiled once for the module."""
    import tempfile

    saved = {k: os.environ.get(k) for k in
             ("MTPU_ENCODE_ENGINE", "MTPU_CODEC", "MTPU_TRACE_SLOW_MS",
              "MTPU_TRACE")}
    os.environ.update(MTPU_ENCODE_ENGINE="device", MTPU_CODEC="dense-gf8",
                      MTPU_TRACE_SLOW_MS="0")
    os.environ.pop("MTPU_TRACE", None)
    reg = Metrics()
    old_codec_reg = registry._reg()
    registry.set_metrics(reg)
    spans.reset()
    spans.set_metrics(reg)
    tmpfs = "/dev/shm" if os.access("/dev/shm", os.W_OK) else None
    root = tempfile.mkdtemp(prefix="mtpu-spans-", dir=tmpfs)
    try:
        yield {"reg": reg,
               "n16": Set(os.path.join(root, "n16"), 16, 4, reg),
               "n4": Set(os.path.join(root, "n4"), 4, 2, reg)}
    finally:
        shutil.rmtree(root, ignore_errors=True)
        spans.set_metrics(None)
        spans.reset()
        registry.set_metrics(old_codec_reg)
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _dispatches(reg: Metrics) -> float:
    return reg.counter_value("mtpu_codec_dispatch_total", codec="dense-gf8",
                             engine="device")


def _traces(reg: Metrics) -> float:
    return reg.counter_value("codec_trace_total", codec="dense-gf8",
                             engine="device")


def _assert_nested(tree: dict) -> None:
    """One root; every child lies inside its parent; the children that
    ran on their parent's thread, one after the other, sum to no more
    than the parent (the rest is the parent's self time)."""
    by_id = {s["id"]: s for s in tree["spans"]}
    roots = [s for s in tree["spans"] if s["parent"] == 0]
    assert [r["kind"] for r in roots] == ["request"], roots
    slack = 2                                   # start and duration floor to us
    same_thread: dict[int, int] = {}
    for s in tree["spans"]:
        if s["parent"] == 0:
            continue
        parent = by_id[s["parent"]]
        assert s["start_us"] >= parent["start_us"] - slack, (s, parent)
        assert (s["start_us"] + s["duration_us"]
                <= parent["start_us"] + parent["duration_us"] + slack), \
            (s, parent)
        if s["thread"] == parent["thread"]:
            same_thread[parent["id"]] = (same_thread.get(parent["id"], 0)
                                         + s["duration_us"])
    for pid, total in same_thread.items():
        n = sum(1 for s in tree["spans"] if s["parent"] == pid)
        assert total <= by_id[pid]["duration_us"] + slack * n, by_id[pid]


def _kinds(tree: dict) -> dict[str, list[dict]]:
    out: dict[str, list[dict]] = {}
    for s in tree["spans"]:
        out.setdefault(s["kind"], []).append(s)
    return out


def test_put_10mib_12p4_tree_holds_every_layer(plane):
    n16, reg = plane["n16"], plane["reg"]
    n16.put("warm", 10 * MIB)                   # compiles both batch shapes
    d0, t0 = _dispatches(reg), _traces(reg)
    tree = n16.put("k", 10 * MIB)
    kinds = _kinds(tree)
    want = LAYER_KINDS | {"body-read", "admission", "stage"}
    assert want <= set(kinds), want - set(kinds)
    _assert_nested(tree)
    assert tree["api"] == "put_object" and "stats" not in tree
    assert [s["label"] for s in kinds["object"]] == ["put"]
    assert [s["label"] for s in kinds["stream"]] == ["batched_pipelined"]
    # 10 blocks: a batch of 8 and a batch of 2, one dispatch each
    assert len(kinds["device-call"]) == _dispatches(reg) - d0 == 2
    assert {s["label"] for s in kinds["device-call"]} == {"enc"}
    assert len(kinds["device-h2d"]) == len(kinds["device-wait"]) == 2
    assert len(kinds["body-read"]) >= 10
    assert _traces(reg) == t0, "a warm PUT traced a function"
    # the object layer's three phases are its children, in that order
    obj = kinds["object"][0]
    phases = [s for s in tree["spans"] if s["parent"] == obj["id"]
              and s["kind"] in ("admission", "stream", "commit")]
    assert [s["kind"] for s in phases] == ["admission", "stream", "commit"]


def test_put_10mib_fans_its_shard_writes_out_once_a_batch(plane):
    """The pipelined device driver hands each drive its shard's frames of
    a whole batch in one task (ISSUE 28): a 10 MiB PUT waits for three
    quorums (the 8-block batch, the 2-block batch, the commit), not for
    eleven, and what it wrote reads back bitrot-verified."""
    n16 = plane["n16"]
    body = os.urandom(10 * MIB)
    with spans.request_trace("put_object"):
        n16.es.put_object("b", "batched", LimitedReader(io.BytesIO(body),
                                                        len(body)), len(body))
    tree = spans.slow_requests()[-1]
    waits = [s for s in _kinds(tree)["fanout"] if s["label"] == "quorum-wait"]
    assert len(waits) == 3, [s["label"] for s in _kinds(tree)["fanout"]]
    sink = io.BytesIO()
    n16.es.get_object("b", "batched", sink)
    assert sink.getvalue() == body


def test_put_1mib_2p2_runs_inline_and_is_not_dark(plane):
    n4, reg = plane["n4"], plane["reg"]
    n4.put("warm", MIB)
    d0 = _dispatches(reg)
    tree = n4.put("k", MIB)
    kinds = _kinds(tree)
    assert LAYER_KINDS | {"body-read", "admission"} <= set(kinds), \
        set(kinds)
    _assert_nested(tree)
    # one block never builds a Pipeline: no stage, and still every
    # device phase is on the tree
    assert [s["label"] for s in kinds["stream"]] == ["inline"]
    assert "stage" not in kinds
    assert len(kinds["device-call"]) == _dispatches(reg) - d0 == 1


def test_heal_is_its_own_root_and_counts_traces_per_failure_pattern(
        plane, monkeypatch):
    from minio_tpu.erasure.device_engine import DeviceCodec

    n16, reg = plane["n16"], plane["reg"]
    for key in ("h1", "h2"):
        n16.put(key, 10 * MIB)
    # what a trace is keyed on: the batch's shape, the matrix's rows (the
    # target count) and `with_hashes`. The failure pattern (which shards
    # are rebuilt from which survivors; a hedged read may change them from
    # one batch to the next) is the matrix, an argument
    seen: set = set()
    fresh: list = []
    patterns: set = set()
    recon = DeviceCodec.reconstruct_async

    def logged(self, src, present, targets, with_hashes=False):
        key = (src.shape, len(targets), with_hashes)
        patterns.add((tuple(present[: self.k]), tuple(targets)))
        if key not in seen:
            seen.add(key)
            fresh.append(key)
        return recon(self, src, present, targets, with_hashes)

    monkeypatch.setattr(DeviceCodec, "reconstruct_async", logged)

    def heal(key: str) -> tuple[dict, float, int]:
        spans.clear_slow_requests()
        fresh.clear()
        t0 = _traces(reg)
        out = n16.es.heal_object("b", key)
        assert len(out["healed"]) == 2, out
        trees = spans.slow_requests()
        assert [t["api"] for t in trees] == ["heal_object"], trees
        return trees[0], _traces(reg) - t0, len(fresh)

    n16.wipe(3, 7)
    d0 = _dispatches(reg)
    tree, traced, new_keys = heal("h1")
    kinds = _kinds(tree)
    assert LAYER_KINDS <= set(kinds), LAYER_KINDS - set(kinds)
    _assert_nested(tree)
    assert tree["path"] == "/b/h1"
    assert [s["label"] for s in kinds["object"]] == ["heal"]
    assert [s["label"] for s in kinds["stream"]] == ["heal_fused"]
    assert {s["label"] for s in kinds["device-call"]} == {"rec"}
    assert len(kinds["device-call"]) == _dispatches(reg) - d0 == 2
    # the first heal traces its 8-block and its 2-block batch
    assert traced == new_keys == 2, (traced, fresh)
    # the same object and drives again, another object after it, then
    # other drives (other survivors, other targets): patterns never seen,
    # in batch shapes that are known, and not one trace
    # (a wipe takes both objects off its drives)
    known = 0
    for key, drives in (("h1", (3, 7)), ("h2", ()), ("h2", (3, 7)),
                        ("h1", ()), ("h1", (0, 12)), ("h2", ())):
        if drives:
            n16.wipe(*drives)
        if drives == (0, 12):
            known = len(patterns)
        _, traced, new_keys = heal(key)
        assert traced == new_keys == 0, (key, drives, traced, fresh)
    assert len(patterns) > known >= 2, patterns
    text = reg.render_prometheus()
    assert "mtpu_codec_trace_total{" in text
    assert "mtpu_mtpu_codec_trace_total" not in text


def test_codec_trace_total_rises_on_a_new_shape_only(plane):
    import numpy as np

    from minio_tpu.erasure import device_engine

    reg = plane["reg"]
    codec = device_engine.for_geometry(2, 2, "dense-gf8")
    src = np.zeros((1, 2, 4096), dtype=np.uint8)

    def rebuild(present, targets) -> float:
        t0 = _traces(reg)
        out, _ = codec.reconstruct_async(src.copy(), present, targets)
        assert device_engine.to_host(out).shape == (1, len(targets), 4096)
        return _traces(reg) - t0

    assert rebuild((0, 1), (2, 3)) == 1         # a batch shape of its own
    assert rebuild((0, 1), (2, 3)) == 0         # again: the cached function
    assert rebuild((1, 2), (0, 3)) == 0         # a new pattern is a matrix
    assert rebuild((0, 2), (1,)) == 1           # its rows are jit's key
    assert rebuild((1, 2), (0, 3)) == 0
    assert rebuild((0, 1), (2, 3)) == 0


def test_heal_under_a_request_keeps_the_outer_root(plane):
    n16 = plane["n16"]
    n16.put("h3", 10 * MIB)
    n16.wipe(5)
    spans.clear_slow_requests()
    with spans.request_trace("heal_admin"):
        n16.es.heal_object("b", "h3")
    trees = spans.slow_requests()
    assert [t["api"] for t in trees] == ["heal_admin"]
    assert "object" in _kinds(trees[0])


def test_put_get_and_heal_record_disk_spans_behind_the_health_wrapper(plane):
    """The hung-drive guard of storage/diskcheck holds on every host, and
    with it the `disk` span: a PUT's per-drive calls, the shard reads of
    a ten-block GET on the reader's pool threads and a heal's reads and
    renames each land on their request's tree, by op and drive."""
    n16 = plane["n16"]
    put = n16.put("d1", 10 * MIB)
    spans.clear_slow_requests()
    sink = io.BytesIO()
    with spans.request_trace("get_object"):
        n16.es.get_object("b", "d1", sink)
    get = spans.slow_requests()[-1]
    assert len(sink.getvalue()) == 10 * MIB
    n16.wipe(3, 7)
    spans.clear_slow_requests()
    assert len(n16.es.heal_object("b", "d1")["healed"]) == 2
    heal = spans.slow_requests()[-1]
    drives = {f"d{i}" for i in range(16)}
    for tree, api, op, at_least in ((put, "put_object", "rename_data", 16),
                                    (get, "get_object", "read_file_stream",
                                     12),
                                    (heal, "heal_object", "rename_data", 2)):
        assert tree["api"] == api
        labels = [s["label"] for s in _kinds(tree)["disk"]]
        assert all(lb.split(":")[1] in drives for lb in labels), labels
        hit = {lb.split(":")[1] for lb in labels if lb.startswith(op + ":")}
        assert len(hit) >= at_least, (api, sorted(labels))


def test_heal_root_stays_out_of_the_slow_request_window(monkeypatch):
    spans.reset()
    monkeypatch.setenv("MTPU_TRACE_SLOW_MS", "auto")
    for _ in range(spans.P99_RECALC_EVERY * 2):
        with spans.request_trace("heal_object", background=True):
            pass
    assert spans.slow_threshold_ms() == float("inf")
    assert len(spans._durations_ms) == 0
    with spans.request_trace("put_object"):
        pass
    assert len(spans._durations_ms) == 1


def test_heal_tree_is_served_by_the_admin_endpoint(plane):
    from minio_tpu.api.admin import AdminHandlers

    n16 = plane["n16"]
    n16.put("h4", 10 * MIB)
    n16.wipe(9)
    n16.es.heal_object("b", "h4")

    class Ctx:
        qdict = {"n": "4"}

    body = json.loads(AdminHandlers(None, None).slow_requests(Ctx()).body)
    tree = body["captured"][-1]
    assert tree["api"] == "heal_object"
    assert {"object", "stream", "device-call"} <= set(_kinds(tree))


def test_span_seconds_renders_kind_and_op(plane):
    n4, n16, reg = plane["n4"], plane["n16"], plane["reg"]
    n4.put("r", MIB)
    n16.put("h5", 10 * MIB)
    n16.wipe(11)
    n16.es.heal_object("b", "h5")
    text = reg.render_prometheus()
    for kind in ("request", "body-read", "admission", "object", "commit",
                 "stream", "device-h2d", "device-call", "device-wait",
                 "disk"):
        line = f'mtpu_span_seconds_count{{kind="{kind}",op="put_object"}}'
        assert line in text, kind
    for kind in ("request", "object", "commit", "stream", "device-h2d",
                 "device-call", "device-wait"):
        line = f'mtpu_span_seconds_sum{{kind="{kind}",op="heal_object"}}'
        assert line in text, kind
    # one series per (kind, op), never one without op
    assert 'mtpu_span_seconds_count{kind="request"}' not in text


def test_trace_off_appends_nothing(plane, monkeypatch):
    n4 = plane["n4"]
    spans.reset()
    reg = Metrics()
    spans.set_metrics(reg)
    try:
        monkeypatch.setenv("MTPU_TRACE", "0")
        body = os.urandom(MIB)
        with spans.request_trace("put_object") as ctx:
            assert ctx is None
            n4.es.put_object("b", "off", io.BytesIO(body), MIB)
        with spans._rings_mu:
            assert sum(r.n for r in spans._rings.values()) == 0
        assert "span_seconds" not in reg.render_prometheus()
    finally:
        spans.set_metrics(plane["reg"])


def test_heal_storm_leaves_s3_exemplars_in_place(monkeypatch):
    """Every heal outlasts the threshold S3 requests set, so every
    heal's tree is captured: into a few slots of its own."""
    spans.reset()
    monkeypatch.setenv("MTPU_TRACE_SLOW_MS", "0")
    for i in range(3):
        with spans.request_trace("put_object", path=f"/b/slow{i}"):
            pass
    for i in range(spans.SLOW_STORE_CAP + spans.SLOW_BACKGROUND_CAP):
        with spans.request_trace("heal_object", background=True,
                                 path=f"/b/h{i}"):
            pass
    trees = spans.slow_requests(1000)
    assert [t["path"] for t in trees if t["api"] == "put_object"] == [
        "/b/slow0", "/b/slow1", "/b/slow2"]
    heals = [t["path"] for t in trees if t["api"] == "heal_object"]
    last = spans.SLOW_STORE_CAP + spans.SLOW_BACKGROUND_CAP
    assert heals == [f"/b/h{i}" for i in
                     range(last - spans.SLOW_BACKGROUND_CAP, last)]
    # one list, by time: the newest is the last heal
    assert spans.slow_requests(1)[0]["path"] == f"/b/h{last - 1}"
    assert spans.clear_slow_requests() == 3 + spans.SLOW_BACKGROUND_CAP
    assert spans.slow_requests() == []
    spans.reset()


class _StubAnnotation:
    """In place of jax.profiler.TraceAnnotation: keeps, per thread, the
    annotations that are open, and every name it was given."""

    open_by_thread: dict[int, list[str]] = {}
    names: list[str] = []
    nested: list[tuple] = []
    mu = threading.Lock()

    @staticmethod
    def is_enabled() -> bool:
        return True

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        with self.mu:
            held = self.open_by_thread.setdefault(threading.get_ident(), [])
            if held:
                self.nested.append((list(held), self.name))
            held.append(self.name)
            self.names.append(self.name)
        return self

    def __exit__(self, *exc):
        with self.mu:
            self.open_by_thread[threading.get_ident()].remove(self.name)
        return False


def test_mirrored_spans_never_nest_on_a_thread(plane, monkeypatch):
    import jax

    stub = _StubAnnotation
    stub.names.clear()
    stub.nested.clear()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", stub)
    n16 = plane["n16"]
    n16.put("m", 10 * MIB)
    n16.wipe(2, 13)
    n16.es.heal_object("b", "m")
    assert not stub.nested, stub.nested[:5]
    assert all(not held for held in stub.open_by_thread.values())
    kinds = {n.split()[0] for n in stub.names}
    want = {"mtpu:body-read", "mtpu:admission", "mtpu:device-h2d",
            "mtpu:device-call", "mtpu:device-wait", "mtpu:disk",
            "mtpu:stage", "mtpu:stage-wait", "mtpu:commit"}
    assert want <= kinds, want - kinds
    # the parents that would swallow every gap under them stay off
    assert not kinds & {"mtpu:request", "mtpu:object", "mtpu:stream"}
    # a disk op sums under the op, not under each of sixteen endpoints
    assert "mtpu:disk rename_data" in stub.names
    assert {"mtpu:device-call enc", "mtpu:device-call rec"} <= set(
        stub.names)
    stages = {n for n in stub.names if n.startswith("mtpu:stage ")}
    assert stages <= {"mtpu:stage put/md5", "mtpu:stage put/pack"}, stages
    # an outer annotation suppresses the inner one instead of nesting
    stub.names.clear()
    with spans.request_trace("put_object"):
        with spans.span("commit", mirror=True):
            with spans.twin("disk", "rename_data"):
                pass
            with spans.span("device-wait"):
                pass
    assert stub.names == ["mtpu:commit"] and not stub.nested


def test_spans_module_never_imports_jax():
    code = (
        "import sys\n"
        "from minio_tpu.observability import spans\n"
        "from minio_tpu.observability.metrics import Metrics\n"
        "spans.set_metrics(Metrics())\n"
        "with spans.request_trace('put_object'):\n"
        "    with spans.span('device-call', 'enc'):\n"
        "        pass\n"
        "    with spans.twin('disk', 'x'):\n"
        "        spans.record('disk', 'x:d0', 1000)\n"
        "assert 'jax' not in sys.modules, 'spans pulled jax in'\n"
        "assert 'jaxlib' not in sys.modules\n"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", code], cwd=repo,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
