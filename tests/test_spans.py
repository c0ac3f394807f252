"""Request-span tracing plane (ISSUE 12): span nesting/parentage,
per-thread ring capture, the slow-request exemplar store (fixed and
auto-p99 thresholds), metrics exposition of mtpu_span_seconds by kind,
TraceHub span routing, the admin query — and the end-to-end acceptance
proof: a REAL armed PUT and a degraded GET in a forced-multicore
subprocess yield connected span trees covering S3 dispatch → admission
→ pipeline stages → worker shm ops (cross-process child timing) →
storage fan-out quorum wait."""

import json
import os
import subprocess
import sys
import threading

import pytest

from minio_tpu.observability import spans
from minio_tpu.observability.metrics import Metrics
from minio_tpu.observability.trace import TraceHub
from minio_tpu.ops import gf_native

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(autouse=True)
def _clean_spans(monkeypatch):
    monkeypatch.setenv("MTPU_TRACE_SLOW_MS", "0")
    monkeypatch.delenv("MTPU_TRACE", raising=False)
    spans.reset()
    spans.set_metrics(None)
    spans.set_trace_hub(None)
    yield
    spans.reset()
    spans.set_metrics(None)
    spans.set_trace_hub(None)


def _tree_by_api(trees, api):
    matches = [t for t in trees if t["api"] == api]
    assert matches, f"no captured tree for {api}: " \
        f"{[t['api'] for t in trees]}"
    return matches[-1]


def _assert_connected(tree):
    ids = {s["id"] for s in tree["spans"]}
    roots = [s for s in tree["spans"] if s["parent"] == 0]
    assert [r["kind"] for r in roots] == ["request"], roots
    for s in tree["spans"]:
        assert s["parent"] == 0 or s["parent"] in ids, s


def test_span_nesting_parentage_and_capture():
    with spans.request_trace("put_object", request_id="r1") as ctx:
        assert ctx is not None
        with spans.span("admission", "put"):
            pass
        with spans.span("worker", "encode"):
            spans.record("worker-exec", "encode pid 7", 1_000_000)
        spans.record("stage", "put/encode", 2_000_000)
    trees = spans.slow_requests()
    assert len(trees) == 1
    tree = trees[0]
    assert tree["api"] == "put_object"
    assert tree["request_id"] == "r1"
    _assert_connected(tree)
    by_kind = {s["kind"]: s for s in tree["spans"]}
    # Cross-process stitch: worker-exec hangs off the worker span.
    assert by_kind["worker-exec"]["parent"] == by_kind["worker"]["id"]
    assert by_kind["worker-exec"]["duration_us"] == 1000
    # Siblings hang off the root.
    root = by_kind["request"]["id"]
    assert by_kind["admission"]["parent"] == root
    assert by_kind["stage"]["parent"] == root


def test_cross_thread_carrier_attributes_to_the_request():
    seen = {}

    def stage_thread(carrier):
        with spans.activate(carrier):
            spans.record("stage", "pipe/encode", 5_000_000)
            seen["ctx"] = spans.current()

    with spans.request_trace("put_object") as ctx:
        t = threading.Thread(target=stage_thread,
                             args=(spans.capture(),))
        t.start()
        t.join()
    assert seen["ctx"] is ctx
    tree = spans.slow_requests()[-1]
    kinds = [s["kind"] for s in tree["spans"]]
    assert "stage" in kinds, kinds


def test_disabled_by_env(monkeypatch):
    monkeypatch.setenv("MTPU_TRACE", "0")
    with spans.request_trace("put_object") as ctx:
        assert ctx is None
        assert spans.current() is None
        spans.record("stage", "x", 1)  # must be a no-op
    assert spans.slow_requests() == []


def test_fixed_threshold_filters_fast_requests(monkeypatch):
    monkeypatch.setenv("MTPU_TRACE_SLOW_MS", "10000")
    with spans.request_trace("get_object"):
        pass
    assert spans.slow_requests() == []


def test_auto_threshold_tracks_running_p99(monkeypatch):
    monkeypatch.setenv("MTPU_TRACE_SLOW_MS", "auto")
    assert spans.slow_threshold_ms() == float("inf")
    for _ in range(spans.P99_RECALC_EVERY * 2):
        with spans.request_trace("head_object"):
            pass
    # Enough samples: the threshold is now a real (finite) p99.
    assert spans.slow_threshold_ms() != float("inf")


def test_slow_store_is_bounded():
    for i in range(spans.SLOW_STORE_CAP + 10):
        with spans.request_trace(f"req{i}"):
            pass
    assert len(spans.slow_requests()) == spans.SLOW_STORE_CAP
    assert spans.clear_slow_requests() == spans.SLOW_STORE_CAP
    assert spans.slow_requests() == []


def test_exposition_has_span_kind_histograms():
    """mtpu_span_seconds{kind=...,op=...} appears for admission/stage/
    fanout after real (1-core-safe) traffic through the instrumented
    seams; op is the root's API name."""
    import threading as _th

    from minio_tpu.pipeline import Pipeline, Stage
    from minio_tpu.pipeline.admission import (
        AdmissionConfig,
        AdmissionGovernor,
    )
    from minio_tpu.utils.fanout import quorum_wait

    reg = Metrics()
    spans.set_metrics(reg)
    gov = AdmissionGovernor(AdmissionConfig(slots=2))
    with spans.request_trace("put_object"):
        with gov.slot("client-a"):
            Pipeline("span-test", [
                Stage("double", lambda x: x * 2),
            ]).run(range(3))
        cv = _th.Condition()
        quorum_wait(cv, set(), lambda: 0, 0, 0.01, 0.0)
    text = reg.render_prometheus()
    for kind in ("admission", "stage", "request"):
        assert (f'mtpu_span_seconds_count{{kind="{kind}",'
                f'op="put_object"}}') in text, kind
    # a fan-out's series keeps its phase as well
    assert ('mtpu_span_seconds_count{kind="fanout",label="quorum-wait",'
            'op="put_object"}') in text
    assert reg.counter_value("trace_slow_captures_total") >= 1


def test_trace_hub_routes_span_trees_to_span_subscribers_only():
    hub = TraceHub()
    spans.set_trace_hub(hub)
    q_plain = hub.subscribe()
    q_spans = hub.subscribe(spans=True)
    assert hub.any_spans
    with spans.request_trace("get_object"):
        pass
    entry = q_spans.get(timeout=2)
    assert entry["type"] == "spans"
    assert entry["api"] == "get_object"
    assert any(s["kind"] == "request" for s in entry["spans"])
    assert q_plain.empty(), "plain subscriber must not receive spans"
    hub.unsubscribe(q_spans)
    assert not hub.any_spans


def test_admin_slow_requests_endpoint_shape():
    from minio_tpu.api.admin import AdminHandlers

    with spans.request_trace("put_object"):
        spans.record("stage", "put/encode", 123_000)
    admin = AdminHandlers(None, None)

    class Ctx:
        qdict = {"n": "10"}

    resp = admin.slow_requests(Ctx())
    body = json.loads(resp.body)
    assert body["threshold_ms"] == 0.0
    assert body["captured"][-1]["api"] == "put_object"
    resp = admin.slow_requests_clear(Ctx())
    assert json.loads(resp.body)["cleared"] >= 1
    assert spans.slow_requests() == []


def test_put_tree_holds_one_device_call_per_dispatch(tmp_path, monkeypatch):
    """The tree's own device-call spans say how many fused dispatches
    the request made (what the process-wide counters' delta could not,
    under concurrency): as many as the dispatch counter rose."""
    import io

    from minio_tpu.erasure import registry
    from minio_tpu.object.erasure_objects import ErasureObjects
    from minio_tpu.storage.local import LocalStorage

    monkeypatch.setenv("MTPU_ENCODE_ENGINE", "device")
    monkeypatch.setenv("MTPU_CODEC", "dense-gf8")    # `auto` ranks by a probe
    reg = Metrics()
    monkeypatch.setattr(registry, "_metrics", reg)
    es = ErasureObjects(
        [LocalStorage(str(tmp_path / f"d{i}"), endpoint=f"d{i}")
         for i in range(4)], default_parity=2)
    es.make_bucket("b")
    payload = os.urandom(9 << 20)        # two batches: 8 blocks and 1
    with spans.request_trace("put_object"):
        es.put_object("b", "k", io.BytesIO(payload), len(payload))
    tree = spans.slow_requests()[-1]
    assert "stats" not in tree
    calls = [s for s in tree["spans"] if s["kind"] == "device-call"]
    rose = reg.counter_value("mtpu_codec_dispatch_total",
                             codec="dense-gf8", engine="device")
    assert len(calls) == rose == 2, (calls, rose)
    assert {s["label"] for s in calls} == {"enc"}


def test_defer_resume_reenters_ioflow_tag_and_admission_identity():
    """Regression for the streaming-GET accounting hole (ISSUE 19):
    the response body streams on the writer's thread AFTER the handler
    scope — and its ioflow op tag + admission identity — exited. PR9's
    resume() re-entered the identity only; defer() must capture BOTH so
    the decode/verify (or hot-tier follower) bytes the stream moves
    land in the ledger under THIS request's op class and in the
    governor under THIS caller, not as untagged/anonymous."""
    from minio_tpu.observability import ioflow
    from minio_tpu.pipeline import admission

    ioflow.reset()
    try:
        rt = spans.request_trace("get_object")
        with admission.client_context("alice", bucket="hotb"):
            with ioflow.tag("get", bucket="hotb"):
                with rt:
                    rt.defer()
        # Handler scope closed: this thread is untagged/anonymous again.
        assert admission.identity() == ("", "")
        out = {}

        def stream():
            with spans.resume(rt):
                ioflow.account("d0", "read", 1234)
                out["ident"] = admission.identity()
            out["after"] = admission.identity()

        t = threading.Thread(target=stream)
        t.start()
        t.join()

        b = ioflow.snapshot()["bytes"]
        assert b.get(("d0", "get", "read")) == 1234
        assert ("d0", "untagged", "read") not in b
        assert out["ident"] == ("alice", "hotb")
        assert out["after"] == ("", "")  # resume scoped, not leaked
        assert rt.deferred is False      # the stream finished the trace
    finally:
        ioflow.reset()


def test_defer_cancelled_by_handler_exception():
    """A handler that dies pre-stream finishes its trace at scope exit;
    resume() on it must be a full no-op (no ledger/identity install)."""
    rt = spans.request_trace("get_object")
    with pytest.raises(RuntimeError):
        with rt:
            rt.defer()
            raise RuntimeError("framing error before the stream")
    assert rt.deferred is False
    with spans.resume(rt) as ctx:
        assert ctx is None


@pytest.mark.skipif(not gf_native.available(),
                    reason="worker pool needs the native engine")
def test_e2e_span_tree_real_put_and_degraded_get():
    """THE acceptance proof: a real armed PUT and a degraded GET
    (every data shard destroyed) through a live S3 server, in a
    forced-multicore subprocess, yield CONNECTED span trees covering
    S3 dispatch → admission wait → pipeline stages → worker shm ops
    (with cross-process child timing) → storage fan-out quorum wait;
    and mtpu_span_seconds{kind=...} histograms render for the
    admission/stage/worker/fanout kinds."""
    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        proc = subprocess.run(
            [sys.executable, os.path.join(HERE, "_span_child.py"), tmp],
            capture_output=True, text=True, timeout=220,
        )
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout)
    assert out["arm_reason"] == "armed"
    assert not out["pool"]["fallbacks_by_op"], out["pool"]
    assert out["pool"]["tasks_by_op"].get("encode", 0) >= 1
    assert out["pool"]["tasks_by_op"].get("decode", 0) >= 1

    put = _tree_by_api(out["trees"], "put_object")
    get = _tree_by_api(out["trees"], "get_object")
    _assert_connected(put)
    _assert_connected(get)

    put_kinds = {s["kind"] for s in put["spans"]}
    assert {"request", "admission", "stage", "worker", "worker-exec",
            "fanout"} <= put_kinds, put_kinds
    get_kinds = {s["kind"] for s in get["spans"]}
    assert {"request", "admission", "worker", "worker-exec",
            "fanout"} <= get_kinds, get_kinds

    # Cross-process child timing: every worker-exec hangs off a worker
    # dispatch span and carries a real duration.
    for tree in (put, get):
        workers = {s["id"] for s in tree["spans"]
                   if s["kind"] == "worker"}
        execs = [s for s in tree["spans"] if s["kind"] == "worker-exec"]
        assert execs
        for s in execs:
            assert s["parent"] in workers
            assert s["duration_us"] > 0

    # GET decode + verify both offloaded (degraded read, armed pool).
    get_worker_labels = {s["label"].split()[0] for s in get["spans"]
                         if s["kind"] == "worker"}
    assert "decode" in get_worker_labels, get_worker_labels
    assert "verify" in get_worker_labels, get_worker_labels

    # Exposition: the four acceptance kinds render as histograms.
    expo = "\n".join(out["exposition"])
    for kind in ("admission", "stage", "worker", "fanout"):
        assert f'kind="{kind}"' in expo, (kind, expo)

    # The admin query served the same capture over HTTP.
    admin_apis = [t["api"] for t in out["admin"]["captured"]]
    assert "put_object" in admin_apis and "get_object" in admin_apis
