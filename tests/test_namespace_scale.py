"""Namespace-at-scale down-payment (ISSUE 14 satellite, ROADMAP item
4): a 200k-object synthetic bucket driven through one full scanner
cycle and paginated ListObjects, asserting BOUNDED memory (no
O(bucket) materialization anywhere in the crawl) and that the new
cycle-progress / histogram gauges actually move.

The fixture is synthetic by design — 200k real PUTs would spend the
suite's budget on disk IO that this test is specifically about NOT
needing: the scanner's contract is to stream pages, and a synthetic
layer lets tracemalloc put a hard number on that."""

import io
import tracemalloc

import pytest

from minio_tpu.background.scanner import DataScanner, DynamicSleeper
from minio_tpu.object.types import ListObjectsInfo, ObjectInfo
from minio_tpu.observability.metrics import Metrics

N_OBJECTS = 200_000
PAGE = 1000


class _Bucket:
    name = "synth"


class SyntheticLayer:
    """200k-object bucket generated lazily page by page: the scanner
    (and any listing consumer) must never see more than one page in
    memory. Also records every save_usage payload so the test can
    assert the snapshot stays O(buckets)."""

    def __init__(self, n: int = N_OBJECTS):
        self.n = n
        self.heals = 0
        self.saved_usage_bytes = 0
        self.pages_served = 0
        self.max_page = 0

    # --- the surface DataScanner touches ---

    def list_buckets(self):
        return [_Bucket()]

    def _obj(self, i: int) -> ObjectInfo:
        # Sizes sweep 11 log2 bins; versions sweep 1..8 (4 bins).
        return ObjectInfo(
            bucket="synth", name=f"obj-{i:07d}",
            size=1024 << (i % 11),
            mod_time_ns=1_700_000_000_000_000_000 + i,
            num_versions=1 + (i % 8),
            user_defined={},
        )

    def list_objects(self, bucket, prefix="", marker="",
                     max_keys=PAGE, **kw):
        assert bucket == "synth"
        start = int(marker.split("-")[1]) + 1 if marker else 0
        count = min(max_keys, self.n - start)
        out = ListObjectsInfo()
        out.objects = [self._obj(i) for i in range(start, start + count)]
        self.pages_served += 1
        self.max_page = max(self.max_page, len(out.objects))
        out.is_truncated = start + count < self.n
        out.next_marker = (out.objects[-1].name if out.objects else "")
        return out

    def heal_object(self, bucket, object_, *a, **kw):
        self.heals += 1
        return {"healed": []}

    def bucket_exists(self, bucket):
        return bucket == "synth"

    def check_bucket(self, bucket):
        from minio_tpu.utils.errors import ErrBucketNotFound

        if not self.bucket_exists(bucket):
            raise ErrBucketNotFound(bucket)

    def make_bucket(self, bucket):
        pass

    def put_object(self, bucket, object_, reader, size, *a, **kw):
        self.saved_usage_bytes = size
        reader.read()

    def get_object_bytes(self, bucket, object_):
        from minio_tpu.utils.errors import ErrObjectNotFound

        raise ErrObjectNotFound(object_)


@pytest.mark.slow
def test_scanner_cycle_200k_bounded_memory_and_gauges():
    ol = SyntheticLayer()
    m = Metrics()
    scanner = DataScanner(ol, metrics=m,
                          sleeper=DynamicSleeper(0.0, 0.0))
    tracemalloc.start()
    usage = scanner.scan_cycle()
    _current, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()

    # Bounded memory: one page of ObjectInfos is ~1 MB; materializing
    # the 200k-object bucket would be >100 MB. 32 MB is generous slack
    # for interpreter noise while still catching any O(bucket) buffer.
    assert peak < 32 << 20, f"scan cycle peaked at {peak >> 20} MiB"

    bu = usage.buckets_usage["synth"]
    assert bu.objects_count == N_OBJECTS
    assert usage.objects_total_count == N_OBJECTS
    # Histograms: streaming log2 bins, O(1) memory, complete coverage.
    assert sum(bu.size_hist) == N_OBJECTS
    assert sum(bu.versions_hist) == N_OBJECTS
    assert sum(1 for n in bu.size_hist if n) == 11  # 2^10..2^20
    assert sum(1 for n in bu.versions_hist if n) == 4  # 1,2-3,4-7,8
    # The usage snapshot persisted O(buckets), not O(objects).
    assert 0 < ol.saved_usage_bytes < 64 << 10

    # Cycle-progress gauges moved (published DURING the cycle too;
    # final state: complete).
    assert m.gauge("scanner_cycle_progress") == 1.0
    assert m.gauge("scanner_objects_per_second") > 0
    assert m.gauge("scanner_cycle_duration_seconds") > 0
    assert scanner.progress()["objectsScannedTotal"] == N_OBJECTS
    # Heal sampling fired at ~1/512 of the namespace.
    assert ol.heals == N_OBJECTS // scanner.heal_prob

    # Histogram gauges render through the scrape collector.
    from minio_tpu.observability.metrics_v2 import MetricsCollector

    MetricsCollector(m, scanner=scanner).collect()
    assert m.gauge("bucket_objects_size_distribution",
                   bucket="synth", bin="2^10") > 0
    assert m.gauge("bucket_objects_version_distribution",
                   bucket="synth", bin="2^0") > 0
    expo = m.render_prometheus()
    assert "mtpu_bucket_objects_size_distribution" in expo


@pytest.mark.slow
def test_paginated_listing_200k_streams_pages():
    """Paginated ListObjectsV2 over the 200k bucket through the REAL
    S3 handler (`S3ApiHandlers.list_objects_v2`): continuation-token
    encode/decode round-trips resume exactly, every page is bounded at
    max-keys, each response serializes only its own slice of XML, and
    the whole crawl never materializes O(bucket) state."""
    import xml.etree.ElementTree as ET

    from minio_tpu.api.handlers import S3ApiHandlers

    class _Ctx:
        bucket = "synth"
        object = ""

        def __init__(self, qdict):
            self.qdict = qdict

    ol = SyntheticLayer()
    h = S3ApiHandlers(ol, bucket_meta=None, iam=None)
    ns = "{http://s3.amazonaws.com/doc/2006-03-01/}"
    tracemalloc.start()
    seen = 0
    token = ""
    while True:
        q = {"max-keys": str(PAGE)}
        if token:
            q["continuation-token"] = token
        resp = h.list_objects_v2(_Ctx(q))
        assert resp.status == 200
        root = ET.fromstring(resp.body)
        keys = [c.find(f"{ns}Key").text
                for c in root.iter(f"{ns}Contents")]
        assert len(keys) <= PAGE
        assert int(root.find(f"{ns}KeyCount").text) == len(keys)
        # Token resume is exact: first key of this page follows the
        # last key of the previous page with no gap or overlap.
        assert keys[0] == f"obj-{seen:07d}"
        seen += len(keys)
        if root.find(f"{ns}IsTruncated").text != "true":
            break
        token = root.find(f"{ns}NextContinuationToken").text
    _cur, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert seen == N_OBJECTS
    assert ol.pages_served == N_OBJECTS // PAGE
    assert ol.max_page == PAGE
    assert peak < 16 << 20, f"listing peaked at {peak >> 20} MiB"
