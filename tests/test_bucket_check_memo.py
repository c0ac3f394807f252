"""The S3 front end's bucket check on a one-node, 16-drive server: it
answers from the object layer's memo of buckets seen on the drives, asks
the drives where the memo holds no fresh positive, never keeps a
negative, and forgets a bucket the moment it is deleted. HeadBucket
alone asks every drive every time. Drive calls are read from
`mtpu_disk_ops_total{op="stat_vol"}`, the checks from
`mtpu_bucket_check_total{answer}`.

    JAX_PLATFORMS=cpu python -m pytest tests/test_bucket_check_memo.py -q
"""

from __future__ import annotations

import http.client
import os

import pytest

from minio_tpu.api.sign import sign_v4_request
from minio_tpu.object.pools import BUCKET_CHECK_ANSWERS, ErasureServerPools
from minio_tpu.server import Server

AK, SK = "minioadmin", "minioadmin"
DRIVES = 16
BODY = b"x" * 4096


def request(srv, method: str, path: str, body: bytes = b""):
    host = srv.endpoint
    hdrs = sign_v4_request(SK, AK, method, host, path, [], {}, body)
    conn = http.client.HTTPConnection(host, timeout=60)
    try:
        conn.request(method, path, body=body, headers=hdrs)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


@pytest.fixture(scope="module")
def node(tmp_path_factory):
    root = tmp_path_factory.mktemp("memo")
    srv = Server([f"{root}/d{{1...{DRIVES}}}"], port=0, root_user=AK,
                 root_password=SK, enable_scanner=False).start()
    srv.root = str(root)
    yield srv
    srv.stop()


@pytest.fixture
def long_ttl(monkeypatch):
    """A memo that cannot lapse while a test runs, however slow the host."""
    monkeypatch.setattr(ErasureServerPools, "_BUCKET_SEEN_TTL_S", 3600.0)


def stat_vols(srv) -> float:
    """`stat_vol` calls over the node's drives."""
    return sum(srv.metrics.counter_value("disk_ops_total", op="stat_vol",
                                         disk=d.endpoint())
               for pool in srv.object_layer.pools for d in pool.disks)


def checks(srv) -> dict:
    return {a: srv.metrics.counter_value("bucket_check_total", answer=a)
            for a in BUCKET_CHECK_ANSWERS}


def test_the_counter_stands_at_zero_from_boot_and_the_ttl_is_two_seconds(
        node):
    text = node.metrics.render_prometheus()
    for answer in BUCKET_CHECK_ANSWERS:
        assert f'mtpu_bucket_check_total{{answer="{answer}"}}' in text
    assert ErasureServerPools._BUCKET_SEEN_TTL_S == 2.0


def test_requests_within_the_ttl_ask_no_drive(node, long_ttl):
    assert request(node, "PUT", "/warm")[0] == 200
    assert request(node, "PUT", "/warm/first", BODY)[0] == 200
    vols, before = stat_vols(node), checks(node)
    for i in range(10):
        assert request(node, "PUT", f"/warm/k{i}", BODY)[0] == 200
    for i in range(10):
        status, data = request(node, "GET", f"/warm/k{i}")
        assert (status, data) == (200, BODY)
    assert stat_vols(node) == vols
    after = checks(node)
    assert after["memo"] - before["memo"] == 20
    assert after["drives"] == before["drives"]


def test_a_lapsed_memo_asks_every_drive_once(node, monkeypatch):
    assert request(node, "PUT", "/lapse")[0] == 200
    assert request(node, "PUT", "/lapse/a", BODY)[0] == 200
    monkeypatch.setattr(ErasureServerPools, "_BUCKET_SEEN_TTL_S", 0.0)
    vols, before = stat_vols(node), checks(node)
    assert request(node, "PUT", "/lapse/b", BODY)[0] == 200
    # the front end's check and the object layer's own, each on every drive
    assert stat_vols(node) - vols == 2 * DRIVES
    assert checks(node)["drives"] - before["drives"] == 1


MISSING = [
    ("PUT", "/nobucket/k", BODY),
    ("GET", "/nobucket/k", b""),
    ("HEAD", "/nobucket/k", b""),
    ("DELETE", "/nobucket/k", b""),
    ("GET", "/nobucket", b""),
]


@pytest.mark.parametrize("method,path,body", MISSING,
                         ids=["put", "get", "head", "delete", "list"])
def test_a_missing_bucket_asks_the_drives_on_every_request(
        node, long_ttl, method, path, body):
    # the first request may also fill the memo of `.minio.sys`, where the
    # bucket's metadata is looked for
    request(node, method, path, body)
    for _ in range(3):
        vols, before = stat_vols(node), checks(node)
        status, data = request(node, method, path, body)
        assert status == 404
        if method != "HEAD":
            assert b"<Code>NoSuchBucket</Code>" in data
        assert stat_vols(node) - vols == DRIVES
        after = checks(node)
        assert after["drives"] - before["drives"] == 1
        assert after["memo"] == before["memo"]


def test_head_bucket_asks_every_drive_every_time(node, long_ttl):
    assert request(node, "PUT", "/headed")[0] == 200
    assert request(node, "PUT", "/headed/k", BODY)[0] == 200
    for _ in range(3):
        vols, before = stat_vols(node), checks(node)
        assert request(node, "HEAD", "/headed")[0] == 200
        assert stat_vols(node) - vols == DRIVES
        assert checks(node) == before


def test_a_deleted_bucket_is_refused_on_the_next_request(node, long_ttl):
    assert request(node, "PUT", "/gone")[0] == 200
    assert request(node, "PUT", "/gone/k", BODY)[0] == 200
    assert request(node, "GET", "/gone/k")[0] == 200
    assert request(node, "DELETE", "/gone/k")[0] == 204
    assert request(node, "DELETE", "/gone")[0] == 204
    for method, body in (("PUT", BODY), ("GET", b"")):
        status, data = request(node, method, "/gone/k", body)
        assert status == 404 and b"<Code>NoSuchBucket</Code>" in data
    for d in range(1, DRIVES + 1):
        assert not os.path.exists(os.path.join(node.root, f"d{d}", "gone"))
