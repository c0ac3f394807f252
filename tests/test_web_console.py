"""Web console JSON-RPC: login token flow, bucket/object methods,
token-authed upload/download byte paths, presigned share links
(ref cmd/web-handlers.go, cmd/web-router.go)."""

import http.client
import json
import urllib.parse

import pytest

AK, SK = "webroot", "webroot-secret"


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    from minio_tpu.server import Server

    root = tmp_path_factory.mktemp("web")
    srv = Server(
        [str(root / "disk{1...4}")], port=0,
        root_user=AK, root_password=SK, enable_scanner=False,
    ).start()
    yield srv
    srv.stop()


def rpc(srv, method, params=None, token=None):
    body = json.dumps({
        "jsonrpc": "2.0", "id": 1, "method": method,
        "params": params or {},
    }).encode()
    headers = {"Content-Type": "application/json"}
    if token:
        headers["Authorization"] = f"Bearer {token}"
    conn = http.client.HTTPConnection(srv.endpoint, timeout=30)
    try:
        conn.request("POST", "/minio/webrpc", body=body, headers=headers)
        r = conn.getresponse()
        raw = r.read()
        try:
            return r.status, json.loads(raw)
        except ValueError:
            return r.status, {"raw": raw}  # XML S3 error (auth denials)
    finally:
        conn.close()


@pytest.fixture(scope="module")
def token(server):
    st, resp = rpc(server, "web.Login",
                   {"username": AK, "password": SK})
    assert st == 200, resp
    return resp["result"]["token"]


def test_login_rejects_bad_password(server):
    st, _ = rpc(server, "web.Login",
                {"username": AK, "password": "wrong"})
    assert st == 403


def test_methods_require_token(server):
    st, _ = rpc(server, "web.ListBuckets")
    assert st == 403
    st, _ = rpc(server, "web.ListBuckets", token="garbage.token")
    assert st == 403


def test_bucket_lifecycle_via_rpc(server, token):
    st, resp = rpc(server, "web.MakeBucket",
                   {"bucketName": "webbucket"}, token)
    assert st == 200 and "result" in resp
    st, resp = rpc(server, "web.ListBuckets", token=token)
    names = [b["name"] for b in resp["result"]["buckets"]]
    assert "webbucket" in names


def test_upload_download_roundtrip(server, token):
    rpc(server, "web.MakeBucket", {"bucketName": "webdata"}, token)
    payload = b"browser upload bytes" * 100
    conn = http.client.HTTPConnection(server.endpoint, timeout=30)
    try:
        conn.request("PUT", "/minio/upload/webdata/file.bin",
                     body=payload,
                     headers={"Authorization": f"Bearer {token}",
                              "Content-Length": str(len(payload))})
        r = conn.getresponse()
        assert r.status == 200, r.read()
        r.read()
    finally:
        conn.close()

    # listing sees it
    st, resp = rpc(server, "web.ListObjects",
                   {"bucketName": "webdata"}, token)
    assert [o["name"] for o in resp["result"]["objects"]] == ["file.bin"]

    # token-in-query download (browser link style)
    q = urllib.parse.urlencode({"token": token})
    conn = http.client.HTTPConnection(server.endpoint, timeout=30)
    try:
        conn.request("GET", f"/minio/download/webdata/file.bin?{q}")
        r = conn.getresponse()
        assert r.status == 200
        assert r.read() == payload
        assert "attachment" in r.getheader("Content-Disposition", "")
    finally:
        conn.close()

    # download with no/bad token refused
    conn = http.client.HTTPConnection(server.endpoint, timeout=30)
    try:
        conn.request("GET", "/minio/download/webdata/file.bin")
        r = conn.getresponse()
        assert r.status == 403
        r.read()
    finally:
        conn.close()


def test_presigned_share_link_works(server, token):
    rpc(server, "web.MakeBucket", {"bucketName": "sharebkt"}, token)
    payload = b"shared content"
    conn = http.client.HTTPConnection(server.endpoint, timeout=30)
    try:
        conn.request("PUT", "/minio/upload/sharebkt/doc.txt", body=payload,
                     headers={"Authorization": f"Bearer {token}",
                              "Content-Length": str(len(payload))})
        assert conn.getresponse().status == 200
    finally:
        conn.close()
    st, resp = rpc(server, "web.PresignedGet",
                   {"bucketName": "sharebkt", "objectName": "doc.txt",
                    "host": server.endpoint}, token)
    assert st == 200, resp
    url = resp["result"]["url"]
    # The presigned URL is directly fetchable with no further auth.
    parsed = urllib.parse.urlsplit(url)
    conn = http.client.HTTPConnection(parsed.netloc, timeout=30)
    try:
        conn.request("GET", f"{parsed.path}?{parsed.query}")
        r = conn.getresponse()
        assert r.status == 200
        assert r.read() == payload
    finally:
        conn.close()


def test_remove_object_and_unknown_method(server, token):
    st, resp = rpc(server, "web.RemoveObject",
                   {"bucketName": "webdata", "objects": ["file.bin"]},
                   token)
    assert st == 200
    st, resp = rpc(server, "web.ListObjects",
                   {"bucketName": "webdata"}, token)
    assert resp["result"]["objects"] == []
    st, resp = rpc(server, "web.NoSuchMethod", {}, token)
    assert st == 200 and resp["error"]["code"] == -32601


def test_web_plane_cannot_touch_internal_buckets(server, token):
    """The web RPC/byte paths enforce the same reserved-bucket guard as
    the S3 data plane — no side door into `.minio.sys`."""
    st, resp = rpc(server, "web.ListObjects",
                   {"bucketName": ".minio.sys"}, token)
    assert st == 403 or "error" in resp
    st, resp = rpc(server, "web.RemoveObject",
                   {"bucketName": ".minio.sys",
                    "objects": ["config/config.json"]}, token)
    assert st == 403 or "error" in resp
    conn = http.client.HTTPConnection(server.endpoint, timeout=10)
    try:
        conn.request("PUT", "/minio/upload/.minio.sys/config/config.json",
                     body=b"evil",
                     headers={"Authorization": f"Bearer {token}",
                              "Content-Length": "4"})
        r = conn.getresponse()
        assert r.status == 403
        r.read()
    finally:
        conn.close()


def test_web_download_decodes_transformed_objects(server, token):
    """An SSE-encrypted object fetched via /minio/download returns the
    PLAINTEXT content — the web byte path runs the same GET chain as
    S3, never raw stored ciphertext."""
    from minio_tpu.api.sign import sign_v4_request

    rpc(server, "web.MakeBucket", {"bucketName": "webenc"}, token)
    body = b"secret web payload " * 300
    path = "/webenc/enc.bin"
    h = sign_v4_request(SK, AK, "PUT", server.endpoint, path, [],
                        {"x-amz-server-side-encryption": "AES256"}, body)
    conn = http.client.HTTPConnection(server.endpoint, timeout=30)
    try:
        conn.request("PUT", path, body=body, headers=h)
        assert conn.getresponse().status == 200
    finally:
        conn.close()

    q = urllib.parse.urlencode({"token": token})
    conn = http.client.HTTPConnection(server.endpoint, timeout=30)
    try:
        conn.request("GET", f"/minio/download/webenc/enc.bin?{q}")
        r = conn.getresponse()
        assert r.status == 200
        assert r.read() == body  # decrypted, not ciphertext
    finally:
        conn.close()

    # and web-uploaded bytes read back identically over signed S3 GET
    conn = http.client.HTTPConnection(server.endpoint, timeout=30)
    try:
        conn.request("PUT", "/minio/upload/webenc/up.bin", body=body,
                     headers={"Authorization": f"Bearer {token}",
                              "Content-Length": str(len(body))})
        assert conn.getresponse().status == 200
    finally:
        conn.close()
    h = sign_v4_request(SK, AK, "GET", server.endpoint,
                        "/webenc/up.bin", [], {}, b"")
    conn = http.client.HTTPConnection(server.endpoint, timeout=30)
    try:
        conn.request("GET", "/webenc/up.bin", headers=h)
        r = conn.getresponse()
        assert r.status == 200 and r.read() == body
    finally:
        conn.close()


def test_console_page_served(server):
    """The embedded UI page is served unauthenticated at
    /minio/console/ and speaks the webrpc endpoints."""
    conn = http.client.HTTPConnection(server.endpoint, timeout=10)
    try:
        conn.request("GET", "/minio/console/")
        r = conn.getresponse()
        body = r.read()
        assert r.status == 200
        assert "text/html" in r.getheader("Content-Type", "")
        assert b"web.Login" in body and b"/minio/webrpc" in body
    finally:
        conn.close()


def test_download_accepts_authorization_header(server, token):
    """The console fetches downloads with a Bearer header (keeps the
    token out of URLs); the server must accept it (regression: only
    ?token= worked)."""
    rpc(server, "web.MakeBucket", {"bucketName": "hdrload"}, token)
    conn = http.client.HTTPConnection(server.endpoint, timeout=30)
    try:
        conn.request("PUT", "/minio/upload/hdrload/f.bin", body=b"hdr!",
                     headers={"Authorization": f"Bearer {token}",
                              "Content-Length": "4"})
        assert conn.getresponse().status == 200
    finally:
        conn.close()
    conn = http.client.HTTPConnection(server.endpoint, timeout=30)
    try:
        conn.request("GET", "/minio/download/hdrload/f.bin",
                     headers={"Authorization": f"Bearer {token}"})
        r = conn.getresponse()
        assert r.status == 200 and r.read() == b"hdr!"
    finally:
        conn.close()


def _enable_versioning(server, bucket):
    import tests.test_s3_api as s3t

    c = s3t.Client(server.s3, access=AK, secret=SK)
    body = (b'<VersioningConfiguration><Status>Enabled</Status>'
            b'</VersioningConfiguration>')
    st, _, _ = c.request("PUT", f"/{bucket}", query=[("versioning", "")],
                         body=body)
    assert st == 200


def test_versions_view_restore_and_delete(server, token):
    assert rpc(server, "web.MakeBucket",
               {"bucketName": "webver"}, token)[1].get("result") == {}
    _enable_versioning(server, "webver")
    for data in (b"v1-bytes", b"v2-bytes"):
        conn = http.client.HTTPConnection(server.endpoint, timeout=30)
        conn.request("PUT", "/minio/upload/webver/doc.txt", body=data,
                     headers={"Authorization": f"Bearer {token}"})
        assert conn.getresponse().status == 200
        conn.close()
    st, resp = rpc(server, "web.ListObjectVersions",
                   {"bucketName": "webver", "prefix": "doc.txt"}, token)
    assert st == 200, resp
    versions = [v for v in resp["result"]["versions"]
                if v["name"] == "doc.txt"]
    assert len(versions) == 2
    assert versions[0]["isLatest"] and not versions[1]["isLatest"]
    old = versions[1]
    # Restore the old version: server-side copy -> NEW latest version.
    st, resp = rpc(server, "web.RestoreVersion",
                   {"bucketName": "webver", "objectName": "doc.txt",
                    "versionId": old["versionId"]}, token)
    assert st == 200 and resp.get("result") == {}, resp
    # Download now serves v1 content.
    conn = http.client.HTTPConnection(server.endpoint, timeout=30)
    conn.request("GET", "/minio/download/webver/doc.txt",
                 headers={"Authorization": f"Bearer {token}"})
    r = conn.getresponse()
    assert r.status == 200 and r.read() == b"v1-bytes"
    conn.close()
    # Delete one specific version permanently.
    st, resp = rpc(server, "web.ListObjectVersions",
                   {"bucketName": "webver", "prefix": "doc.txt"}, token)
    n_before = len(resp["result"]["versions"])
    victim = resp["result"]["versions"][-1]
    st, resp = rpc(server, "web.DeleteVersion",
                   {"bucketName": "webver", "objectName": "doc.txt",
                    "versionId": victim["versionId"]}, token)
    assert st == 200 and resp.get("result") == {}, resp
    st, resp = rpc(server, "web.ListObjectVersions",
                   {"bucketName": "webver", "prefix": "doc.txt"}, token)
    assert len(resp["result"]["versions"]) == n_before - 1
    assert all(v["versionId"] != victim["versionId"]
               for v in resp["result"]["versions"])


def test_policy_editor_roundtrip(server, token):
    assert rpc(server, "web.MakeBucket",
               {"bucketName": "webpol"}, token)[1].get("result") == {}
    st, resp = rpc(server, "web.GetBucketPolicy",
                   {"bucketName": "webpol"}, token)
    assert st == 200 and resp["result"]["policy"] == ""
    policy = json.dumps({
        "Version": "2012-10-17",
        "Statement": [{
            "Effect": "Allow", "Principal": {"AWS": ["*"]},
            "Action": ["s3:GetObject"],
            "Resource": ["arn:aws:s3:::webpol/*"],
        }],
    })
    st, resp = rpc(server, "web.SetBucketPolicy",
                   {"bucketName": "webpol", "policy": policy}, token)
    assert st == 200 and resp.get("result") == {}, resp
    st, resp = rpc(server, "web.GetBucketPolicy",
                   {"bucketName": "webpol"}, token)
    got = json.loads(resp["result"]["policy"])
    assert got["Statement"][0]["Action"] == ["s3:GetObject"]
    # Clearing: empty policy string removes it.
    st, resp = rpc(server, "web.SetBucketPolicy",
                   {"bucketName": "webpol", "policy": ""}, token)
    assert st == 200, resp
    st, resp = rpc(server, "web.GetBucketPolicy",
                   {"bucketName": "webpol"}, token)
    assert resp["result"]["policy"] == ""


def test_console_page_has_new_controls(server):
    conn = http.client.HTTPConnection(server.endpoint, timeout=30)
    conn.request("GET", "/minio/console/")
    r = conn.getresponse()
    page = r.read().decode()
    conn.close()
    for needle in ("web.ListObjectVersions", "web.RestoreVersion",
                   "web.SetBucketPolicy", "shareexp", "Delete selected"):
        assert needle in page, needle


def test_delete_bucket_takes_the_buckets_metadata_along(server, token):
    """The console's DeleteBucket is the S3 handler's: a bucket made again
    under the same name starts without the old one's policy."""
    assert rpc(server, "web.MakeBucket",
               {"bucketName": "webgone"}, token)[1].get("result") == {}
    policy = json.dumps({
        "Version": "2012-10-17",
        "Statement": [{
            "Effect": "Allow", "Principal": {"AWS": ["*"]},
            "Action": ["s3:GetObject"],
            "Resource": ["arn:aws:s3:::webgone/*"],
        }],
    })
    st, resp = rpc(server, "web.SetBucketPolicy",
                   {"bucketName": "webgone", "policy": policy}, token)
    assert st == 200 and resp.get("result") == {}, resp
    st, resp = rpc(server, "web.DeleteBucket",
                   {"bucketName": "webgone"}, token)
    assert st == 200 and resp.get("result") == {}, resp
    st, resp = rpc(server, "web.GetBucketPolicy",
                   {"bucketName": "webgone"}, token)
    assert "result" not in resp, resp
    assert rpc(server, "web.MakeBucket",
               {"bucketName": "webgone"}, token)[1].get("result") == {}
    st, resp = rpc(server, "web.GetBucketPolicy",
                   {"bucketName": "webgone"}, token)
    assert st == 200 and resp["result"]["policy"] == "", resp
