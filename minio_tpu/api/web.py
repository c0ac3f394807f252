"""Web console RPC: the browser-facing JSON-RPC plane — behavioral
parity with the reference's web handlers (cmd/web-handlers.go:
web.Login issuing a JWT, ListBuckets/ListObjects for the UI,
MakeBucket/DeleteBucket/RemoveObject, presigned share links, and the
/minio/upload / /minio/download byte paths authenticated by the web
token instead of SigV4).

Protocol: JSON-RPC 2.0 POSTs at /minio/webrpc, methods namespaced
`web.*` like the reference (pkg/rpc). Tokens are HMAC-signed
{sub, exp} blobs keyed off the account's secret — the reference signs
JWTs with the credential secret the same way (cmd/jwt.go).
"""

from __future__ import annotations

import base64
import hashlib
import hmac
import io
import json
import time

from .errors import S3Error
from .handlers import Response

WEBRPC_PATH = "/minio/webrpc"
UPLOAD_PREFIX = "/minio/upload/"
DOWNLOAD_PREFIX = "/minio/download/"
CONSOLE_PATHS = ("/minio/console", "/minio/console/")

TOKEN_TTL_S = 24 * 3600


def _sign_token(access_key: str, secret_key: str,
                ttl_s: int = TOKEN_TTL_S) -> str:
    payload = json.dumps({
        "sub": access_key, "exp": time.time() + ttl_s,
    }).encode()
    b64 = base64.urlsafe_b64encode(payload).decode().rstrip("=")
    sig = hmac.new(
        secret_key.encode(), b64.encode(), hashlib.sha256
    ).hexdigest()
    return f"{b64}.{sig}"


def _verify_token(token: str, iam) -> str:
    """Returns the authenticated access key, or raises S3Error."""
    try:
        b64, sig = token.split(".", 1)
        pad = b64 + "=" * (-len(b64) % 4)
        payload = json.loads(base64.urlsafe_b64decode(pad))
        access_key = payload["sub"]
    except Exception as exc:
        raise S3Error("AccessDenied", "malformed web token") from exc
    creds = iam.get_credentials(access_key)
    if creds is None:
        raise S3Error("AccessDenied", "unknown web session account")
    want = hmac.new(
        creds.secret_key.encode(), b64.encode(), hashlib.sha256
    ).hexdigest()
    if not hmac.compare_digest(want, sig):
        raise S3Error("AccessDenied", "bad web token signature")
    if payload.get("exp", 0) < time.time():
        raise S3Error("AccessDenied", "web session expired")
    return access_key


class WebHandlers:
    """JSON-RPC dispatcher + the token-authed byte paths.

    The byte paths DELEGATE to the S3 data-plane handlers (`s3_handlers`)
    rather than touching the object layer directly, so uploads and
    downloads get the identical pipeline — quota admission, retention
    defaults, compression/SSE transforms, events, replication — as a
    SigV4 request (the reference's web handlers call the same
    objectAPI+filter path, cmd/web-handlers.go Upload/Download)."""

    def __init__(self, object_layer, iam, bucket_meta, region="us-east-1",
                 s3_handlers=None):
        self.ol = object_layer
        self.iam = iam
        self.bm = bucket_meta
        self.region = region
        self.h = s3_handlers

    # --- entry points (wired from the S3 server dispatch) ---

    def handles(self, path: str) -> bool:
        return (path == WEBRPC_PATH
                or path in CONSOLE_PATHS
                or path.startswith(UPLOAD_PREFIX)
                or path.startswith(DOWNLOAD_PREFIX))

    def dispatch(self, ctx) -> Response:
        if ctx.path in CONSOLE_PATHS:
            # The embedded single-page UI (ref the reference serving its
            # React bundle from cmd/web-router.go). Unauthenticated:
            # the page itself only works after web.Login.
            from .console_html import CONSOLE_HTML

            return Response(
                200, {"Content-Type": "text/html; charset=utf-8"},
                CONSOLE_HTML.encode(),
            )
        if ctx.path == WEBRPC_PATH:
            return self._rpc(ctx)
        if ctx.path.startswith(UPLOAD_PREFIX):
            return self._upload(ctx)
        return self._download(ctx)

    # --- JSON-RPC plane ---

    _METHODS = {
        "web.Login": "_m_login",
        "web.ServerInfo": "_m_server_info",
        "web.ListBuckets": "_m_list_buckets",
        "web.MakeBucket": "_m_make_bucket",
        "web.DeleteBucket": "_m_delete_bucket",
        "web.ListObjects": "_m_list_objects",
        "web.RemoveObject": "_m_remove_object",
        "web.PresignedGet": "_m_presigned_get",
        "web.ListObjectVersions": "_m_list_object_versions",
        "web.DeleteVersion": "_m_delete_version",
        "web.RestoreVersion": "_m_restore_version",
        "web.GetBucketPolicy": "_m_get_bucket_policy",
        "web.SetBucketPolicy": "_m_set_bucket_policy",
    }

    def _rpc(self, ctx) -> Response:
        if ctx.method != "POST":
            raise S3Error("MethodNotAllowed", ctx.method)
        try:
            req = json.loads(ctx.body or b"{}")
            method = req["method"]
            params = req.get("params", {})
            rpc_id = req.get("id")
        except (ValueError, KeyError) as exc:
            raise S3Error("InvalidRequest", "malformed JSON-RPC") from exc
        name = self._METHODS.get(method)
        if name is None:
            return self._rpc_error(rpc_id, -32601, f"unknown {method}")
        # Every method except Login needs a valid token.
        access_key = None
        if method != "web.Login":
            token = ctx.headers.get("authorization", "")
            token = token.removeprefix("Bearer ").strip()
            access_key = _verify_token(token, self.iam)
        try:
            result = getattr(self, name)(params, access_key)
        except S3Error:
            raise
        except Exception as exc:  # noqa: BLE001 - rpc-shaped failure
            return self._rpc_error(rpc_id, -32000, str(exc))
        return Response(200, {"Content-Type": "application/json"},
                        json.dumps({
                            "jsonrpc": "2.0", "id": rpc_id,
                            "result": result,
                        }).encode())

    @staticmethod
    def _rpc_error(rpc_id, code: int, message: str) -> Response:
        return Response(200, {"Content-Type": "application/json"},
                        json.dumps({
                            "jsonrpc": "2.0", "id": rpc_id,
                            "error": {"code": code, "message": message},
                        }).encode())

    # --- methods (ref web-handlers.go Login/ListBuckets/...) ---

    def _m_login(self, params, _):
        user = params.get("username", "")
        password = params.get("password", "")
        creds = self.iam.get_credentials(user)
        if creds is None or not hmac.compare_digest(
                creds.secret_key.encode(), password.encode()):
            raise S3Error("AccessDenied", "invalid login")
        return {"token": _sign_token(user, password),
                "uiVersion": "mtpu-web-1"}

    def _m_server_info(self, params, access_key):
        import platform

        return {
            "MinioVersion": "minio-tpu/0.1",
            "MinioPlatform": platform.system(),
            "user": access_key,
        }

    def _m_list_buckets(self, params, access_key):
        out = []
        for b in self.ol.list_buckets():
            if b.name.startswith("."):
                continue
            if not self._allowed(access_key, "s3:ListBucket", b.name):
                continue
            out.append({"name": b.name, "creationDate": b.created_ns})
        return {"buckets": out}

    def _m_make_bucket(self, params, access_key):
        bucket = params.get("bucketName", "")
        self._authorize(access_key, "s3:CreateBucket", bucket)
        from .handlers import valid_bucket_name

        if not valid_bucket_name(bucket):
            raise S3Error("InvalidBucketName", bucket)
        self.ol.make_bucket(bucket)
        return {}

    def _m_delete_bucket(self, params, access_key):
        bucket = params.get("bucketName", "")
        self._authorize(access_key, "s3:DeleteBucket", bucket)
        # Through the S3 handler, so the bucket's metadata goes and every
        # peer forgets the bucket, as after an S3 DeleteBucket.
        self.h.delete_bucket(self._sub_ctx("DELETE", bucket, "",
                                           access_key=access_key))
        return {}

    def _m_list_objects(self, params, access_key):
        bucket = params.get("bucketName", "")
        prefix = params.get("prefix", "")
        self._authorize(access_key, "s3:ListBucket", bucket)
        res = self.ol.list_objects(bucket, prefix=prefix, delimiter="/",
                                   marker=params.get("marker", ""))
        from . import transforms

        return {
            "objects": [
                # Logical (client-visible) size, like the S3 listing —
                # never the stored compressed/ciphertext size.
                {"name": o.name,
                 "size": transforms.actual_object_size(
                     o.user_defined, o.size),
                 "etag": o.etag,
                 "lastModified": o.mod_time_ns}
                for o in res.objects
            ],
            "prefixes": list(res.prefixes),
            "isTruncated": res.is_truncated,
            "nextMarker": res.next_marker,
        }

    def _m_remove_object(self, params, access_key):
        """Deletes go through the S3 DeleteObject handler so per-object
        policy, versioning delete markers, retention/legal-hold checks,
        events, and delete replication all apply — the console is not a
        side door around WORM."""
        bucket = params.get("bucketName", "")
        objects = params.get("objects", [])
        for obj in objects:
            # Per-OBJECT authorization: prefix-scoped Deny/Allow must
            # behave exactly as on the S3 plane.
            self._authorize(access_key, "s3:DeleteObject", bucket, obj)
            sub = self._sub_ctx("DELETE", bucket, obj,
                                access_key=access_key)
            self.h.delete_object(sub)
        return {}

    def _m_list_object_versions(self, params, access_key):
        """All versions (incl. delete markers) under a prefix — the
        console's versions view (the reference UI reads versions via its
        SDK; web parity lives here)."""
        bucket = params.get("bucketName", "")
        # objectName filters to ONE key server-side (the console's
        # versions view) so sibling keys sharing the prefix aren't
        # serialized and shipped just to be dropped client-side.
        object_name = params.get("objectName", "")
        prefix = object_name or params.get("prefix", "")
        self._authorize(access_key, "s3:ListBucketVersions", bucket)
        res = self.ol.list_object_versions(
            bucket, prefix=prefix, key_marker=params.get("keyMarker", ""),
            version_id_marker=params.get("versionIdMarker", ""),
        )
        from . import transforms

        versions = []
        for v in res.versions:
            if object_name and v.name != object_name:
                continue
            versions.append({
                "name": v.name,
                "versionId": v.version_id or "null",
                "isLatest": v.is_latest,
                "deleteMarker": v.delete_marker,
                "size": transforms.actual_object_size(
                    v.user_defined, v.size) if not v.delete_marker else 0,
                "etag": v.etag,
                "lastModified": v.mod_time_ns,
            })
        return {
            "versions": versions,
            "isTruncated": res.is_truncated,
            "nextKeyMarker": res.next_key_marker,
            "nextVersionIdMarker": res.next_version_id_marker,
        }

    def _m_delete_version(self, params, access_key):
        """Permanently delete ONE version (or remove a delete marker) —
        through the S3 DeleteObject handler so retention/legal-hold and
        replication semantics hold."""
        bucket = params.get("bucketName", "")
        object_ = params.get("objectName", "")
        version_id = params.get("versionId", "")
        if not version_id:
            raise S3Error("InvalidArgument", "versionId required")
        self._authorize(access_key, "s3:DeleteObjectVersion", bucket, object_)
        sub = self._sub_ctx("DELETE", bucket, object_,
                            access_key=access_key,
                            query=[("versionId", version_id)])
        self.h.delete_object(sub)
        return {}

    def _m_restore_version(self, params, access_key):
        """Make an old version current again: server-side copy of that
        version onto the same key (the S3-native restore idiom; goes
        through the copy handler so events/replication/SSE apply)."""
        bucket = params.get("bucketName", "")
        object_ = params.get("objectName", "")
        version_id = params.get("versionId", "")
        if not version_id:
            raise S3Error("InvalidArgument", "versionId required")
        self._authorize(access_key, "s3:GetObjectVersion", bucket, object_)
        self._authorize(access_key, "s3:PutObject", bucket, object_)
        import urllib.parse

        src = (f"/{urllib.parse.quote(bucket)}/"
               f"{urllib.parse.quote(object_)}?versionId={version_id}")
        sub = self._sub_ctx("PUT", bucket, object_,
                            headers={"x-amz-copy-source": src},
                            access_key=access_key)
        self.h.put_object(sub)
        return {}

    def _m_get_bucket_policy(self, params, access_key):
        bucket = params.get("bucketName", "")
        self._authorize(access_key, "s3:GetBucketPolicy", bucket)
        if not self.ol.bucket_exists(bucket):
            # "no policy set" and "no such bucket" must be
            # distinguishable, like the S3-plane handler.
            raise S3Error("NoSuchBucket", bucket)
        meta = self.bm.get(bucket)
        return {"policy": meta.policy_json or ""}

    def _m_set_bucket_policy(self, params, access_key):
        """Set (or clear, with an empty string) the bucket policy JSON —
        the console's policy editor (ref web.SetBucketPolicy; raw JSON
        instead of the ref's canned none/readonly/readwrite presets,
        which the UI provides as templates client-side)."""
        bucket = params.get("bucketName", "")
        policy = params.get("policy", "")
        self._authorize(access_key, "s3:PutBucketPolicy", bucket)
        if not policy.strip():
            self.h.delete_bucket_policy(
                self._sub_ctx("DELETE", bucket, "", access_key=access_key)
            )
            return {}
        data = policy.encode()
        self.h.put_bucket_policy(self._sub_ctx(
            "PUT", bucket, "", access_key=access_key,
            body_reader=io.BytesIO(data), content_length=len(data),
        ))
        return {}

    def _m_presigned_get(self, params, access_key):
        """Shareable presigned GET URL (ref web.PresignedGet)."""
        bucket = params.get("bucketName", "")
        object_ = params.get("objectName", "")
        expiry = min(int(params.get("expiry", 604800)), 604800)
        self._authorize(access_key, "s3:GetObject", bucket, object_)
        creds = self.iam.get_credentials(access_key)
        from .sign import presign_v4

        host = params.get("host", "")
        qs = presign_v4(
            creds.secret_key, access_key, "GET", host,
            f"/{bucket}/{object_}", region=self.region, expires=expiry,
        )
        return {"url": f"http://{host}/{bucket}/{object_}?{qs}"}

    # --- byte paths (delegate to the S3 data-plane handlers) ---

    def _sub_ctx(self, method: str, bucket: str, object_: str,
                 headers: dict | None = None, body_reader=None,
                 content_length=None, access_key: str = "",
                 query: list | None = None):
        """Synthetic RequestContext addressing /bucket/object so the S3
        handlers run their normal pipeline after web-token auth."""
        from .server import RequestContext

        sub = RequestContext(
            method, f"/{bucket}/{object_}", list(query or []),
            dict(headers or {}),
            body_reader if body_reader is not None else io.BytesIO(b""),
            content_length,
        )
        sub.access_key = access_key
        return sub

    def _upload(self, ctx) -> Response:
        access_key = _verify_token(
            ctx.headers.get("authorization", "").removeprefix("Bearer ")
            .strip(), self.iam,
        )
        bucket, _, object_ = ctx.path[len(UPLOAD_PREFIX):].partition("/")
        if not bucket or not object_:
            raise S3Error("InvalidArgument", "upload path")
        self._authorize(access_key, "s3:PutObject", bucket, object_)
        # STREAM the body through the full S3 PUT pipeline (quota,
        # retention defaults, compression/SSE transforms, events,
        # replication) — never buffered here. Auth headers are stripped
        # so only content/metadata headers flow through.
        headers = {
            k: v for k, v in ctx.raw_headers.items()
            if k.lower() != "authorization"
        }
        sub = self._sub_ctx("PUT", bucket, object_, headers=headers,
                            body_reader=ctx.body_reader,
                            content_length=ctx.content_length,
                            access_key=access_key)
        return self.h.put_object(sub)

    def _download(self, ctx) -> Response:
        # Token accepted from the Authorization header (preferred: never
        # lands in URLs/logs) or the ?token= query (share-link style).
        token = ctx.headers.get("authorization", "") \
            .removeprefix("Bearer ").strip() \
            or dict(ctx.query).get("token", "")
        access_key = _verify_token(token, self.iam)
        bucket, _, object_ = ctx.path[len(DOWNLOAD_PREFIX):].partition("/")
        self._authorize(access_key, "s3:GetObject", bucket, object_)
        # The S3 GET handler streams and runs the decrypt/decompress
        # chain — the browser must receive object CONTENT, never stored
        # ciphertext/compressed frames.
        sub = self._sub_ctx("GET", bucket, object_, access_key=access_key)
        resp = self.h.get_object(sub)
        resp.headers["Content-Disposition"] = (
            f'attachment; filename="{object_.rsplit("/", 1)[-1]}"'
        )
        return resp

    # --- authz ---

    @staticmethod
    def _guard_names(bucket: str, object_: str = ""):
        """Same central guards as the S3 data plane: internal metadata
        buckets are unreachable regardless of policy, and object names
        can't carry traversal segments (server.py _process invariant —
        the web plane must not be a side door around it)."""
        from .handlers import valid_object_name
        from .server import _check_reserved_bucket

        if bucket:
            _check_reserved_bucket(bucket)
        if object_ and not valid_object_name(object_):
            raise S3Error("InvalidArgument",
                          f"invalid object name {object_!r}")

    def _allowed(self, access_key: str, action: str, bucket: str,
                 object_: str = "") -> bool:
        from ..iam.policy import Args

        return self.iam.is_allowed(Args(
            account=access_key, action=action,
            bucket=bucket, object=object_,
        ))

    def _authorize(self, access_key: str, action: str, bucket: str,
                   object_: str = ""):
        self._guard_names(bucket, object_)
        if not self._allowed(access_key, action, bucket, object_):
            raise S3Error("AccessDenied", f"{action} {bucket}/{object_}")
