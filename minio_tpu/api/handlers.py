"""S3 API handlers: bucket + object + multipart endpoints over the
ObjectLayer — behavioral parity with the reference's
cmd/object-handlers.go (4007 LoC), cmd/bucket-handlers.go,
cmd/bucket-listobjects-handlers.go, re-designed as plain request->
response functions (no Go middleware plumbing).

Each handler receives a RequestContext (parsed request) and returns a
Response; signature/authz has already run in server.py dispatch.
"""

from __future__ import annotations

import datetime
import hashlib
import json
import io
import re
import urllib.parse
import xml.etree.ElementTree as ET
from dataclasses import dataclass, field

from ..object.types import CompletePart, ObjectOptions
from ..utils.errors import ErrBucketNotFound, StorageError
from .errors import S3Error, from_object_error

MAX_OBJECT_SIZE = 5 * 1024 ** 4         # 5 TiB
MAX_PART_SIZE = 5 * 1024 ** 3           # 5 GiB
MAX_PARTS = 10000
MAX_DELETE_OBJECTS = 1000
MAX_KEY_LENGTH = 1024


def iso8601(ns: int) -> str:
    dt = datetime.datetime.fromtimestamp(ns / 1e9, datetime.timezone.utc)
    return dt.strftime("%Y-%m-%dT%H:%M:%S.") + f"{dt.microsecond // 1000:03d}Z"


def http_date(ns: int) -> str:
    dt = datetime.datetime.fromtimestamp(ns / 1e9, datetime.timezone.utc)
    return dt.strftime("%a, %d %b %Y %H:%M:%S GMT")


@dataclass
class Response:
    status: int = 200
    headers: dict = field(default_factory=dict)
    body: bytes = b""
    # Streaming mode: callable(dst) that writes the body to dst. Headers
    # (incl. Content-Length) must be final before streaming starts;
    # mid-stream failures abort the connection (the status line is gone).
    body_stream: object = None

    @classmethod
    def xml(cls, root: ET.Element, status: int = 200,
            headers: dict | None = None) -> "Response":
        body = (
            b'<?xml version="1.0" encoding="UTF-8"?>\n'
            + ET.tostring(root, encoding="unicode").encode()
        )
        h = {"Content-Type": "application/xml"}
        h.update(headers or {})
        return cls(status, h, body)


class _NullSink:
    def write(self, b) -> int:
        return len(b)


def _xml_root(tag: str) -> ET.Element:
    root = ET.Element(tag)
    root.set("xmlns", "http://s3.amazonaws.com/doc/2006-03-01/")
    return root


def valid_bucket_name(bucket: str) -> bool:
    """S3 DNS-compatible bucket naming rules; 'minio' is reserved for the
    health/metrics/admin route namespace (ref cmd/generic-handlers.go
    minioReservedBucket)."""
    if bucket == "minio":
        return False
    if not (3 <= len(bucket) <= 63):
        return False
    if bucket.startswith((".", "-")) or bucket.endswith((".", "-")):
        return False
    if ".." in bucket or ".-" in bucket or "-." in bucket:
        return False
    return all(c.islower() or c.isdigit() or c in ".-" for c in bucket)


class _RangeCopyReader:
    """Stream a source-object range in 1 MiB pulls so UploadPartCopy never
    buffers a whole (up to 5 GiB) part in memory."""

    def __init__(self, ol, bucket, object_, offset, length, opts):
        self._ol = ol
        self._bucket = bucket
        self._object = object_
        self._pos = offset
        self._left = length
        self._opts = opts

    def read(self, n: int = -1) -> bytes:
        if self._left <= 0:
            return b""
        if n is None or n < 0:
            n = self._left
        n = min(n, self._left, 1 << 20)
        data = self._ol.get_object_bytes(
            self._bucket, self._object, offset=self._pos, length=n,
            opts=self._opts,
        )
        self._pos += len(data)
        self._left -= len(data)
        if not data:
            self._left = 0
        return data


def _parse_http_date(h: str) -> int | None:
    """RFC 7231 IMF-fixdate -> epoch seconds; None if unparseable (the
    one shared parse behind every conditional-header site)."""
    try:
        return int(datetime.datetime.strptime(
            h, "%a, %d %b %Y %H:%M:%S GMT"
        ).replace(tzinfo=datetime.timezone.utc).timestamp())
    except ValueError:
        return None


def _etag_matches(header_value: str, etag: str) -> bool:
    """True when the header's ETag (quoted, bare, or '*') names `etag` —
    shared by the GET (304) and copy-source (412) precondition checks."""
    return header_value in (f'"{etag}"', etag, "*")


def parse_copy_source(header: str) -> tuple[str, str, str]:
    """Parse x-amz-copy-source into (bucket, object, versionId).

    Shared by the dispatch layer (source authorization) and the copy
    handler (ref cmd/object-handlers.go CopyObjectHandler source parse).
    """
    # Split the versionId suffix BEFORE percent-decoding: clients encode a
    # literal '?' in the key as %3F precisely to disambiguate it from the
    # version marker.
    raw, vid = header, ""
    if "?versionId=" in raw:
        raw, _, vid = raw.partition("?versionId=")
    src = urllib.parse.unquote(raw)
    if src.startswith("/"):
        src = src[1:]
    if "/" not in src:
        raise S3Error("InvalidArgument", "bad x-amz-copy-source")
    sbucket, _, sobject = src.partition("/")
    if not sbucket or not valid_object_name(sobject):
        raise S3Error("InvalidArgument", "bad x-amz-copy-source")
    return sbucket, sobject, urllib.parse.unquote(vid)


def valid_object_name(obj: str) -> bool:
    if not obj or len(obj) > MAX_KEY_LENGTH:
        return False
    if obj.startswith("/"):
        return False
    for seg in obj.split("/"):
        if seg in (".", ".."):
            return False
    return True


def parse_range(header: str, size: int) -> tuple[int, int] | None:
    """Parse 'bytes=a-b' into (offset, length); None = whole object
    (ref cmd/httprange.go)."""
    if not header:
        return None
    if not header.startswith("bytes="):
        raise S3Error("InvalidRange", header)
    spec = header[len("bytes="):]
    if "," in spec:
        raise S3Error("NotImplemented", "multiple ranges")
    start_s, _, end_s = spec.partition("-")
    try:
        if start_s == "":
            # suffix range: last N bytes
            n = int(end_s)
            if n <= 0:
                raise S3Error("InvalidRange", header)
            off = max(0, size - n)
            return off, size - off
        start = int(start_s)
        if end_s == "":
            if start >= size:
                raise S3Error("InvalidRange", header)
            return start, size - start
        end = int(end_s)
        if start > end or start >= size:
            raise S3Error("InvalidRange", header)
        end = min(end, size - 1)
        return start, end - start + 1
    except ValueError as exc:
        raise S3Error("InvalidRange", header) from exc


_RESPONSE_OVERRIDES = {
    "response-content-type": "Content-Type",
    "response-content-language": "Content-Language",
    "response-expires": "Expires",
    "response-cache-control": "Cache-Control",
    "response-content-disposition": "Content-Disposition",
    "response-content-encoding": "Content-Encoding",
}

_REMEMBERED_HEADERS = (
    "content-type", "cache-control", "content-disposition",
    "content-encoding", "content-language", "expires",
)


def extract_user_metadata(headers: dict) -> dict:
    """x-amz-meta-* + standard content headers -> stored metadata
    (ref cmd/utils.go extractMetadata)."""
    meta = {}
    for k, v in headers.items():
        lk = k.lower()
        if lk.startswith("x-amz-meta-"):
            meta[lk] = v
        elif lk in _REMEMBERED_HEADERS:
            meta[lk] = v
        elif lk == "x-amz-storage-class":
            meta["x-amz-storage-class"] = v.upper()
    return meta


class S3ApiHandlers:
    """All S3 endpoints bound to an ObjectLayer + subsystems."""

    def __init__(self, object_layer, bucket_meta, iam, notify=None,
                 config=None, sse_config=None, repl_pool=None, quota=None,
                 tier_engine=None, notification=None):
        from ..bucket.quota import BucketQuotaSys

        self.ol = object_layer
        self.bm = bucket_meta
        self.iam = iam
        self.notify = notify
        # The peer broadcast (distributed/peer.NotificationSys); None on
        # a single node.
        self.notification = notification
        self.config = config
        self.sse_config = sse_config
        self.repl = repl_pool
        self.quota = quota or BucketQuotaSys(object_layer, bucket_meta)
        self.tier_engine = tier_engine

    # ---------- object lock helpers (ref cmd/bucket-object-lock.go) -------

    def _lock_config(self, bucket: str):
        from ..bucket import objectlock as ol_mod

        xml_text = self.bm.get(bucket).object_lock_xml
        if not xml_text:
            return None
        try:
            return ol_mod.LockConfig.parse(xml_text)
        except Exception:  # noqa: BLE001 - malformed config never blocks IO
            return None

    def _apply_object_lock(self, ctx, opts):
        """Validate x-amz-object-lock-* headers / apply the bucket default
        retention to a new write (ref ParseObjectLockHeaders +
        default-retention in PutObjectHandler)."""
        from ..bucket import objectlock as ol_mod

        try:
            explicit = ol_mod.extract_lock_headers(ctx.headers)
        except ValueError as exc:
            raise S3Error("InvalidArgument", str(exc)) from exc
        cfg = self._lock_config(ctx.bucket)
        if explicit:
            if cfg is None or not cfg.enabled:
                raise S3Error(
                    "InvalidRequest",
                    "Bucket is missing ObjectLockConfiguration",
                )
            opts.user_defined.update(explicit)
        elif cfg is not None:
            opts.user_defined.update(cfg.default_retention_meta())

    def _enforce_retention(self, ctx, bucket: str, object_: str,
                           version_id: str):
        """Refuse deleting a retained/held version
        (ref enforceRetentionBypassForDelete)."""
        from ..bucket import objectlock as ol_mod

        try:
            oi = self.ol.get_object_info(
                bucket, object_,
                ObjectOptions(version_id=version_id,
                              versioned=bool(version_id)),
            )
        except StorageError:
            return  # missing/marker: nothing to retain
        bypass = (
            ctx.headers.get(ol_mod.HDR_BYPASS_GOVERNANCE, "").lower()
            == "true"
        )
        reason = ol_mod.check_deletable(oi.user_defined, bypass)
        if reason is not None:
            raise S3Error("AccessDenied", reason)

    # ---------- replication hooks (ref cmd/bucket-replication.go) ----------

    def _repl_rule(self, bucket: str, key: str):
        if self.repl is None:
            return None
        bmeta = self.bm.get(bucket)
        if not bmeta.replication_xml:
            return None
        from ..replication.config import ReplicationConfig

        try:
            return ReplicationConfig.parse(bmeta.replication_xml).rule_for(key)
        except Exception:  # noqa: BLE001 - malformed config never blocks IO
            return None

    def _schedule_replication(self, bucket: str, key: str,
                              version_id: str, op: str):
        from ..replication.pool import ReplicationTask

        self.repl.schedule(ReplicationTask(
            bucket=bucket, object=key, version_id=version_id, op=op,
        ))

    def _opts_for(self, bucket: str, query: dict,
                  headers: dict | None = None) -> ObjectOptions:
        bmeta = self.bm.get(bucket)
        # versionId="null" stays the literal sentinel here so the object
        # layer still sees a TARGETED request (a null-targeted delete must
        # remove the null version, not lay down a delete marker); the
        # xl.meta journal maps it to the internal empty version id.
        return ObjectOptions(
            version_id=query.get("versionId", ""),
            versioned=bmeta.versioning_enabled,
            version_suspended=bmeta.versioning_suspended,
        )

    def _event(self, name: str, bucket: str, oi=None, key: str = ""):
        if self.notify is not None:
            self.notify.send(name, bucket, oi=oi, key=key)

    # ---------- service ----------

    def list_buckets(self, ctx) -> Response:
        root = _xml_root("ListAllMyBucketsResult")
        owner = ET.SubElement(root, "Owner")
        ET.SubElement(owner, "ID").text = "minio-tpu"
        ET.SubElement(owner, "DisplayName").text = "minio-tpu"
        buckets = ET.SubElement(root, "Buckets")
        for b in self.ol.list_buckets():
            if b.name.startswith("."):  # hide .minio.sys
                continue
            be = ET.SubElement(buckets, "Bucket")
            ET.SubElement(be, "Name").text = b.name
            ET.SubElement(be, "CreationDate").text = iso8601(b.created_ns)
        return Response.xml(root)

    # ---------- bucket ----------

    def make_bucket(self, ctx) -> Response:
        if not valid_bucket_name(ctx.bucket):
            raise S3Error("InvalidBucketName", ctx.bucket)
        try:
            self.ol.make_bucket(ctx.bucket)
        except StorageError as exc:
            raise from_object_error(exc) from exc
        self._event("s3:BucketCreated:*", ctx.bucket)
        return Response(200, {"Location": "/" + ctx.bucket})

    def head_bucket(self, ctx) -> Response:
        if not self.ol.bucket_exists(ctx.bucket):
            raise S3Error("NoSuchBucket", ctx.bucket)
        return Response(200)

    def delete_bucket(self, ctx) -> Response:
        force = ctx.headers.get("x-minio-force-delete", "") == "true"
        try:
            self.ol.delete_bucket(ctx.bucket, force=force)
        except StorageError as exc:
            raise from_object_error(exc) from exc
        self.bm.delete(ctx.bucket)
        if self.notification is not None:
            # Every other node forgets the bucket's metadata and its memo
            # of the bucket now, not when the memo lapses (ref
            # DeleteBucketHandler -> DeleteBucketMetadata).
            self.notification.delete_bucket_metadata(ctx.bucket)
        self._event("s3:BucketRemoved:*", ctx.bucket)
        return Response(204)

    def get_bucket_location(self, ctx) -> Response:
        self._check_bucket(ctx.bucket)
        root = _xml_root("LocationConstraint")
        root.text = ""  # us-east-1 == empty
        return Response.xml(root)

    def _check_bucket(self, bucket: str):
        """The object layer's check, which answers from its memo of
        buckets seen on the drives; HeadBucket alone asks every drive."""
        try:
            self.ol.check_bucket(bucket)
        except ErrBucketNotFound as exc:
            raise S3Error("NoSuchBucket", bucket) from exc

    def listen_notification(self, ctx) -> Response:
        """GET /bucket?events=...&prefix=&suffix= — live bucket event
        feed (ref ListenNotificationHandler, cmd/bucket-notification-
        handlers.go:160): newline-delimited JSON records streamed as
        events happen, blank-line keepalives every few seconds, ended by
        client disconnect. MinIO-extension API used by `mc watch`."""
        self._check_bucket(ctx.bucket)
        if self.notify is None:
            raise S3Error("NotImplemented", "no event notifier")
        from ..event.rules import TargetRule, expand_name, valid_event_name

        want_events: list[str] = []
        for k, v in ctx.query:
            if k == "events" and v:
                if not valid_event_name(v):
                    # ref ParseName errors on unknown event names — a
                    # silent never-matching stream helps nobody.
                    raise S3Error("InvalidArgument",
                                  f"unknown event name {v!r}")
                want_events.extend(expand_name(v))
        if not want_events:
            raise S3Error("InvalidArgument", "events parameter required")
        # One shared matcher with the notification targets — the listen
        # filter must never diverge from rule-target semantics.
        rule = TargetRule(
            arn="", events=want_events,
            prefix=ctx.qdict.get("prefix", ""),
            suffix=ctx.qdict.get("suffix", ""),
        )
        bucket = ctx.bucket
        notify = self.notify

        def stream(dst):
            import queue as _queue

            sub = notify.subscribe()
            try:
                while True:
                    try:
                        name, b, key, payload = sub.get(timeout=5.0)
                    except _queue.Empty:
                        # Keepalive: lets dead clients surface as write
                        # errors instead of leaking subscriptions.
                        dst.write(b"\n")
                        dst.flush()
                        continue
                    if b != bucket or not rule.matches(name, key):
                        continue
                    dst.write(json.dumps(
                        {"Records": payload.get("Records", [])}
                    ).encode() + b"\n")
                    dst.flush()
            except (BrokenPipeError, ConnectionResetError, OSError):
                return  # client hung up: normal end of a watch
            finally:
                notify.unsubscribe(sub)

        resp = Response(
            200, {"Content-Type": "application/json"}, body_stream=stream
        )
        resp.unbounded_stream = True
        return resp

    # --- dummy bucket subresources (ref cmd/dummy-handlers.go): canned
    # S3-shaped answers for SDK feature probes ---

    def get_bucket_cors(self, ctx) -> Response:
        self._check_bucket(ctx.bucket)
        raise S3Error("NoSuchCORSConfiguration", ctx.bucket)

    def get_bucket_website(self, ctx) -> Response:
        self._check_bucket(ctx.bucket)
        raise S3Error("NoSuchWebsiteConfiguration", ctx.bucket)

    def delete_bucket_website(self, ctx) -> Response:
        self._check_bucket(ctx.bucket)
        return Response(200)

    def get_bucket_accelerate(self, ctx) -> Response:
        self._check_bucket(ctx.bucket)
        return Response.xml(_xml_root("AccelerateConfiguration"))

    def get_bucket_request_payment(self, ctx) -> Response:
        self._check_bucket(ctx.bucket)
        root = _xml_root("RequestPaymentConfiguration")
        ET.SubElement(root, "Payer").text = "BucketOwner"
        return Response.xml(root)

    def get_bucket_logging(self, ctx) -> Response:
        self._check_bucket(ctx.bucket)
        return Response.xml(_xml_root("BucketLoggingStatus"))

    def get_bucket_policy_status(self, ctx) -> Response:
        # ref GetBucketPolicyStatusHandler: IsPublic == the policy has
        # an Allow statement granting to the wildcard principal. Parsed
        # structurally: a Deny-all policy or a wildcard Action with a
        # specific principal must NOT read as public.
        self._check_bucket(ctx.bucket)
        # Metadata load OUTSIDE the try: a storage failure must surface
        # as an error, never masquerade as IsPublic=FALSE.
        meta = self.bm.get(ctx.bucket)
        public = False
        try:
            import json as _json

            doc = _json.loads(meta.policy_json) if meta.policy_json else {}
            stmts = doc.get("Statement") or []
            if isinstance(stmts, dict):
                stmts = [stmts]
            for s in stmts:
                if s.get("Effect") != "Allow":
                    continue
                pr = s.get("Principal")
                aws = pr.get("AWS") if isinstance(pr, dict) else pr
                if isinstance(aws, str):
                    aws = [aws]
                if aws and "*" in aws:
                    public = True
                    break
        except Exception:  # noqa: BLE001 - unparseable = not public
            public = False
        root = _xml_root("PolicyStatus")
        ET.SubElement(root, "IsPublic").text = "TRUE" if public else "FALSE"
        return Response.xml(root)

    # --- listing ---

    def list_objects_v1(self, ctx) -> Response:
        self._check_bucket(ctx.bucket)
        q = ctx.qdict
        prefix = q.get("prefix", "")
        marker = q.get("marker", "")
        delimiter = q.get("delimiter", "")
        max_keys = min(int(q.get("max-keys", "1000") or "1000"), 1000)
        if max_keys < 0:
            raise S3Error("InvalidArgument", "max-keys negative")
        try:
            res = self.ol.list_objects(
                ctx.bucket, prefix=prefix, marker=marker,
                delimiter=delimiter, max_keys=max_keys,
            )
        except StorageError as exc:
            raise from_object_error(exc) from exc
        encode = self._listing_encoder(ctx)
        enc = encode or (lambda s: s)
        root = _xml_root("ListBucketResult")
        ET.SubElement(root, "Name").text = ctx.bucket
        # Under encoding-type=url EVERY key-derived element is encoded
        # (Prefix/Marker/NextMarker/Delimiter) — NextMarker is the one
        # clients must echo back, and raw bytes there defeat the point.
        ET.SubElement(root, "Prefix").text = enc(prefix)
        ET.SubElement(root, "Marker").text = enc(marker)
        ET.SubElement(root, "MaxKeys").text = str(max_keys)
        if delimiter:
            ET.SubElement(root, "Delimiter").text = enc(delimiter)
        ET.SubElement(root, "IsTruncated").text = (
            "true" if res.is_truncated else "false"
        )
        if res.is_truncated and res.next_marker:
            ET.SubElement(root, "NextMarker").text = enc(res.next_marker)
        if encode is not None:
            ET.SubElement(root, "EncodingType").text = "url"
        self._fill_entries(root, res, encode=encode)
        return Response.xml(root)

    def list_objects_v2(self, ctx) -> Response:
        self._check_bucket(ctx.bucket)
        q = ctx.qdict
        prefix = q.get("prefix", "")
        delimiter = q.get("delimiter", "")
        max_keys = min(int(q.get("max-keys", "1000") or "1000"), 1000)
        token = q.get("continuation-token", "")
        start_after = q.get("start-after", "")
        fetch_owner = q.get("fetch-owner", "") == "true"
        marker = token or start_after
        if token:
            import base64

            try:
                marker = base64.b64decode(token).decode()
            except Exception as exc:
                raise S3Error(
                    "InvalidArgument", "bad continuation-token"
                ) from exc
        try:
            res = self.ol.list_objects(
                ctx.bucket, prefix=prefix, marker=marker,
                delimiter=delimiter, max_keys=max_keys,
            )
        except StorageError as exc:
            raise from_object_error(exc) from exc
        encode = self._listing_encoder(ctx)
        enc = encode or (lambda s: s)
        root = _xml_root("ListBucketResult")
        ET.SubElement(root, "Name").text = ctx.bucket
        ET.SubElement(root, "Prefix").text = enc(prefix)
        ET.SubElement(root, "MaxKeys").text = str(max_keys)
        if delimiter:
            ET.SubElement(root, "Delimiter").text = enc(delimiter)
        if start_after:
            ET.SubElement(root, "StartAfter").text = enc(start_after)
        ET.SubElement(root, "KeyCount").text = str(
            len(res.objects) + len(res.prefixes)
        )
        ET.SubElement(root, "IsTruncated").text = (
            "true" if res.is_truncated else "false"
        )
        if token:
            ET.SubElement(root, "ContinuationToken").text = token
        if res.is_truncated and res.next_marker:
            import base64

            # Continuation tokens are opaque b64 — already XML-safe.
            ET.SubElement(root, "NextContinuationToken").text = (
                base64.b64encode(res.next_marker.encode()).decode()
            )
        if encode is not None:
            ET.SubElement(root, "EncodingType").text = "url"
        self._fill_entries(root, res, owner=fetch_owner, encode=encode)
        return Response.xml(root)

    def list_object_versions(self, ctx) -> Response:
        """GET /bucket?versions (ref ListObjectVersionsHandler,
        cmd/bucket-listobjects-handlers.go:214-352)."""
        self._check_bucket(ctx.bucket)
        q = ctx.qdict
        prefix = q.get("prefix", "")
        key_marker = q.get("key-marker", "")
        vid_marker = q.get("version-id-marker", "")
        delimiter = q.get("delimiter", "")
        max_keys = min(int(q.get("max-keys", "1000") or "1000"), 1000)
        if max_keys < 0:
            raise S3Error("InvalidArgument", "max-keys negative")
        if vid_marker and not key_marker:
            raise S3Error(
                "InvalidArgument", "version-id-marker without key-marker"
            )
        try:
            res = self.ol.list_object_versions(
                ctx.bucket, prefix=prefix, key_marker=key_marker,
                version_id_marker=vid_marker, delimiter=delimiter,
                max_keys=max_keys,
            )
        except StorageError as exc:
            raise from_object_error(exc) from exc
        # encoding-type=url applies to this listing too (boto3 sends it
        # by default and url-decodes the response — ignoring it would
        # hand clients decoded keys that 404 on the next request).
        encode = self._listing_encoder(ctx)
        enc = encode or (lambda s: s)
        root = _xml_root("ListVersionsResult")
        ET.SubElement(root, "Name").text = ctx.bucket
        ET.SubElement(root, "Prefix").text = enc(prefix)
        ET.SubElement(root, "KeyMarker").text = enc(key_marker)
        if vid_marker:
            ET.SubElement(root, "VersionIdMarker").text = vid_marker
        ET.SubElement(root, "MaxKeys").text = str(max_keys)
        if delimiter:
            ET.SubElement(root, "Delimiter").text = enc(delimiter)
        if encode is not None:
            ET.SubElement(root, "EncodingType").text = "url"
        ET.SubElement(root, "IsTruncated").text = (
            "true" if res.is_truncated else "false"
        )
        if res.is_truncated:
            ET.SubElement(root, "NextKeyMarker").text = enc(
                res.next_key_marker
            )
            ET.SubElement(root, "NextVersionIdMarker").text = (
                res.next_version_id_marker
            )
        for oi in res.versions:
            tag = "DeleteMarker" if oi.delete_marker else "Version"
            v = ET.SubElement(root, tag)
            ET.SubElement(v, "Key").text = enc(oi.name)
            ET.SubElement(v, "VersionId").text = oi.version_id or "null"
            ET.SubElement(v, "IsLatest").text = (
                "true" if oi.is_latest else "false"
            )
            ET.SubElement(v, "LastModified").text = iso8601(oi.mod_time_ns)
            if not oi.delete_marker:
                ET.SubElement(v, "ETag").text = f'"{oi.etag}"'
                ET.SubElement(v, "Size").text = str(oi.size)
                ET.SubElement(v, "StorageClass").text = "STANDARD"
            o = ET.SubElement(v, "Owner")
            ET.SubElement(o, "ID").text = "minio-tpu"
            ET.SubElement(o, "DisplayName").text = "minio-tpu"
        for p in res.prefixes:
            cp = ET.SubElement(root, "CommonPrefixes")
            ET.SubElement(cp, "Prefix").text = enc(p)
        return Response.xml(root)

    @staticmethod
    def _listing_encoder(ctx):
        """encoding-type=url (ref ListObjects EncodingType): keys with
        characters XML 1.0 can't carry are URL-encoded on request."""
        enc = ctx.qdict.get("encoding-type", "")
        if not enc:
            return None
        if enc != "url":
            raise S3Error("InvalidArgument",
                          f"encoding-type {enc!r} (only 'url')")
        return lambda s: urllib.parse.quote(s, safe="/")

    def _fill_entries(self, root, res, owner: bool = True, encode=None):
        enc = encode or (lambda s: s)
        for oi in res.objects:
            c = ET.SubElement(root, "Contents")
            ET.SubElement(c, "Key").text = enc(oi.name)
            ET.SubElement(c, "LastModified").text = iso8601(oi.mod_time_ns)
            ET.SubElement(c, "ETag").text = f'"{oi.etag}"'
            ET.SubElement(c, "Size").text = str(oi.size)
            ET.SubElement(c, "StorageClass").text = "STANDARD"
            if owner:
                o = ET.SubElement(c, "Owner")
                ET.SubElement(o, "ID").text = "minio-tpu"
                ET.SubElement(o, "DisplayName").text = "minio-tpu"
        for p in res.prefixes:
            cp = ET.SubElement(root, "CommonPrefixes")
            ET.SubElement(cp, "Prefix").text = enc(p)

    def delete_multiple_objects(self, ctx) -> Response:
        self._check_bucket(ctx.bucket)
        try:
            req = ET.fromstring(ctx.body)
        except ET.ParseError as exc:
            raise S3Error("MalformedXML", str(exc)) from exc
        ns = "{http://s3.amazonaws.com/doc/2006-03-01/}"
        objects = []
        quiet = False
        for el in req:
            tag = el.tag.removeprefix(ns)
            if tag == "Quiet":
                quiet = (el.text or "").strip() == "true"
            elif tag == "Object":
                key = ""
                vid = ""
                for sub in el:
                    st = sub.tag.removeprefix(ns)
                    if st == "Key":
                        key = sub.text or ""
                    elif st == "VersionId":
                        vid = sub.text or ""
                if key:
                    objects.append((key, vid))
        if len(objects) > MAX_DELETE_OBJECTS:
            raise S3Error("InvalidRequest", "too many objects")
        root = _xml_root("DeleteResult")
        for key, vid in objects:
            try:
                opts = self._opts_for(ctx.bucket, {"versionId": vid})
                # The bulk path destroys data exactly like the single
                # DELETE, so it enforces retention/legal hold identically
                # (ref DeleteMultipleObjectsHandler ->
                # enforceRetentionBypassForDelete per object).
                try:
                    if vid:
                        self._enforce_retention(ctx, ctx.bucket, key, vid)
                    elif not opts.versioned:
                        self._enforce_retention(ctx, ctx.bucket, key, "")
                except S3Error as s3e:
                    e = ET.SubElement(root, "Error")
                    ET.SubElement(e, "Key").text = key
                    if vid:
                        ET.SubElement(e, "VersionId").text = vid
                    ET.SubElement(e, "Code").text = s3e.api.code
                    ET.SubElement(e, "Message").text = str(s3e)
                    continue
                self.ol.delete_object(ctx.bucket, key, opts)
                if not quiet:
                    d = ET.SubElement(root, "Deleted")
                    ET.SubElement(d, "Key").text = key
                    if vid:
                        ET.SubElement(d, "VersionId").text = vid
                self._event("s3:ObjectRemoved:Delete", ctx.bucket, key=key)
            except StorageError as exc:
                api = from_object_error(exc)
                if api.api.code in ("NoSuchKey", "NoSuchVersion"):
                    if not quiet:
                        d = ET.SubElement(root, "Deleted")
                        ET.SubElement(d, "Key").text = key
                    continue
                e = ET.SubElement(root, "Error")
                ET.SubElement(e, "Key").text = key
                ET.SubElement(e, "Code").text = api.api.code
                ET.SubElement(e, "Message").text = api.detail
        return Response.xml(root)

    # --- bucket config subresources ---

    def put_bucket_policy(self, ctx) -> Response:
        self._check_bucket(ctx.bucket)
        from ..iam.policy import Policy

        try:
            Policy.parse(ctx.body)
        except (ValueError, KeyError) as exc:
            raise S3Error("MalformedXML", f"bad policy: {exc}") from exc
        self.bm.update(ctx.bucket, "policy_json", ctx.body.decode())
        return Response(204)

    def get_bucket_policy(self, ctx) -> Response:
        self._check_bucket(ctx.bucket)
        bm = self.bm.get(ctx.bucket)
        if not bm.policy_json:
            raise S3Error("NoSuchBucketPolicy", ctx.bucket)
        return Response(
            200, {"Content-Type": "application/json"},
            bm.policy_json.encode(),
        )

    def delete_bucket_policy(self, ctx) -> Response:
        self._check_bucket(ctx.bucket)
        self.bm.update(ctx.bucket, "policy_json", "")
        return Response(204)

    def _xml_subresource(self, ctx, fld: str, missing_code: str,
                         root_tag: str | None = None, pre_put=None):
        """GET/PUT/DELETE for the XML-blob bucket subresources."""
        self._check_bucket(ctx.bucket)
        if ctx.method == "GET":
            bm = self.bm.get(ctx.bucket)
            val = getattr(bm, fld)
            if not val:
                raise S3Error(missing_code, ctx.bucket)
            return Response(200, {"Content-Type": "application/xml"},
                            val.encode())
        if ctx.method == "PUT":
            if pre_put is not None:
                pre_put()
            try:
                ET.fromstring(ctx.body)
            except ET.ParseError as exc:
                raise S3Error("MalformedXML", str(exc)) from exc
            self.bm.update(ctx.bucket, fld, ctx.body.decode())
            return Response(200)
        self.bm.update(ctx.bucket, fld, "")
        return Response(204)

    def bucket_versioning(self, ctx) -> Response:
        self._check_bucket(ctx.bucket)
        if ctx.method == "PUT":
            try:
                root = ET.fromstring(ctx.body)
            except ET.ParseError as exc:
                raise S3Error("MalformedXML", str(exc)) from exc
            status = ""
            for el in root.iter():
                if el.tag.endswith("Status"):
                    status = (el.text or "").strip()
            if status != "Enabled" and self.bm.get(ctx.bucket).replication_xml:
                # Suspending versioning would silently break delete-marker
                # replication (ref cmd/bucket-handlers.go
                # PutBucketVersioningHandler replication/lock guards).
                raise S3Error(
                    "InvalidBucketState",
                    "A replication configuration is present on this bucket, "
                    "so the versioning state cannot be suspended.",
                )
            self.bm.update(ctx.bucket, "versioning_xml", ctx.body.decode())
            return Response(200)
        bm = self.bm.get(ctx.bucket)
        if bm.versioning_xml:
            return Response(200, {"Content-Type": "application/xml"},
                            bm.versioning_xml.encode())
        root = _xml_root("VersioningConfiguration")
        return Response.xml(root)

    def bucket_tagging(self, ctx) -> Response:
        return self._xml_subresource(ctx, "tagging_xml", "NoSuchTagSet")

    def bucket_lifecycle(self, ctx) -> Response:
        def validate():
            # Full rule validation at write time (ref lifecycle.go
            # ParseLifecycleConfig + Validate) — an invalid document
            # must 400 here, never silently no-op in the scanner.
            # Unparseable XML is MalformedXML (the AWS code for it);
            # well-formed-but-invalid rules are InvalidArgument.
            from ..bucket.lifecycle import Lifecycle, LifecycleError

            try:
                lc = Lifecycle.parse(ctx.body.decode())
            except LifecycleError as exc:
                raise S3Error("MalformedXML", str(exc)) from exc
            try:
                lc.validate()
            except LifecycleError as exc:
                raise S3Error("InvalidArgument", str(exc)) from exc

        return self._xml_subresource(
            ctx, "lifecycle_xml", "NoSuchLifecycleConfiguration",
            pre_put=validate,
        )

    def bucket_encryption(self, ctx) -> Response:
        return self._xml_subresource(
            ctx, "sse_xml", "ServerSideEncryptionConfigurationNotFoundError"
        )

    def bucket_object_lock(self, ctx) -> Response:
        # Object lock requires versioning (WORM versions) and a valid
        # config (ref PutBucketObjectLockConfigHandler).
        def _validate():
            if not self.bm.get(ctx.bucket).versioning_enabled:
                raise S3Error(
                    "InvalidBucketState",
                    "Versioning must be 'Enabled' on the bucket to apply "
                    "an Object Lock configuration.",
                )
            from ..bucket import objectlock as ol_mod

            try:
                ol_mod.LockConfig.parse(ctx.body.decode())
            except ET.ParseError as exc:
                raise S3Error("MalformedXML", str(exc)) from exc
            except ValueError as exc:
                raise S3Error("InvalidArgument", str(exc)) from exc

        return self._xml_subresource(
            ctx, "object_lock_xml", "ObjectLockConfigurationNotFoundError",
            pre_put=_validate,
        )

    # ---------- object retention / legal hold (ref cmd/object-handlers.go
    # PutObjectRetentionHandler / PutObjectLegalHoldHandler) ----------

    def _lock_target_info(self, ctx):
        vid = ctx.qdict.get("versionId", "")
        opts = ObjectOptions(version_id=vid,
                             versioned=self.bm.get(ctx.bucket)
                             .versioning_enabled)
        try:
            return self.ol.get_object_info(ctx.bucket, ctx.object, opts)
        except StorageError as exc:
            raise from_object_error(exc) from exc

    # ---------- object tagging (ref cmd/object-handlers.go
    # PutObjectTaggingHandler/GetObjectTaggingHandler; tags live in the
    # version's metadata like the reference's UserTags) ----------

    TAGS_META_KEY = "x-mtpu-internal-tags"
    MAX_TAGS = 10

    def _validate_tags(self, tags: list[tuple[str, str]]):
        """One rule set for BOTH tag write paths (subresource XML and
        the x-amz-tagging header)."""
        if len(tags) > self.MAX_TAGS:
            raise S3Error("InvalidTag", f"more than {self.MAX_TAGS} tags")
        if len({k for k, _ in tags}) != len(tags):
            raise S3Error("InvalidTag", "duplicate tag keys")
        for k, v in tags:
            if not k or len(k) > 128 or len(v) > 256:
                raise S3Error("InvalidTag", f"bad tag {k!r}")

    def _tag_target_info(self, ctx):
        """Resolve the tagging/ACL target; a delete-markered latest is
        NoSuchKey like GET/HEAD (AWS: these verbs 404 on deleted keys)."""
        opts = self._opts_for(ctx.bucket, ctx.qdict)
        try:
            oi = self.ol.get_object_info(ctx.bucket, ctx.object, opts)
        except StorageError as exc:
            raise from_object_error(exc) from exc
        if oi.delete_marker:
            raise S3Error("NoSuchKey", ctx.object)
        return oi, opts

    def get_object_tagging(self, ctx) -> Response:
        self._check_bucket(ctx.bucket)
        oi, opts = self._tag_target_info(ctx)
        tags = urllib.parse.parse_qsl(
            oi.user_defined.get(self.TAGS_META_KEY, ""),
            keep_blank_values=True,
        )
        root = ET.Element("Tagging")
        ts = ET.SubElement(root, "TagSet")
        for k, v in tags:
            tag = ET.SubElement(ts, "Tag")
            ET.SubElement(tag, "Key").text = k
            ET.SubElement(tag, "Value").text = v
        headers = {}
        if oi.version_id and oi.version_id != "null":
            headers["x-amz-version-id"] = oi.version_id
        resp = Response.xml(root)
        resp.headers.update(headers)
        return resp

    def put_object_tagging(self, ctx) -> Response:
        self._check_bucket(ctx.bucket)
        _, opts = self._tag_target_info(ctx)
        try:
            root = ET.fromstring(ctx.body)
        except ET.ParseError as exc:
            raise S3Error("MalformedXML", str(exc)) from exc
        tags: list[tuple[str, str]] = []
        for tag in root.iter():
            if not tag.tag.endswith("Tag"):
                continue
            k = v = None
            for sub in tag:
                if sub.tag.endswith("Key"):
                    k = (sub.text or "").strip()
                elif sub.tag.endswith("Value"):
                    v = sub.text or ""
            if k is None or v is None:
                raise S3Error("InvalidTag", "tag missing Key or Value")
            tags.append((k, v))
        self._validate_tags(tags)
        try:
            self.ol.update_object_metadata(
                ctx.bucket, ctx.object, opts.version_id,
                {self.TAGS_META_KEY: urllib.parse.urlencode(tags)},
            )
        except StorageError as exc:
            raise from_object_error(exc) from exc
        return Response(200)

    def delete_object_tagging(self, ctx) -> Response:
        self._check_bucket(ctx.bucket)
        _, opts = self._tag_target_info(ctx)
        try:
            self.ol.update_object_metadata(
                ctx.bucket, ctx.object, opts.version_id,
                {self.TAGS_META_KEY: ""},
            )
        except StorageError as exc:
            raise from_object_error(exc) from exc
        return Response(204)

    # ---------- canned ACLs (ref cmd/acl-handlers.go: S3 ACLs are
    # hardwired to the private/FULL_CONTROL owner model; IAM/bucket
    # policy is the real authorization surface) ----------

    def get_acl(self, ctx) -> Response:
        self._check_bucket(ctx.bucket)
        if ctx.object:
            self._tag_target_info(ctx)
        root = ET.Element("AccessControlPolicy")
        owner = ET.SubElement(root, "Owner")
        ET.SubElement(owner, "ID").text = "minio-tpu"
        ET.SubElement(owner, "DisplayName").text = "minio-tpu"
        acl = ET.SubElement(root, "AccessControlList")
        grant = ET.SubElement(acl, "Grant")
        grantee = ET.SubElement(grant, "Grantee")
        grantee.set("xmlns:xsi", "http://www.w3.org/2001/XMLSchema-instance")
        grantee.set("xsi:type", "CanonicalUser")
        ET.SubElement(grantee, "ID").text = "minio-tpu"
        ET.SubElement(grant, "Permission").text = "FULL_CONTROL"
        return Response.xml(root)

    def put_acl(self, ctx) -> Response:
        self._check_bucket(ctx.bucket)
        if ctx.object:
            # ACL verbs must agree about existence: PUT on a missing or
            # delete-markered key is NoSuchKey, like GET (and AWS).
            self._tag_target_info(ctx)
        canned = ctx.headers.get("x-amz-acl", "private")
        if canned != "private":
            raise S3Error("NotImplemented",
                          "only the private canned ACL is supported")
        if ctx.body:
            # Parse the document: ONLY the owner FULL_CONTROL grant is
            # representable; any additional/other grant must be refused
            # loudly, never silently dropped (ref acl-handlers.go
            # rejecting non-private policies).
            try:
                root = ET.fromstring(ctx.body)
            except ET.ParseError as exc:
                raise S3Error("MalformedXML", str(exc)) from exc
            perms = [
                (el.text or "").strip()
                for el in root.iter() if el.tag.endswith("Permission")
            ]
            if not perms or any(p != "FULL_CONTROL" for p in perms) \
                    or len(perms) > 1:
                raise S3Error("NotImplemented",
                              "custom grants are not supported")
        return Response(200)

    # Object-level ACL verbs: same canned semantics, distinct handler
    # names so IAM authorizes s3:GetObjectAcl / s3:PutObjectAcl rather
    # than the bucket actions.
    def get_object_acl(self, ctx) -> Response:
        return self.get_acl(ctx)

    def put_object_acl(self, ctx) -> Response:
        return self.put_acl(ctx)

    def object_retention(self, ctx) -> Response:
        from ..bucket import objectlock as ol_mod

        self._check_bucket(ctx.bucket)
        oi = self._lock_target_info(ctx)
        if ctx.method == "GET":
            mode, until = ol_mod.retention_state(oi.user_defined)
            if not mode:
                raise S3Error("NoSuchObjectLockConfiguration")
            return Response(
                200, {"Content-Type": "application/xml"},
                ol_mod.retention_xml(mode, ol_mod.iso8601_utc(until)),
            )
        cfg = self._lock_config(ctx.bucket)
        if cfg is None or not cfg.enabled:
            raise S3Error("InvalidRequest",
                          "Bucket is missing ObjectLockConfiguration")
        try:
            mode, until_iso = ol_mod.parse_retention_body(ctx.body)
        except ET.ParseError as exc:
            raise S3Error("MalformedXML", str(exc)) from exc
        except ValueError as exc:
            raise S3Error("InvalidArgument", str(exc)) from exc
        # Tightening is always allowed; loosening COMPLIANCE is never
        # allowed, loosening GOVERNANCE needs the bypass header
        # (ref objectlock FilterObjectLockMetadata + retention checks).
        old_mode, old_until = ol_mod.retention_state(oi.user_defined)
        import time as _time

        if old_mode and old_until > _time.time():
            shortens = ol_mod.parse_iso8601(until_iso) < old_until
            bypass = (
                ctx.headers.get(ol_mod.HDR_BYPASS_GOVERNANCE, "").lower()
                == "true"
            )
            if old_mode == ol_mod.MODE_COMPLIANCE and (
                    shortens or mode != ol_mod.MODE_COMPLIANCE):
                raise S3Error("AccessDenied",
                              "COMPLIANCE retention cannot be loosened")
            if old_mode == ol_mod.MODE_GOVERNANCE and shortens and not bypass:
                raise S3Error("AccessDenied",
                              "governance retention shortening requires "
                              "bypass")
        try:
            self.ol.update_object_metadata(
                ctx.bucket, ctx.object, oi.version_id or "",
                {ol_mod.META_MODE: mode, ol_mod.META_RETAIN_UNTIL: until_iso},
            )
        except StorageError as exc:
            raise from_object_error(exc) from exc
        return Response(200)

    def object_legal_hold(self, ctx) -> Response:
        from ..bucket import objectlock as ol_mod

        self._check_bucket(ctx.bucket)
        oi = self._lock_target_info(ctx)
        if ctx.method == "GET":
            status = "ON" if ol_mod.legal_hold_on(oi.user_defined) else "OFF"
            if ol_mod.META_LEGAL_HOLD not in oi.user_defined:
                raise S3Error("NoSuchObjectLockConfiguration")
            return Response(200, {"Content-Type": "application/xml"},
                            ol_mod.legal_hold_xml(status))
        cfg = self._lock_config(ctx.bucket)
        if cfg is None or not cfg.enabled:
            raise S3Error("InvalidRequest",
                          "Bucket is missing ObjectLockConfiguration")
        try:
            status = ol_mod.parse_legal_hold_body(ctx.body)
        except ET.ParseError as exc:
            raise S3Error("MalformedXML", str(exc)) from exc
        except ValueError as exc:
            raise S3Error("InvalidArgument", str(exc)) from exc
        try:
            self.ol.update_object_metadata(
                ctx.bucket, ctx.object, oi.version_id or "",
                {ol_mod.META_LEGAL_HOLD: status},
            )
        except StorageError as exc:
            raise from_object_error(exc) from exc
        return Response(200)

    def bucket_replication(self, ctx) -> Response:
        # Replication requires versioning on the source bucket so deletes
        # become replicable delete markers (ref cmd/bucket-handlers.go
        # PutBucketReplicationConfigHandler ErrReplicationNeedsVersioningError,
        # cmd/bucket-replication.go:574 version-aware replicateDelete).
        def _needs_versioning():
            if not self.bm.get(ctx.bucket).versioning_enabled:
                raise S3Error("ReplicationNeedsVersioningError")

        return self._xml_subresource(
            ctx, "replication_xml", "ReplicationConfigurationNotFoundError",
            pre_put=_needs_versioning,
        )

    def bucket_notification(self, ctx) -> Response:
        self._check_bucket(ctx.bucket)
        if ctx.method == "PUT":
            try:
                ET.fromstring(ctx.body)
            except ET.ParseError as exc:
                raise S3Error("MalformedXML", str(exc)) from exc
            self.bm.update(ctx.bucket, "notification_xml", ctx.body.decode())
            if self.notify is not None:
                self.notify.load_bucket_rules(ctx.bucket)
            return Response(200)
        bm = self.bm.get(ctx.bucket)
        if bm.notification_xml:
            return Response(200, {"Content-Type": "application/xml"},
                            bm.notification_xml.encode())
        root = _xml_root("NotificationConfiguration")
        return Response.xml(root)

    # ---------- object ----------

    def _apply_storage_class(self, ctx, opts):
        """x-amz-storage-class → erasure parity via the storage_class
        config subsystem (ref cmd/config/storageclass applied at
        cmd/erasure-object.go:611-618)."""
        sc = ctx.headers.get("x-amz-storage-class", "").upper()
        if not sc:
            return
        if sc not in ("STANDARD", "REDUCED_REDUNDANCY"):
            raise S3Error("InvalidStorageClass", sc)
        if self.config is None:
            return
        kvs = self.config.get("storage_class")
        spec = kvs.get("rrs" if sc == "REDUCED_REDUNDANCY"
                       else "standard", "") or ""
        if spec.upper().startswith("EC:"):
            try:
                opts.parity = int(spec[3:])
            except ValueError as exc:
                raise S3Error(
                    "InvalidArgument", f"bad storage class spec {spec!r}"
                ) from exc

    def _apply_codec(self, ctx, opts):
        """x-mtpu-codec → forced erasure codec id (the top of the
        erasure/registry.py selection precedence). Validated HERE so an
        unknown id rejects the request before any byte streams; "auto"
        explicitly re-enables the measured-probe selection even when
        MTPU_CODEC forces a codec server-wide."""
        cid = ctx.headers.get("x-mtpu-codec", "")
        if not cid:
            return
        from ..erasure import registry

        if cid != "auto" and cid not in registry.codec_ids():
            raise S3Error(
                "InvalidArgument",
                f"unknown erasure codec {cid!r} "
                f"(registered: {sorted(registry.codec_ids())} or auto)",
            )
        opts.codec = cid

    def put_object(self, ctx) -> Response:
        if not valid_object_name(ctx.object):
            raise S3Error("InvalidArgument", f"bad object name {ctx.object!r}")
        self._check_bucket(ctx.bucket)
        copy_source = ctx.headers.get("x-amz-copy-source", "")
        if copy_source:
            return self._copy_object(ctx, copy_source)
        size = ctx.content_length
        if size is None:
            raise S3Error("MissingContentLength")
        if size > MAX_OBJECT_SIZE:
            raise S3Error("EntityTooLarge")
        opts = self._opts_for(ctx.bucket, ctx.qdict)
        opts.user_defined = extract_user_metadata(ctx.headers)
        # x-amz-tagging: urlencoded tags supplied at write time (ref
        # xhttp.AmzObjectTagging handling in PutObjectHandler) — same
        # validation as the ?tagging subresource, stored normalized.
        tag_hdr = ctx.headers.get("x-amz-tagging", "")
        if tag_hdr:
            tags = urllib.parse.parse_qsl(tag_hdr, keep_blank_values=True)
            self._validate_tags(tags)
            opts.user_defined[self.TAGS_META_KEY] = \
                urllib.parse.urlencode(tags)
        self._apply_storage_class(ctx, opts)
        self._apply_codec(ctx, opts)
        self._apply_object_lock(ctx, opts)
        try:
            self.quota.check(ctx.bucket, size)
        except StorageError as exc:
            raise from_object_error(exc) from exc
        repl_rule = self._repl_rule(ctx.bucket, ctx.object)
        incoming_replica = (
            opts.user_defined.get("x-amz-meta-mtpu-replication") == "replica"
        )
        if incoming_replica:
            # The replica marker suppresses re-replication, so it is
            # privileged: s3:ReplicateObject required. Enforced HERE so
            # every ingress path (SigV4, web console, POST policy)
            # passes through one guard (ref ReplicateObjectAction check,
            # cmd/auth-handler.go).
            from ..iam.policy import Args as _Args

            account = getattr(ctx, "access_key", "") or ""
            _args = _Args(account=account, action="s3:ReplicateObject",
                          bucket=ctx.bucket, object=ctx.object)
            bucket_policy = self.bm.get(ctx.bucket).policy()
            allowed = (
                (bool(account) and self.iam.is_allowed(_args))
                or (bucket_policy is not None
                    and bucket_policy.is_allowed(_args))
            )
            if not allowed:
                raise S3Error(
                    "AccessDenied",
                    "replica marker requires s3:ReplicateObject",
                )
        if repl_rule is not None:
            from ..replication.pool import PENDING, REPL_STATUS_KEY, REPLICA

            opts.user_defined[REPL_STATUS_KEY] = (
                REPLICA if incoming_replica else PENDING
            )
        reader = ctx.body_reader
        resp_extra: dict = {}
        from . import transforms

        want_md5_hex = self._parse_content_md5(ctx.headers)
        if transforms.transforms_active(ctx.headers, self.config, ctx.object):
            # Streaming transform chain (md5-verify -> compress ->
            # encrypt): no stage holds the object; a bad plaintext digest
            # aborts the encode stream before commit.
            reader, size, resp_extra = transforms.build_put_stream(
                ctx.headers, self.config, self.sse_config,
                ctx.bucket, ctx.object, reader, size, opts.user_defined,
                want_md5_hex=want_md5_hex,
            )
        else:
            # Verified inside the object layer during the encode stream,
            # BEFORE commit (ref hash.NewReader wired at
            # cmd/object-handlers.go:1555-1570).
            opts.want_md5_hex = want_md5_hex
        try:
            oi = self.ol.put_object(
                ctx.bucket, ctx.object, reader, size, opts
            )
        except StorageError as exc:
            raise from_object_error(exc) from exc
        headers = {"ETag": f'"{oi.etag}"'}
        headers.update(resp_extra)
        if oi.version_id and oi.version_id != "null":
            headers["x-amz-version-id"] = oi.version_id
        self._event("s3:ObjectCreated:Put", ctx.bucket, oi=oi)
        if repl_rule is not None and not incoming_replica:
            vid = oi.version_id if oi.version_id != "null" else ""
            self._schedule_replication(ctx.bucket, ctx.object, vid, "put")
            headers["X-Amz-Replication-Status"] = "PENDING"
        return Response(200, headers)

    def _copy_object(self, ctx, copy_source: str) -> Response:
        sbucket, sobject, vid = parse_copy_source(copy_source)
        try:
            src_opts = self._opts_for(sbucket, {"versionId": vid})
            src_info = self.ol.get_object_info(sbucket, sobject, src_opts)
        except StorageError as exc:
            raise from_object_error(exc) from exc
        self._copy_source_conditions(ctx, src_info)
        opts = self._opts_for(ctx.bucket, ctx.qdict)
        directive = ctx.headers.get("x-amz-metadata-directive", "COPY")
        from ..bucket import objectlock as ol_mod

        self_copy = (sbucket, sobject) == (ctx.bucket, ctx.object)
        if directive == "REPLACE":
            opts.user_defined = extract_user_metadata(ctx.headers)
        else:
            # Retention/hold NEVER copies from the source version (the
            # destination's protection comes from this request's headers
            # or the bucket default, AWS semantics) and neither do the
            # internal transform/replication markers — except on a
            # self-copy, where the stored bytes (and their path-bound
            # sealed key) are reused verbatim.
            drop = (ol_mod.META_MODE, ol_mod.META_RETAIN_UNTIL,
                    ol_mod.META_LEGAL_HOLD)
            opts.user_defined = {
                k: v for k, v in src_info.user_defined.items()
                if k not in drop and not k.startswith("x-mtpu-internal-")
            }
        # A copy writes a new object/version: it honors lock headers /
        # the bucket default retention and the hard quota exactly like a
        # streaming PUT (ref CopyObjectHandler lock+quota wiring). The
        # quota charge is the LOGICAL size — a compressed source can
        # expand at the destination.
        from . import transforms as _tfm

        self._apply_object_lock(ctx, opts)
        try:
            self.quota.check(ctx.bucket, _tfm.actual_object_size(
                src_info.user_defined, src_info.size))
        except StorageError as exc:
            raise from_object_error(exc) from exc
        if self_copy and not vid and directive != "REPLACE":
            # AWS rejects untargeted self-copy without changed metadata
            # regardless of bucket versioning (ref cpSrcDstSame,
            # cmd/object-handlers.go).
            raise S3Error(
                "InvalidRequest",
                "This copy request is illegal because it is being made "
                "to the same object without changing metadata.",
            )
        from . import transforms

        # The destination's transform chain applies when this request
        # asks for one (SSE/compression headers or filters) — and a
        # transformed source always re-encodes on a cross-key copy, since
        # its sealed key is bound to the source path.
        src_transformed = transforms.is_transformed(src_info.user_defined)
        dest_transforms = transforms.transforms_active(
            ctx.headers, self.config, ctx.object
        )
        if self_copy and not vid and not opts.versioned and \
                not dest_transforms:
            # Unversioned REPLACE self-copy: metadata-only update — never
            # re-put the bytes, which would deadlock the writer lock
            # against its own locked source read (srcInfo.metadataOnly).
            try:
                mod_time_ns = self.ol.update_object_metadata(
                    ctx.bucket, ctx.object, src_info.version_id or "",
                    opts.user_defined, replace_user_meta=True,
                )
            except StorageError as exc:
                raise from_object_error(exc) from exc
            src_info.mod_time_ns = mod_time_ns or src_info.mod_time_ns
            self._event("s3:ObjectCreated:Copy", ctx.bucket, oi=src_info)
            return self._copy_result(src_info)

        repl_rule = self._repl_rule(ctx.bucket, ctx.object)
        if repl_rule is not None:
            from ..replication.pool import PENDING, REPL_STATUS_KEY

            opts.user_defined[REPL_STATUS_KEY] = PENDING
        copy_sse_headers: dict | None = None
        if src_transformed or dest_transforms or self_copy:
            # Decode the logical stream into a spool (bounded RSS; also
            # satisfies the self-copy rule that the source read COMPLETES
            # before the destination put takes the same write lock), then
            # apply the destination's transform chain (ref CopyObject
            # re-encryption, cmd/object-handlers.go + encryption-v1.go).
            src_headers = dict(ctx.headers)
            # Copy-source SSE-C headers address the SOURCE decryption.
            for suffix in ("algorithm", "key", "key-md5"):
                v = ctx.headers.get(
                    "x-amz-copy-source-server-side-encryption-customer-"
                    + suffix, "")
                if v:
                    src_headers[
                        "x-amz-server-side-encryption-customer-" + suffix
                    ] = v
            try:
                spool = transforms.decode_to_spool(
                    self.ol, sbucket, sobject, src_opts,
                    src_info.user_defined, src_headers, self.sse_config,
                )
            except StorageError as exc:
                raise from_object_error(exc) from exc
            with spool:
                spool.seek(0, io.SEEK_END)
                size = spool.tell()
                spool.seek(0)
                reader, stored_size = spool, size
                if dest_transforms:
                    reader, stored_size, copy_sse_headers = (
                        transforms.build_put_stream(
                            ctx.headers, self.config, self.sse_config,
                            ctx.bucket, ctx.object, spool, size,
                            opts.user_defined,
                        )
                    )
                try:
                    oi = self.ol.put_object(
                        ctx.bucket, ctx.object, reader, stored_size, opts
                    )
                except StorageError as exc:
                    raise from_object_error(exc) from exc
        else:
            # Stream source -> destination in 1 MiB pulls; a multi-GiB
            # copy must not materialize in memory.
            reader = _RangeCopyReader(
                self.ol, sbucket, sobject, 0, src_info.size, src_opts
            )
            try:
                oi = self.ol.put_object(
                    ctx.bucket, ctx.object, reader, src_info.size, opts
                )
            except StorageError as exc:
                raise from_object_error(exc) from exc
        if repl_rule is not None:
            rvid = oi.version_id if oi.version_id != "null" else ""
            self._schedule_replication(ctx.bucket, ctx.object, rvid, "put")
        self._event("s3:ObjectCreated:Copy", ctx.bucket, oi=oi)
        return self._copy_result(oi, copy_sse_headers)

    @staticmethod
    def _copy_result(oi, extra_headers: dict | None = None) -> Response:
        """CopyObjectResult XML + version/SSE headers (shared epilogue)."""
        root = _xml_root("CopyObjectResult")
        ET.SubElement(root, "LastModified").text = iso8601(oi.mod_time_ns)
        ET.SubElement(root, "ETag").text = f'"{oi.etag}"'
        headers = dict(extra_headers or {})
        if oi.version_id and oi.version_id != "null":
            headers["x-amz-version-id"] = oi.version_id
        return Response.xml(root, headers=headers)

    @staticmethod
    def _parse_content_md5(headers: dict) -> str:
        """Decode the Content-MD5 header to hex ('' if absent); malformed
        base64 is InvalidDigest (ref cmd/utils.go md5 header parsing)."""
        md5_hdr = headers.get("content-md5", "")
        if not md5_hdr:
            return ""
        import base64
        import binascii

        try:
            raw = base64.b64decode(md5_hdr, validate=True)
        except (binascii.Error, ValueError) as exc:
            raise S3Error("InvalidDigest") from exc
        if len(raw) != 16:
            raise S3Error("InvalidDigest")
        return raw.hex()

    @staticmethod
    def _copy_source_conditions(ctx, src_info):
        """x-amz-copy-source-if-{match,none-match,modified-since,
        unmodified-since}: preconditions on the SOURCE of a copy, all
        failing with 412 (ref checkCopyObjectPreconditions,
        cmd/object-handlers-common.go — unlike GET conditionals, a
        failed none-match/modified-since is 412, never 304)."""
        mod_s = src_info.mod_time_ns // 10 ** 9
        im = ctx.headers.get("x-amz-copy-source-if-match", "")
        if im and not _etag_matches(im, src_info.etag):
            raise S3Error("PreconditionFailed", "x-amz-copy-source-if-match")
        inm = ctx.headers.get("x-amz-copy-source-if-none-match", "")
        if inm and _etag_matches(inm, src_info.etag):
            raise S3Error("PreconditionFailed",
                          "x-amz-copy-source-if-none-match")
        ims = ctx.headers.get("x-amz-copy-source-if-modified-since", "")
        if ims and (t := _parse_http_date(ims)) is not None and mod_s <= t:
            raise S3Error("PreconditionFailed",
                          "x-amz-copy-source-if-modified-since")
        ius = ctx.headers.get("x-amz-copy-source-if-unmodified-since", "")
        if ius and (t := _parse_http_date(ius)) is not None and mod_s > t:
            raise S3Error("PreconditionFailed",
                          "x-amz-copy-source-if-unmodified-since")

    def _conditional_headers(self, ctx, oi):
        """If-Match / If-None-Match / If-(Un)Modified-Since
        (ref cmd/object-handlers-common.go checkPreconditions). GET
        semantics: failed none-match/modified-since is 304; the
        copy-source variant above turns every failure into 412."""
        etag = f'"{oi.etag}"'
        mod_s = oi.mod_time_ns // 10 ** 9
        im = ctx.headers.get("if-match", "")
        if im and not _etag_matches(im, oi.etag):
            raise S3Error("PreconditionFailed", "If-Match")
        inm = ctx.headers.get("if-none-match", "")
        if inm and _etag_matches(inm, oi.etag):
            return Response(304, {"ETag": etag})
        ims = ctx.headers.get("if-modified-since", "")
        if ims and (t := _parse_http_date(ims)) is not None and mod_s <= t:
            return Response(304, {"ETag": etag})
        ius = ctx.headers.get("if-unmodified-since", "")
        if ius and (t := _parse_http_date(ius)) is not None and mod_s > t:
            raise S3Error("PreconditionFailed", "If-Unmodified-Since")
        return None

    def _object_headers(self, ctx, oi) -> dict:
        headers = {
            "ETag": f'"{oi.etag}"',
            "Last-Modified": http_date(oi.mod_time_ns),
            "Content-Type": oi.content_type or "application/octet-stream",
            "Accept-Ranges": "bytes",
        }
        if oi.version_id and oi.version_id != "null":
            headers["x-amz-version-id"] = oi.version_id
        from ..replication.pool import REPL_STATUS_KEY

        if REPL_STATUS_KEY in oi.user_defined:
            headers["X-Amz-Replication-Status"] = (
                oi.user_defined[REPL_STATUS_KEY]
            )
        from .. import tier as tiermod
        from ..bucket import objectlock as ol_mod

        for k, v in oi.user_defined.items():
            if k.startswith("x-amz-meta-"):
                headers[k] = v
            elif k in (ol_mod.META_MODE, ol_mod.META_RETAIN_UNTIL,
                       ol_mod.META_LEGAL_HOLD, tiermod.META_RESTORE):
                headers[k] = v
            elif k in _REMEMBERED_HEADERS and k != "content-type":
                headers[k.title()] = v
        if tiermod.is_transitioned(oi.user_defined):
            headers["x-amz-storage-class"] = oi.user_defined[tiermod.META_TIER]
        elif oi.user_defined.get("x-amz-storage-class",
                                 "STANDARD") != "STANDARD":
            # RRS parity objects advertise their class (AWS echoes only
            # non-STANDARD classes).
            headers["x-amz-storage-class"] = \
                oi.user_defined["x-amz-storage-class"]
        ntags = len(urllib.parse.parse_qsl(
            oi.user_defined.get(self.TAGS_META_KEY, ""),
            keep_blank_values=True,
        ))
        if ntags:
            headers["x-amz-tagging-count"] = str(ntags)
        for qk, hk in _RESPONSE_OVERRIDES.items():
            if qk in ctx.qdict:
                headers[hk] = ctx.qdict[qk]
        return headers

    def get_object(self, ctx) -> Response:
        self._check_bucket(ctx.bucket)
        opts = self._opts_for(ctx.bucket, ctx.qdict)
        try:
            oi = self.ol.get_object_info(ctx.bucket, ctx.object, opts)
        except StorageError as exc:
            raise from_object_error(exc) from exc
        if oi.delete_marker:
            raise S3Error("NoSuchKey", ctx.object)
        early = self._conditional_headers(ctx, oi)
        if early is not None:
            return early
        from . import transforms
        from .. import tier as tiermod

        resp_extra: dict = {}
        # Cache layer (object/cache.py) reuses this info instead of
        # re-reading the metadata quorum.
        opts.cached_info = oi
        transformed = transforms.is_transformed(oi.user_defined)
        logical_size = transforms.actual_object_size(oi.user_defined, oi.size)
        rng = parse_range(ctx.headers.get("range", ""), logical_size)
        offset, length = (rng if rng else (0, logical_size))
        if tiermod.is_transitioned(oi.user_defined) and not \
                tiermod.is_restored(oi.user_defined):
            # Transitioned object: stored bytes live on the remote tier;
            # fetch them and run the normal transform inversion (the
            # sealed key/markers never left the local metadata). The
            # reference serves tiered objects transparently the same way
            # (cmd/bucket-lifecycle.go getTransitionedObjectReader).
            if self.tier_engine is None:
                raise S3Error("InvalidObjectState",
                              "object is transitioned and no tier engine "
                              "is configured")
            try:
                spool, tier_name = self.tier_engine.open_remote_spool(
                    oi.user_defined
                )
            except StorageError as exc:
                raise from_object_error(exc) from exc
            # Validate keys now, before the status line goes out.
            _probe, _, resp_extra = transforms.build_get_chain(
                oi.user_defined, ctx.headers, self.sse_config,
                ctx.bucket, ctx.object, _NullSink(),
                offset=offset, length=length,
            )
            del _probe

            def stream(dst, _spool=spool):
                try:
                    chain, closers, _ = transforms.build_get_chain(
                        oi.user_defined, ctx.headers, self.sse_config,
                        ctx.bucket, ctx.object, dst,
                        offset=offset, length=length,
                    )
                    while True:
                        chunk = _spool.read(1 << 20)
                        if not chunk:
                            break
                        chain.write(chunk)
                    for c in closers:
                        c.close()
                finally:
                    _spool.close()

            headers = self._object_headers(ctx, oi)
            headers.update(resp_extra)
            headers["Content-Length"] = str(length)
            headers["x-amz-storage-class"] = tier_name
            self._event("s3:ObjectAccessed:Get", ctx.bucket, oi=oi)
            if rng:
                headers["Content-Range"] = (
                    f"bytes {offset}-{offset + length - 1}/{logical_size}"
                )
                return Response(206, headers, body_stream=stream)
            return Response(200, headers, body_stream=stream)
        # Read-plane admission (ISSUE 11). The slot itself is taken
        # inside the object layer (its lifetime IS the decode+transfer)
        # — but that runs inside body_stream, AFTER the status line,
        # where a queue-full rejection could only sever the connection.
        # So: (a) probe the governor NOW, inside the caller's
        # client_context, turning the documented fast-fail into a real
        # 503 SlowDown; (b) capture the admission identity and re-enter
        # it inside the stream closures, because body_stream executes
        # after the dispatch's client_context has exited — without this
        # every GET would pool into the anonymous identity and the
        # per-client caps/(key,bucket) tenancy would never bind. The
        # rarer mid-stream deadline expiry keeps the established
        # mid-stream abort semantics (severed connection), exactly like
        # the expected_etag guard below.
        from ..pipeline.admission import (
            client_context,
            current_client,
            read_governor,
        )
        from ..utils.errors import ErrOperationTimedOut

        if read_governor().saturated():
            exc = ErrOperationTimedOut(
                "server busy: GET admission queue full"
            )
            raise from_object_error(exc) from exc
        caller = current_client()
        # Pin the stream to the ADVERTISED version: headers are on the
        # wire before the body, and a concurrent overwrite between the
        # info fetch and the locked data read must abort with ZERO bytes
        # (severed connection) rather than serve different bytes under
        # the old ETag. Applies to every local-read branch below.
        opts.expected_etag = oi.etag
        if transformed:
            # Streaming decrypt/decompress writer chain onto the socket
            # (ref NewGetObjectReader, cmd/object-api-utils.go:595): the
            # object never materializes server-side. Key validation
            # happens NOW, before the status line goes out. Ranged reads
            # decode the stream and window it server-side (bounded RSS;
            # full-object IO — package-aligned seeks are a future step).
            probe, _, resp_extra = transforms.build_get_chain(
                oi.user_defined, ctx.headers, self.sse_config,
                ctx.bucket, ctx.object, _NullSink(),
            )
            del probe

            def stream(dst, _opts=opts):
                with client_context(caller):
                    chain, closers, _ = transforms.build_get_chain(
                        oi.user_defined, ctx.headers, self.sse_config,
                        ctx.bucket, ctx.object, dst,
                        offset=offset, length=length,
                    )
                    self.ol.get_object(ctx.bucket, ctx.object, chain,
                                       opts=_opts)
                    for c in closers:
                        c.close()
        else:
            def stream(dst, _opts=opts):
                with client_context(caller):
                    self.ol.get_object(ctx.bucket, ctx.object, dst,
                                       offset=offset, length=length,
                                       opts=_opts)
        headers = self._object_headers(ctx, oi)
        headers.update(resp_extra)
        headers["Content-Length"] = str(length)
        self._event("s3:ObjectAccessed:Get", ctx.bucket, oi=oi)
        if rng:
            headers["Content-Range"] = (
                f"bytes {offset}-{offset + length - 1}/{logical_size}"
            )
            return Response(206, headers, body_stream=stream)
        return Response(200, headers, body_stream=stream)

    def select_object_content(self, ctx) -> Response:
        """SelectObjectContent: SQL over one CSV/JSON object, response
        framed as an AWS event stream (ref pkg/s3select/select.go +
        SelectObjectContentHandler, cmd/object-handlers.go:97)."""
        self._check_bucket(ctx.bucket)
        opts = self._opts_for(ctx.bucket, ctx.qdict)
        try:
            oi = self.ol.get_object_info(ctx.bucket, ctx.object, opts)
        except StorageError as exc:
            raise from_object_error(exc) from exc
        from ..s3select import eventstream
        from ..s3select.engine import SelectRequest, run_select
        from ..s3select.sql import SQLError

        try:
            req = SelectRequest.from_xml(ctx.body)
        except SQLError as exc:
            raise S3Error("InvalidArgument", str(exc)) from exc
        except ET.ParseError as exc:
            raise S3Error("MalformedXML", str(exc)) from exc

        from . import transforms

        import tempfile

        # Materialize the LOGICAL stream into a disk-backed spool, scan
        # it in column batches, and spool the framed result messages the
        # same way — neither the input nor a giant SELECT * result ever
        # sits in memory.
        out_spool = tempfile.SpooledTemporaryFile(max_size=8 << 20)
        max_payload = (128 << 10) - 512

        def emit(chunk: bytes):
            for off in range(0, len(chunk), max_payload):
                out_spool.write(eventstream.records_message(
                    chunk[off:off + max_payload]
                ))

        try:
            try:
                in_spool = transforms.decode_to_spool(
                    self.ol, ctx.bucket, ctx.object, opts,
                    oi.user_defined, ctx.headers, self.sse_config,
                )
            except StorageError as exc:
                raise from_object_error(exc) from exc
            with in_spool:
                in_spool.seek(0)
                on_batch = None
                if req.request_progress:
                    # Progress frames every >=1 MiB of scanned input
                    # (ref pkg/s3select/progress.go periodic frames).
                    last = [0]

                    def on_batch(scanned, processed, returned):
                        # BytesScanned = input bytes read (compressed
                        # for GZIP/BZIP2); BytesProcessed = decompressed
                        # bytes — the AWS/reference split.
                        if scanned - last[0] >= (1 << 20):
                            last[0] = scanned
                            out_spool.write(eventstream.progress_message(
                                scanned, processed, returned
                            ))

                try:
                    stats = run_select(req, in_spool, emit,
                                       on_batch=on_batch)
                except SQLError as exc:
                    raise S3Error("InvalidArgument", str(exc)) from exc
                except (ValueError, UnicodeDecodeError) as exc:
                    raise S3Error("InvalidRequest",
                                  f"malformed input: {exc}") from exc
            # Stats must agree with the Progress frames: the engine's
            # own counters, not oi.size — a LIMIT query that early-exits
            # scans only part of the object.
            out_spool.write(eventstream.stats_message(
                stats["scanned"], stats["processed"], stats["returned"]
            ))
            out_spool.write(eventstream.end_message())
        except BaseException:
            out_spool.close()
            raise
        total = out_spool.tell()
        out_spool.seek(0)
        self._event("s3:ObjectAccessed:Get", ctx.bucket, oi=oi)

        def stream(dst, _spool=out_spool):
            try:
                while True:
                    chunk = _spool.read(1 << 20)
                    if not chunk:
                        break
                    dst.write(chunk)
            finally:
                _spool.close()

        return Response(
            200,
            {"Content-Type": "application/octet-stream",
             "Content-Length": str(total)},
            body_stream=stream,
        )

    def restore_object(self, ctx) -> Response:
        """POST ?restore: materialize a temporary local copy of a
        transitioned object (ref PostRestoreObjectHandler,
        cmd/bucket-lifecycle.go:369)."""
        self._check_bucket(ctx.bucket)
        if self.tier_engine is None:
            raise S3Error("NotImplemented", "no tier engine configured")
        days = 1
        if ctx.body:
            try:
                root = ET.fromstring(ctx.body)
                for el in root.iter():
                    if el.tag.endswith("Days"):
                        days = max(1, int((el.text or "1").strip()))
            except (ET.ParseError, ValueError) as exc:
                raise S3Error("MalformedXML", str(exc)) from exc
        from ..utils.errors import ErrInvalidArgument

        try:
            self.tier_engine.restore(ctx.bucket, ctx.object, days)
        except ErrInvalidArgument as exc:
            raise S3Error("InvalidObjectState", str(exc)) from exc
        except StorageError as exc:
            raise from_object_error(exc) from exc
        return Response(202)

    def head_object(self, ctx) -> Response:
        self._check_bucket(ctx.bucket)
        opts = self._opts_for(ctx.bucket, ctx.qdict)
        try:
            oi = self.ol.get_object_info(ctx.bucket, ctx.object, opts)
        except StorageError as exc:
            raise from_object_error(exc) from exc
        if oi.delete_marker:
            raise S3Error("NoSuchKey", ctx.object)
        early = self._conditional_headers(ctx, oi)
        if early is not None:
            return early
        from . import transforms

        headers = self._object_headers(ctx, oi)
        headers["Content-Length"] = str(
            transforms.actual_object_size(oi.user_defined, oi.size)
        )
        if transforms.is_transformed(oi.user_defined):
            # SSE-C objects require the key even for HEAD (ref
            # cmd/object-handlers.go HeadObjectHandler decrypt checks).
            from ..crypto import sse as ssemod

            if oi.user_defined.get(ssemod.META_ALGORITHM) == ssemod.ALGO_SSEC:
                if ssemod.parse_ssec_key(ctx.headers) is None:
                    raise S3Error("InvalidRequest", "SSE-C key required")
                headers[ssemod.HDR_SSEC_ALGO] = "AES256"
                headers[ssemod.HDR_SSEC_KEY_MD5] = oi.user_defined.get(
                    ssemod.META_KEY_MD5, ""
                )
            elif oi.user_defined.get(ssemod.META_ALGORITHM) == ssemod.ALGO_SSES3:
                headers[ssemod.HDR_SSE] = "AES256"
            elif (oi.user_defined.get(ssemod.META_ALGORITHM)
                  == ssemod.ALGO_SSEKMS):
                headers[ssemod.HDR_SSE] = "aws:kms"
                headers[ssemod.HDR_SSE_KMS_ID] = oi.user_defined.get(
                    ssemod.META_KMS_KEY_ID, ""
                )
        self._event("s3:ObjectAccessed:Head", ctx.bucket, oi=oi)
        return Response(200, headers)

    def delete_object(self, ctx) -> Response:
        self._check_bucket(ctx.bucket)
        opts = self._opts_for(ctx.bucket, ctx.qdict)
        # Retention/legal-hold enforcement: a versionId-targeted delete
        # destroys that version; an untargeted delete on an UNVERSIONED
        # bucket destroys the only copy. Untargeted versioned deletes lay
        # a marker and never destroy data, so they pass
        # (ref enforceRetentionForDeletion / checkRequestAuthType wiring
        # in DeleteObjectHandler).
        vid = ctx.qdict.get("versionId", "")
        if vid:
            self._enforce_retention(ctx, ctx.bucket, ctx.object, vid)
        elif not opts.versioned:
            self._enforce_retention(ctx, ctx.bucket, ctx.object, "")
        headers = {}
        try:
            oi = self.ol.delete_object(ctx.bucket, ctx.object, opts)
            if oi is not None and getattr(oi, "delete_marker", False):
                headers["x-amz-delete-marker"] = "true"
                if oi.version_id and oi.version_id != "null":
                    headers["x-amz-version-id"] = oi.version_id
        except StorageError as exc:
            api = from_object_error(exc)
            if api.api.code not in ("NoSuchKey", "NoSuchVersion"):
                raise api from exc
        self._event("s3:ObjectRemoved:Delete", ctx.bucket, key=ctx.object)
        # Replicate un-targeted deletes (a versionId-targeted permanent
        # delete stays local, ref replicateDelete semantics).
        if "versionId" not in ctx.qdict:
            rule = self._repl_rule(ctx.bucket, ctx.object)
            if rule is not None:
                op = (
                    "delete-marker"
                    if headers.get("x-amz-delete-marker") == "true"
                    else "delete"
                )
                self._schedule_replication(ctx.bucket, ctx.object, "", op)
        return Response(204, headers)

    # ---------- multipart ----------

    def new_multipart_upload(self, ctx) -> Response:
        self._check_bucket(ctx.bucket)
        if not valid_object_name(ctx.object):
            raise S3Error("InvalidArgument", ctx.object)
        opts = self._opts_for(ctx.bucket, ctx.qdict)
        opts.user_defined = extract_user_metadata(ctx.headers)
        # Same storage-class validation/parity + tag handling as single
        # PUTs (a REDUCED_REDUNDANCY multipart object must actually GET
        # the reduced parity it advertises).
        tag_hdr = ctx.headers.get("x-amz-tagging", "")
        if tag_hdr:
            tags = urllib.parse.parse_qsl(tag_hdr, keep_blank_values=True)
            self._validate_tags(tags)
            opts.user_defined[self.TAGS_META_KEY] = \
                urllib.parse.urlencode(tags)
        self._apply_storage_class(ctx, opts)
        self._apply_codec(ctx, opts)
        # Multipart objects get the same lock treatment as single PUTs
        # (ref NewMultipartUploadHandler lock-header wiring).
        self._apply_object_lock(ctx, opts)
        try:
            upload_id = self.ol.new_multipart_upload(
                ctx.bucket, ctx.object, opts
            )
        except StorageError as exc:
            raise from_object_error(exc) from exc
        root = _xml_root("InitiateMultipartUploadResult")
        ET.SubElement(root, "Bucket").text = ctx.bucket
        ET.SubElement(root, "Key").text = ctx.object
        ET.SubElement(root, "UploadId").text = upload_id
        return Response.xml(root)

    # Browser form uploads are fully buffered (the multipart/form-data
    # body must be parsed before the file part is known); bound the
    # body so a form holder can't OOM the server — larger objects
    # belong on the streaming PUT/multipart APIs.
    MAX_POST_POLICY_BODY = 64 << 20

    def post_policy_object(self, ctx) -> Response:
        """Browser form upload: POST multipart/form-data to the bucket
        with a signed policy document (ref PostPolicyBucketHandler,
        cmd/bucket-handlers.go + cmd/postpolicyform.go). Authentication
        is the policy signature itself, not SigV4 headers — the form's
        x-amz-credential/x-amz-signature pair is verified against the
        IAM secret and the policy conditions against the form fields,
        then the bytes flow through the normal PUT pipeline."""
        from . import sign as signmod

        self._check_bucket(ctx.bucket)
        if (ctx.content_length or 0) > self.MAX_POST_POLICY_BODY:
            raise S3Error(
                "EntityTooLarge",
                f"POST form bodies are capped at "
                f"{self.MAX_POST_POLICY_BODY} bytes",
            )
        ctype = ctx.headers.get("content-type", "")
        fields, file_data, filename = _parse_multipart_form(
            ctype, ctx.body
        )
        policy_b64 = fields.get("policy", "")
        if not policy_b64:
            raise S3Error("MalformedPOSTRequest", "missing policy")
        # --- signature (V4 policy signing: StringToSign IS the policy)
        cred_str = fields.get("x-amz-credential", "")
        sig = fields.get("x-amz-signature", "")
        if not cred_str or not sig:
            raise S3Error("AccessDenied", "missing POST signature fields")
        try:
            cred = signmod.V4Credential(cred_str)
        except signmod.SignError as exc:
            raise S3Error("InvalidArgument",
                          f"bad x-amz-credential: {exc}") from exc
        creds = self.iam.get_credentials(cred.access_key)
        if creds is None:
            raise S3Error("InvalidAccessKeyId", cred.access_key)
        import hashlib as _hl
        import hmac as _hmac

        key = signmod.signing_key(
            creds.secret_key, cred.date, cred.region, cred.service
        )
        want = _hmac.new(key, policy_b64.encode(), _hl.sha256).hexdigest()
        if not _hmac.compare_digest(want, sig):
            raise S3Error("SignatureDoesNotMatch", "POST policy")
        # --- policy conditions
        _check_post_policy(policy_b64, fields, len(file_data), ctx.bucket)
        key_tmpl = fields.get("key", "")
        if not key_tmpl:
            raise S3Error("InvalidArgument", "missing key field")
        object_ = key_tmpl.replace("${filename}", filename)
        if not valid_object_name(object_):
            raise S3Error("InvalidArgument", f"bad key {object_!r}")
        # --- authorization for the signing identity: SAME rule as the
        # SigV4 plane (IAM allow OR bucket-policy allow).
        from ..iam.policy import Args

        args = Args(
            account=cred.access_key, action="s3:PutObject",
            bucket=ctx.bucket, object=object_,
        )
        bucket_policy = self.bm.get(ctx.bucket).policy()
        if not (self.iam.is_allowed(args)
                or (bucket_policy is not None
                    and bucket_policy.is_allowed(args))):
            raise S3Error("AccessDenied", "PutObject")
        # --- run the normal PUT pipeline over the file bytes
        from .server import RequestContext

        headers = {
            k: v for k, v in fields.items()
            if k.startswith("x-amz-meta-") or k == "content-type"
        }
        sub = RequestContext(
            "PUT", f"/{ctx.bucket}/{object_}", [], headers,
            io.BytesIO(file_data), len(file_data),
        )
        sub.access_key = cred.access_key
        # POST-policy uploads branch BEFORE the SigV4 dispatch's
        # admission tagging: attribute their encode slots to the
        # signing identity here, or a hot POST-policy tenant pools
        # into the anonymous client and bypasses per-tenant caps.
        from ..pipeline.admission import client_context

        with client_context(cred.access_key or "anonymous",
                            bucket=ctx.bucket or ""):
            resp = self.put_object(sub)
        status = fields.get("success_action_status", "204")
        if status == "201":
            root = ET.Element("PostResponse")
            ET.SubElement(root, "Bucket").text = ctx.bucket
            ET.SubElement(root, "Key").text = object_
            ET.SubElement(root, "ETag").text = resp.headers.get("ETag", "")
            out = Response.xml(root)
            out.status = 201
            out.headers.update(
                {k: v for k, v in resp.headers.items() if k != "ETag"}
            )
            return out
        return Response(204, dict(resp.headers))

    def put_object_part(self, ctx) -> Response:
        self._check_bucket(ctx.bucket)
        q = ctx.qdict
        upload_id = q.get("uploadId", "")
        try:
            part_number = int(q.get("partNumber", "0"))
        except ValueError as exc:
            raise S3Error("InvalidArgument", "partNumber") from exc
        if not 1 <= part_number <= MAX_PARTS:
            raise S3Error("InvalidArgument", f"partNumber {part_number}")
        copy_source = ctx.headers.get("x-amz-copy-source", "")
        if copy_source:
            # UploadPartCopy (ref cmd/object-handlers.go
            # CopyObjectPartHandler): source read already authorized in
            # dispatch alongside the destination write.
            return self._upload_part_copy(
                ctx, upload_id, part_number, copy_source
            )
        size = ctx.content_length
        if size is None:
            raise S3Error("MissingContentLength")
        if size > MAX_PART_SIZE:
            raise S3Error("EntityTooLarge")
        # Per-part quota admission (ref PutObjectPartHandler's
        # enforceBucketQuotaHard): multipart must not be a quota bypass.
        try:
            self.quota.check(ctx.bucket, size)
        except StorageError as exc:
            raise from_object_error(exc) from exc
        part_opts = ObjectOptions(
            want_md5_hex=self._parse_content_md5(ctx.headers)
        )
        try:
            pi = self.ol.put_object_part(
                ctx.bucket, ctx.object, upload_id, part_number,
                ctx.body_reader, size, part_opts,
            )
        except StorageError as exc:
            raise from_object_error(exc) from exc
        return Response(200, {"ETag": f'"{pi.etag}"'})

    def _upload_part_copy(self, ctx, upload_id: str, part_number: int,
                          copy_source: str) -> Response:
        sbucket, sobject, vid = parse_copy_source(copy_source)
        src_opts = self._opts_for(sbucket, {"versionId": vid})
        try:
            src_info = self.ol.get_object_info(sbucket, sobject, src_opts)
        except StorageError as exc:
            raise from_object_error(exc) from exc
        # Same source preconditions as whole-object copy (ref
        # checkCopyObjectPartPreconditions).
        self._copy_source_conditions(ctx, src_info)
        rng = ctx.headers.get("x-amz-copy-source-range", "")
        offset, length = 0, src_info.size
        if rng:
            # Strict 'bytes=first-last' only, fully inside the source —
            # AWS rejects suffix/open/overlong copy ranges outright
            # (unlike HTTP Range, which clamps).
            m = re.fullmatch(r"bytes=(\d+)-(\d+)", rng)
            if not m:
                raise S3Error("InvalidArgument", rng)
            first, last = int(m.group(1)), int(m.group(2))
            if first > last or last >= src_info.size:
                raise S3Error("InvalidArgument", rng)
            offset, length = first, last - first + 1
        if length > MAX_PART_SIZE:
            raise S3Error("EntityTooLarge")
        reader = _RangeCopyReader(
            self.ol, sbucket, sobject, offset, length, src_opts
        )
        try:
            pi = self.ol.put_object_part(
                ctx.bucket, ctx.object, upload_id, part_number,
                reader, length,
            )
        except StorageError as exc:
            raise from_object_error(exc) from exc
        root = _xml_root("CopyPartResult")
        ET.SubElement(root, "LastModified").text = iso8601(pi.mod_time_ns)
        ET.SubElement(root, "ETag").text = f'"{pi.etag}"'
        return Response.xml(root)

    def complete_multipart_upload(self, ctx) -> Response:
        self._check_bucket(ctx.bucket)
        upload_id = ctx.qdict.get("uploadId", "")
        try:
            req = ET.fromstring(ctx.body)
        except ET.ParseError as exc:
            raise S3Error("MalformedXML", str(exc)) from exc
        ns = "{http://s3.amazonaws.com/doc/2006-03-01/}"
        parts = []
        for el in req:
            if el.tag.removeprefix(ns) != "Part":
                continue
            pn, etag = 0, ""
            for sub in el:
                t = sub.tag.removeprefix(ns)
                if t == "PartNumber":
                    pn = int(sub.text or "0")
                elif t == "ETag":
                    etag = (sub.text or "").strip('"')
            parts.append(CompletePart(pn, etag))
        if not parts:
            raise S3Error("MalformedXML", "no parts")
        if parts != sorted(parts, key=lambda p: p.part_number):
            raise S3Error("InvalidPartOrder")
        opts = self._opts_for(ctx.bucket, ctx.qdict)
        try:
            oi = self.ol.complete_multipart_upload(
                ctx.bucket, ctx.object, upload_id, parts, opts
            )
        except StorageError as exc:
            raise from_object_error(exc) from exc
        root = _xml_root("CompleteMultipartUploadResult")
        ET.SubElement(root, "Location").text = (
            f"/{ctx.bucket}/{ctx.object}"
        )
        ET.SubElement(root, "Bucket").text = ctx.bucket
        ET.SubElement(root, "Key").text = ctx.object
        ET.SubElement(root, "ETag").text = f'"{oi.etag}"'
        headers = {}
        if oi.version_id and oi.version_id != "null":
            headers["x-amz-version-id"] = oi.version_id
        self._event(
            "s3:ObjectCreated:CompleteMultipartUpload", ctx.bucket, oi=oi
        )
        return Response.xml(root, headers=headers)

    def abort_multipart_upload(self, ctx) -> Response:
        self._check_bucket(ctx.bucket)
        upload_id = ctx.qdict.get("uploadId", "")
        try:
            self.ol.abort_multipart_upload(ctx.bucket, ctx.object, upload_id)
        except StorageError as exc:
            raise from_object_error(exc) from exc
        return Response(204)

    def list_object_parts(self, ctx) -> Response:
        self._check_bucket(ctx.bucket)
        q = ctx.qdict
        upload_id = q.get("uploadId", "")
        part_marker = int(q.get("part-number-marker", "0") or "0")
        max_parts = min(int(q.get("max-parts", "1000") or "1000"), 1000)
        try:
            parts = self.ol.list_object_parts(
                ctx.bucket, ctx.object, upload_id, part_marker, max_parts
            )
        except StorageError as exc:
            raise from_object_error(exc) from exc
        root = _xml_root("ListPartsResult")
        ET.SubElement(root, "Bucket").text = ctx.bucket
        ET.SubElement(root, "Key").text = ctx.object
        ET.SubElement(root, "UploadId").text = upload_id
        ET.SubElement(root, "PartNumberMarker").text = str(part_marker)
        ET.SubElement(root, "MaxParts").text = str(max_parts)
        truncated = len(parts) > max_parts
        parts = parts[:max_parts]
        ET.SubElement(root, "IsTruncated").text = (
            "true" if truncated else "false"
        )
        if truncated and parts:
            ET.SubElement(root, "NextPartNumberMarker").text = str(
                parts[-1].part_number
            )
        for p in parts:
            pe = ET.SubElement(root, "Part")
            ET.SubElement(pe, "PartNumber").text = str(p.part_number)
            ET.SubElement(pe, "LastModified").text = iso8601(p.mod_time_ns)
            ET.SubElement(pe, "ETag").text = f'"{p.etag}"'
            ET.SubElement(pe, "Size").text = str(p.size)
        return Response.xml(root)

    def list_multipart_uploads(self, ctx) -> Response:
        self._check_bucket(ctx.bucket)
        prefix = ctx.qdict.get("prefix", "")
        try:
            uploads = self.ol.list_multipart_uploads(ctx.bucket, prefix)
        except StorageError as exc:
            raise from_object_error(exc) from exc
        # Same encoding-type=url contract as the object listings.
        encode = self._listing_encoder(ctx)
        enc = encode or (lambda s: s)
        root = _xml_root("ListMultipartUploadsResult")
        ET.SubElement(root, "Bucket").text = ctx.bucket
        ET.SubElement(root, "Prefix").text = enc(prefix)
        if encode is not None:
            ET.SubElement(root, "EncodingType").text = "url"
        ET.SubElement(root, "IsTruncated").text = "false"
        for mp in uploads:
            u = ET.SubElement(root, "Upload")
            ET.SubElement(u, "Key").text = enc(mp.object)
            ET.SubElement(u, "UploadId").text = mp.upload_id
        return Response.xml(root)


class PostPolicyError(S3Error):
    pass


def _parse_multipart_form(content_type: str, body: bytes):
    """multipart/form-data -> (fields dict, file bytes, filename)."""
    from email import message_from_bytes
    from email.policy import HTTP

    raw = (f"Content-Type: {content_type}\r\nMIME-Version: 1.0\r\n\r\n"
           .encode() + body)
    msg = message_from_bytes(raw, policy=HTTP)
    if not msg.is_multipart():
        raise S3Error("MalformedPOSTRequest", "not multipart/form-data")
    fields: dict[str, str] = {}
    file_data: bytes | None = None
    filename = ""
    for part in msg.iter_parts():
        name = part.get_param("name", header="content-disposition")
        if not name:
            continue
        payload = part.get_payload(decode=True) or b""
        if name == "file":
            file_data = payload
            filename = part.get_filename() or ""
        else:
            fields[name.lower()] = payload.decode("utf-8", "replace").strip()
    if file_data is None:
        raise S3Error("MalformedPOSTRequest", "missing file field")
    return fields, file_data, filename


def _check_post_policy(policy_b64: str, fields: dict, size: int,
                       bucket: str = ""):
    """Validate the browser POST policy document's expiration and
    conditions against the submitted form fields (ref
    cmd/postpolicyform.go checkPostPolicy)."""
    import base64 as _b64
    import datetime as _dt
    import json as _json

    try:
        doc = _json.loads(_b64.b64decode(policy_b64))
    except Exception as exc:
        raise S3Error("MalformedPOSTRequest", "bad policy") from exc
    exp = doc.get("expiration", "")
    try:
        when = _dt.datetime.fromisoformat(str(exp).replace("Z", "+00:00"))
    except (ValueError, TypeError) as exc:
        raise S3Error("MalformedPOSTRequest", "bad expiration") from exc
    if when.tzinfo is None:
        when = when.replace(tzinfo=_dt.timezone.utc)
    if when < _dt.datetime.now(_dt.timezone.utc):
        raise S3Error("AccessDenied", "policy expired")
    # The bucket is addressed by the URL, not a form field (AWS POST
    # policy semantics): surface it to the condition matcher.
    fields = dict(fields)
    fields.setdefault("bucket", bucket)
    covered: set[str] = set()
    try:
        for cond in doc.get("conditions", []):
            if isinstance(cond, dict):
                for k, v in cond.items():
                    k = str(k).lower().lstrip("$")
                    covered.add(k)
                    if k in ("policy", "x-amz-signature", "file"):
                        continue
                    if fields.get(k, "") != str(v):
                        raise S3Error(
                            "AccessDenied",
                            f"policy condition failed: {k}",
                        )
            elif isinstance(cond, list) and len(cond) == 3:
                op, key, val = str(cond[0]).lower(), str(cond[1]), cond[2]
                if op == "content-length-range":
                    lo, hi = int(cond[1]), int(cond[2])
                    if not lo <= size <= hi:
                        raise S3Error(
                            "EntityTooLarge" if size > hi
                            else "EntityTooSmall",
                            f"{size} outside [{lo},{hi}]",
                        )
                    continue
                k = key.lower().lstrip("$")
                covered.add(k)
                have = fields.get(k, "")
                if op == "eq" and have != str(val):
                    raise S3Error("AccessDenied",
                                  f"policy eq condition failed: {k}")
                if op == "starts-with" and not have.startswith(str(val)):
                    raise S3Error("AccessDenied",
                                  f"policy starts-with failed: {k}")
            else:
                raise S3Error("MalformedPOSTRequest",
                              f"unsupported condition shape")
    except S3Error:
        raise
    except Exception as exc:  # noqa: BLE001 - malformed document shapes
        raise S3Error("MalformedPOSTRequest",
                      f"bad policy conditions: {exc}") from exc
    # EVERY non-plumbing form field must be covered by a condition
    # (AWS POST policy rule) — blocks smuggling metadata, including
    # the privileged replica marker, past whoever signed the form.
    exempt = {
        "policy", "x-amz-signature", "x-amz-algorithm",
        "x-amz-credential", "x-amz-date", "x-amz-security-token",
        "bucket", "success_action_status", "success_action_redirect",
    }
    for k in fields:
        if k not in exempt and k not in covered:
            raise S3Error(
                "AccessDenied", f"form field {k!r} not covered by policy"
            )
