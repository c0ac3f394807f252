"""Embeddable gateway: serve the full S3/IAM/admin HTTP stack over an
ARBITRARY ObjectLayer implementation — the analog of the kubegems
fork's flagship delta, `ServerMainForJFS(ctx, jfs ObjectLayer)`
(/root/reference/cmd/server-main.go:529-634: an external program embeds
MinIO's S3 front-end over its own backend, with the scanner/heal/expiry
machinery skipped), plus the gateway adapter framework
(cmd/gateway-interface.go, gateway-unsupported.go: implementors
override what they support, everything else answers NotImplemented).
"""

from __future__ import annotations

from .utils.errors import ErrBucketNotFound, ErrMethodNotAllowed


class GatewayUnsupported:
    """Base ObjectLayer for gateway backends: every optional capability
    raises (mapped to S3 NotImplemented/MethodNotAllowed by the API
    plane), so a backend only implements what it genuinely supports
    (ref cmd/gateway-unsupported.go's ~90 stubs)."""

    def _unsupported(self, op: str):
        raise ErrMethodNotAllowed(f"gateway does not support {op}")

    # --- bucket surface ---

    def make_bucket(self, bucket, opts=None):
        self._unsupported("MakeBucket")

    def delete_bucket(self, bucket, force=False):
        self._unsupported("DeleteBucket")

    def list_buckets(self):
        self._unsupported("ListBuckets")

    def bucket_exists(self, bucket) -> bool:
        try:
            return any(b.name == bucket for b in self.list_buckets())
        except ErrMethodNotAllowed:
            return False

    def check_bucket(self, bucket):
        if not self.bucket_exists(bucket):
            raise ErrBucketNotFound(bucket)

    def get_bucket_info(self, bucket):
        for b in self.list_buckets():
            if b.name == bucket:
                return b
        raise ErrBucketNotFound(bucket)

    # --- object surface ---

    def put_object(self, bucket, object_, reader, size, opts=None):
        self._unsupported("PutObject")

    def get_object(self, bucket, object_, writer, offset=0, length=-1,
                   opts=None):
        self._unsupported("GetObject")

    def get_object_info(self, bucket, object_, opts=None):
        self._unsupported("GetObjectInfo")

    def get_object_bytes(self, bucket, object_, offset=0, length=-1,
                         opts=None) -> bytes:
        import io

        buf = io.BytesIO()
        self.get_object(bucket, object_, buf, offset, length, opts)
        return buf.getvalue()

    def delete_object(self, bucket, object_, opts=None):
        self._unsupported("DeleteObject")

    def copy_object(self, *a, **k):
        self._unsupported("CopyObject")

    def list_objects(self, bucket, prefix="", marker="", delimiter="",
                     max_keys=1000):
        self._unsupported("ListObjects")

    def list_object_versions(self, *a, **k):
        self._unsupported("ListObjectVersions")

    # --- multipart ---

    def new_multipart_upload(self, *a, **k):
        self._unsupported("NewMultipartUpload")

    def put_object_part(self, *a, **k):
        self._unsupported("PutObjectPart")

    def complete_multipart_upload(self, *a, **k):
        self._unsupported("CompleteMultipartUpload")

    def abort_multipart_upload(self, *a, **k):
        self._unsupported("AbortMultipartUpload")

    def list_multipart_uploads(self, *a, **k):
        self._unsupported("ListMultipartUploads")

    def list_object_parts(self, *a, **k):
        self._unsupported("ListObjectParts")

    # --- metadata / misc ---

    def update_object_metadata(self, *a, **k):
        self._unsupported("UpdateObjectMetadata")

    def heal_object(self, *a, **k):
        self._unsupported("HealObject")

    def health(self) -> dict:
        return {"healthy": True, "gateway": True}


def serve_object_layer(object_layer, address: str = "127.0.0.1",
                       port: int = 0, root_user: str = "minioadmin",
                       root_password: str = "minioadmin",
                       region: str = "us-east-1", iam_in_memory: bool = True):
    """Start the S3 front-end over `object_layer` and return the running
    S3Server (caller owns .stop()) — ServerMainForJFS semantics: full
    S3 API + signatures + IAM + bucket metadata + admin, NO scanner /
    heal / disk monitor (those belong to backends that own disks).

    iam_in_memory: gateway backends often cannot host `.minio.sys`
    blobs; the default keeps IAM state in-process (the reference's
    JUICEFS_META_READ_ONLY guards exist for the same reason,
    cmd/iam.go:583)."""
    from .api import S3Server
    from .bucket import BucketMetadataSys
    from .iam import IAMSys, ObjectStoreBackend

    if iam_in_memory:
        iam = IAMSys(root_user, root_password)
    else:
        iam = IAMSys(root_user, root_password,
                     store=ObjectStoreBackend(object_layer))
        iam.load()
    bucket_meta = BucketMetadataSys(object_layer)
    return S3Server(
        object_layer, iam, bucket_meta, region=region,
        host=address, port=port,
    ).start()
