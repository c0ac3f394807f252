"""FSObjects: the single-disk, non-erasure ObjectLayer — behavioral
parity with the reference's FS mode (cmd/fs-v1.go NewFSObjectLayer,
fs-v1-metadata.go fs.json, fs-v1-multipart.go), re-designed as a plain
file tree:

    <root>/<bucket>/<object>                 object bytes
    <root>/.mtpu.sys/meta/<bucket>/<object>/fs.json   metadata
    <root>/.mtpu.sys/multipart/<sha>/<uploadid>/      parts

It exposes the same duck-typed surface as ErasureServerPools, so the S3
API plane and background services run over either backend (the
reference's ObjectLayer seam, cmd/object-api-interface.go:88).
"""

from __future__ import annotations

import hashlib
import io
import json
import os
import shutil
import time

from ..utils.errors import (
    ErrBucketExists,
    ErrBucketNotEmpty,
    ErrBucketNotFound,
    ErrInvalidPart,
    ErrInvalidUploadID,
    ErrObjectNotFound,
)
from .types import (
    BucketInfo,
    ListObjectsInfo,
    MultipartInfo,
    ObjectInfo,
    ObjectOptions,
    PartInfo,
    compute_etag,
)

SYS_DIR = ".mtpu.sys"


class FSObjects:
    """Single-disk ObjectLayer."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        os.makedirs(os.path.join(self.root, SYS_DIR, "meta"), exist_ok=True)
        os.makedirs(
            os.path.join(self.root, SYS_DIR, "multipart"), exist_ok=True
        )
        os.makedirs(os.path.join(self.root, SYS_DIR, "tmp"), exist_ok=True)

    # --- paths ---

    @staticmethod
    def _safe_segments(bucket: str, object_: str = "") -> list[str]:
        """Reject path components that would escape the storage root —
        the HTTP layer unquotes the URL, so `..%2F` would otherwise reach
        os.path.join (the reference guards this in xl-storage
        checkPathLength / isValidPath; LocalStorage has the same check)."""
        if not bucket or "/" in bucket or bucket in (".", ".."):
            raise ErrBucketNotFound(bucket)
        segs = [s for s in object_.split("/") if s] if object_ else []
        for seg in segs:
            if seg in (".", ".."):
                raise ErrObjectNotFound(f"{bucket}/{object_}")
        return segs

    def _bucket_path(self, bucket: str) -> str:
        self._safe_segments(bucket)
        return os.path.join(self.root, bucket)

    def _obj_path(self, bucket: str, object_: str) -> str:
        segs = self._safe_segments(bucket, object_)
        return os.path.join(self.root, bucket, *segs)

    def _meta_path(self, bucket: str, object_: str) -> str:
        segs = self._safe_segments(bucket, object_)
        return os.path.join(
            self.root, SYS_DIR, "meta", bucket, *segs, "fs.json"
        )

    def _upload_dir(self, bucket: str, object_: str, upload_id: str) -> str:
        # uploadId becomes a directory name: reject separators/dot-dirs so
        # a forged id cannot escape the multipart tree (abort rmtree's it).
        if (not upload_id or "/" in upload_id or "\\" in upload_id
                or upload_id in (".", "..")):
            raise ErrInvalidUploadID(upload_id)
        sha = hashlib.sha256(f"{bucket}/{object_}".encode()).hexdigest()
        return os.path.join(self.root, SYS_DIR, "multipart", sha, upload_id)

    def check_bucket(self, bucket: str):
        if not os.path.isdir(self._bucket_path(bucket)):
            raise ErrBucketNotFound(bucket)

    # --- buckets ---

    def make_bucket(self, bucket: str, opts=None):
        p = self._bucket_path(bucket)
        if os.path.isdir(p):
            raise ErrBucketExists(bucket)
        os.makedirs(p)

    def delete_bucket(self, bucket: str, force: bool = False):
        p = self._bucket_path(bucket)
        self.check_bucket(bucket)
        if not force and any(os.scandir(p)):
            raise ErrBucketNotEmpty(bucket)
        shutil.rmtree(p)
        meta = os.path.join(self.root, SYS_DIR, "meta", bucket)
        shutil.rmtree(meta, ignore_errors=True)

    def bucket_exists(self, bucket: str) -> bool:
        return os.path.isdir(self._bucket_path(bucket))

    def get_bucket_info(self, bucket: str) -> BucketInfo:
        self.check_bucket(bucket)
        st = os.stat(self._bucket_path(bucket))
        return BucketInfo(bucket, int(st.st_mtime_ns))

    def list_buckets(self) -> list[BucketInfo]:
        out = []
        for name in sorted(os.listdir(self.root)):
            if name == SYS_DIR or name.startswith("."):
                continue
            p = os.path.join(self.root, name)
            if os.path.isdir(p):
                out.append(BucketInfo(name, int(os.stat(p).st_mtime_ns)))
        return out

    # --- objects ---

    def put_object(self, bucket, object_, reader, size, opts=None) -> ObjectInfo:
        self.check_bucket(bucket)
        opts = opts or ObjectOptions()
        tmp = os.path.join(
            self.root, SYS_DIR, "tmp", f"put-{os.getpid()}-{time.time_ns()}"
        )
        md5 = hashlib.md5()
        total = 0
        try:
            with open(tmp, "wb") as f:
                # size < 0: unknown-length stream (transform chains);
                # read to EOF.
                while size < 0 or total < size:
                    want = (1 << 20) if size < 0 else min(1 << 20,
                                                          size - total)
                    chunk = reader.read(want)
                    if not chunk:
                        break
                    md5.update(chunk)
                    f.write(chunk)
                    total += len(chunk)
            if size >= 0 and total != size:
                from ..utils.errors import ErrLessData

                raise ErrLessData(f"read {total} of {size}")
            size = total
        except BaseException:
            # reader.read may raise (e.g. body-hash verification): never
            # leave the staged file behind.
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        etag_hex = md5.hexdigest()
        if opts.want_md5_hex and etag_hex != opts.want_md5_hex:
            from ..utils.errors import ErrBadDigest

            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise ErrBadDigest(
                f"content md5 {etag_hex} != declared {opts.want_md5_hex}"
            )
        dst = self._obj_path(bucket, object_)
        os.makedirs(os.path.dirname(dst), exist_ok=True)
        os.replace(tmp, dst)
        etag = compute_etag(md5.digest())
        meta = {
            "etag": etag,
            "size": size,
            "mod_time_ns": time.time_ns(),
            "meta": dict(opts.user_defined or {}),
        }
        self._write_meta(bucket, object_, meta)
        return self._info(bucket, object_, meta)

    def _write_meta(self, bucket: str, object_: str, meta: dict) -> None:
        """Write-temp-then-rename the sidecar meta json: a crash
        mid-dump must never leave a torn document behind (the scanner's
        usage snapshot and every listing read these — ISSUE 14)."""
        mp = self._meta_path(bucket, object_)
        os.makedirs(os.path.dirname(mp), exist_ok=True)
        tmp = mp + f".tmp.{os.getpid()}.{time.monotonic_ns()}"
        with open(tmp, "w") as f:
            json.dump(meta, f)
        os.replace(tmp, mp)

    def update_object_metadata(self, bucket, object_, version_id, updates,
                               replace_user_meta=False):
        """Metadata-only update (replication status flips, metadata-REPLACE
        self-copy) — the FS analog of updateObjectMeta. Returns the new
        mod time ns when replace_user_meta stamped one, else None."""
        meta = self._load_meta(bucket, object_)
        if replace_user_meta:
            # Drop ONLY client metadata; internal markers (sealed SSE
            # key, compression) describe the stored bytes and must
            # survive a metadata REPLACE (parity with the erasure
            # backend's _update_object_metadata).
            user = {k: v for k, v in (meta.get("meta") or {}).items()
                    if not k.startswith("x-amz-meta-")}
        else:
            user = dict(meta.get("meta") or {})
        user.update(updates)
        meta["meta"] = user
        new_mod_time = None
        if replace_user_meta:
            new_mod_time = time.time_ns()
            meta["mod_time_ns"] = new_mod_time
        self._write_meta(bucket, object_, meta)
        return new_mod_time

    def _load_meta(self, bucket: str, object_: str) -> dict:
        try:
            with open(self._meta_path(bucket, object_)) as f:
                return json.load(f)
        except FileNotFoundError:
            p = self._obj_path(bucket, object_)
            if os.path.isfile(p):
                st = os.stat(p)
                return {
                    "etag": "", "size": st.st_size,
                    "mod_time_ns": st.st_mtime_ns, "meta": {},
                }
            raise ErrObjectNotFound(f"{bucket}/{object_}") from None

    def _info(self, bucket: str, object_: str, meta: dict) -> ObjectInfo:
        return ObjectInfo(
            bucket=bucket, name=object_, etag=meta.get("etag", ""),
            size=meta.get("size", 0),
            mod_time_ns=meta.get("mod_time_ns", 0),
            content_type=meta.get("meta", {}).get("content-type", ""),
            user_defined=dict(meta.get("meta", {})),
        )

    def get_object_info(self, bucket, object_, opts=None) -> ObjectInfo:
        self.check_bucket(bucket)
        if not os.path.isfile(self._obj_path(bucket, object_)):
            raise ErrObjectNotFound(f"{bucket}/{object_}")
        return self._info(bucket, object_, self._load_meta(bucket, object_))

    def get_object_bytes(self, bucket, object_, offset=0, length=-1,
                         opts=None) -> bytes:
        self.check_bucket(bucket)
        p = self._obj_path(bucket, object_)
        try:
            with open(p, "rb") as f:
                f.seek(offset)
                return f.read() if length < 0 else f.read(length)
        except (FileNotFoundError, IsADirectoryError):
            raise ErrObjectNotFound(f"{bucket}/{object_}") from None

    def get_object(self, bucket, object_, writer, offset=0, length=-1,
                   opts=None):
        if opts is not None and getattr(opts, "expected_etag", ""):
            # Same coherence pin as the erasure layer: the caller
            # advertised an ETag before the body streams; an overwrite
            # since then must abort with zero bytes, never serve
            # different content under the old headers.
            from ..utils.errors import ErrPreconditionFailed

            cur = self.get_object_info(bucket, object_, opts)
            if cur.etag != opts.expected_etag:
                raise ErrPreconditionFailed(
                    f"{bucket}/{object_}: etag changed"
                )
        data = self.get_object_bytes(bucket, object_, offset, length, opts)
        writer.write(data)
        return self.get_object_info(bucket, object_, opts)

    def delete_object(self, bucket, object_, opts=None):
        self.check_bucket(bucket)
        p = self._obj_path(bucket, object_)
        if not os.path.isfile(p):
            raise ErrObjectNotFound(f"{bucket}/{object_}")
        os.unlink(p)
        meta_dir = os.path.dirname(self._meta_path(bucket, object_))
        shutil.rmtree(meta_dir, ignore_errors=True)
        # prune empty parent dirs up to the bucket root
        d = os.path.dirname(p)
        stop = self._bucket_path(bucket)
        while d != stop:
            try:
                os.rmdir(d)
            except OSError:
                break
            d = os.path.dirname(d)
        return None

    def delete_objects(self, bucket, objects, opts=None) -> list:
        errs = []
        for o in objects:
            try:
                self.delete_object(bucket, o, opts)
                errs.append(None)
            except Exception as exc:  # noqa: BLE001 per-object result
                errs.append(exc)
        return errs

    # --- listing (tree walk, ref cmd/tree-walk.go) ---

    def list_object_versions(self, bucket: str, prefix: str = "",
                             key_marker: str = "",
                             version_id_marker: str = "",
                             delimiter: str = "",
                             max_keys: int = 1000):
        """FS mode has no versioning (ref fs-v1 rejects versioned APIs with
        NotImplemented for writes); listing versions reports every object
        as its single 'null' version, matching S3 on an unversioned
        bucket."""
        from .types import ListObjectVersionsInfo

        lo = self.list_objects(bucket, prefix, key_marker, delimiter, max_keys)
        out = ListObjectVersionsInfo(
            is_truncated=lo.is_truncated,
            next_key_marker=lo.next_marker,
            prefixes=lo.prefixes,
        )
        for oi in lo.objects:
            oi.version_id = "null"
            oi.is_latest = True
            out.versions.append(oi)
        return out

    def list_objects(self, bucket: str, prefix: str = "", marker: str = "",
                     delimiter: str = "", max_keys: int = 1000,
                     opts=None) -> ListObjectsInfo:
        self.check_bucket(bucket)
        base = self._bucket_path(bucket)
        names: list[str] = []

        def walk(rel: str):
            p = os.path.join(base, *rel.split("/")) if rel else base
            try:
                entries = sorted(os.listdir(p))
            except (FileNotFoundError, NotADirectoryError):
                return
            for name in entries:
                child_rel = f"{rel}/{name}" if rel else name
                full = os.path.join(p, name)
                if os.path.isdir(full):
                    walk(child_rel)
                else:
                    names.append(child_rel)

        walk("")
        names = [n for n in names if n.startswith(prefix)]
        out = ListObjectsInfo()
        seen_prefixes = set()
        count = 0
        for n in names:
            if delimiter:
                rest = n[len(prefix):]
                if delimiter in rest:
                    cp = prefix + rest.split(delimiter)[0] + delimiter
                    # A marker equal to (or past) a common prefix skips
                    # everything rolled up under it — otherwise pagination
                    # re-emits the same prefix forever.
                    if marker and cp <= marker:
                        continue
                    if cp not in seen_prefixes:
                        seen_prefixes.add(cp)
                        out.prefixes.append(cp)
                        count += 1
                        if count >= max_keys:
                            out.is_truncated = True
                            out.next_marker = cp
                            break
                    continue
            if marker and n <= marker:
                continue
            if count >= max_keys:
                out.is_truncated = True
                out.next_marker = out.objects[-1].name if out.objects else n
                break
            out.objects.append(
                self._info(bucket, n, self._load_meta(bucket, n))
            )
            count += 1
        return out

    # --- multipart (ref cmd/fs-v1-multipart.go) ---

    def new_multipart_upload(self, bucket, object_, opts=None) -> str:
        self.check_bucket(bucket)
        from ..storage.fileinfo import new_uuid

        upload_id = new_uuid()
        d = self._upload_dir(bucket, object_, upload_id)
        os.makedirs(d)
        with open(os.path.join(d, "fs.json"), "w") as f:
            json.dump({
                "bucket": bucket, "object": object_,
                "meta": dict((opts.user_defined if opts else {}) or {}),
            }, f)
        return upload_id

    def _check_upload(self, bucket, object_, upload_id) -> str:
        d = self._upload_dir(bucket, object_, upload_id)
        if not os.path.isdir(d):
            raise ErrInvalidUploadID(upload_id)
        return d

    def put_object_part(self, bucket, object_, upload_id, part_number,
                        reader, size, opts=None) -> PartInfo:
        d = self._check_upload(bucket, object_, upload_id)
        md5 = hashlib.md5()
        total = 0
        tmp = os.path.join(d, f".tmp-{part_number}")
        try:
            with open(tmp, "wb") as f:
                while total < size:
                    chunk = reader.read(min(1 << 20, size - total))
                    if not chunk:
                        break
                    md5.update(chunk)
                    f.write(chunk)
                    total += len(chunk)
            if total != size:
                from ..utils.errors import ErrLessData

                raise ErrLessData(f"read {total} of {size}")
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        etag = md5.hexdigest()
        if opts is not None and opts.want_md5_hex and etag != opts.want_md5_hex:
            from ..utils.errors import ErrBadDigest

            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise ErrBadDigest(
                f"part md5 {etag} != declared {opts.want_md5_hex}"
            )
        os.replace(tmp, os.path.join(d, f"part.{part_number}"))
        with open(os.path.join(d, f"part.{part_number}.json"), "w") as f:
            json.dump({"etag": etag, "size": total,
                       "mod_time_ns": time.time_ns()}, f)
        return PartInfo(part_number, etag, total, total, time.time_ns())

    def list_object_parts(self, bucket, object_, upload_id, part_marker=0,
                          max_parts=1000) -> list[PartInfo]:
        d = self._check_upload(bucket, object_, upload_id)
        out = []
        for name in sorted(os.listdir(d)):
            if not name.endswith(".json") or name == "fs.json":
                continue
            pn = int(name.split(".")[1])
            if pn <= part_marker:
                continue
            with open(os.path.join(d, name)) as f:
                info = json.load(f)
            out.append(PartInfo(pn, info["etag"], info["size"],
                                info["size"], info["mod_time_ns"]))
        out.sort(key=lambda p: p.part_number)
        return out[: max_parts + 1]

    def list_multipart_uploads(self, bucket, prefix="") -> list[MultipartInfo]:
        self.check_bucket(bucket)
        root = os.path.join(self.root, SYS_DIR, "multipart")
        out = []
        for sha in sorted(os.listdir(root)):
            for upload_id in sorted(os.listdir(os.path.join(root, sha))):
                fs_json = os.path.join(root, sha, upload_id, "fs.json")
                try:
                    with open(fs_json) as f:
                        info = json.load(f)
                except (FileNotFoundError, ValueError):
                    continue
                if info["bucket"] != bucket:
                    continue
                if prefix and not info["object"].startswith(prefix):
                    continue
                out.append(MultipartInfo(
                    bucket, info["object"], upload_id, info.get("meta", {})
                ))
        return out

    def abort_multipart_upload(self, bucket, object_, upload_id):
        d = self._check_upload(bucket, object_, upload_id)
        shutil.rmtree(d)

    def complete_multipart_upload(self, bucket, object_, upload_id, parts,
                                  opts=None) -> ObjectInfo:
        d = self._check_upload(bucket, object_, upload_id)
        with open(os.path.join(d, "fs.json")) as f:
            up_info = json.load(f)
        md5s = []
        tmp = os.path.join(
            self.root, SYS_DIR, "tmp", f"mp-{os.getpid()}-{time.time_ns()}"
        )
        total = 0
        with open(tmp, "wb") as out:
            for cp in parts:
                pj = os.path.join(d, f"part.{cp.part_number}.json")
                try:
                    with open(pj) as f:
                        info = json.load(f)
                except FileNotFoundError:
                    os.unlink(tmp)
                    raise ErrInvalidPart(str(cp.part_number)) from None
                if info["etag"] != cp.etag:
                    os.unlink(tmp)
                    raise ErrInvalidPart(f"{cp.part_number} etag mismatch")
                md5s.append(bytes.fromhex(info["etag"]))
                with open(os.path.join(d, f"part.{cp.part_number}"), "rb") as pf:
                    shutil.copyfileobj(pf, out)
                total += info["size"]
        dst = self._obj_path(bucket, object_)
        os.makedirs(os.path.dirname(dst), exist_ok=True)
        os.replace(tmp, dst)
        etag = compute_etag(
            hashlib.md5(b"".join(md5s)).digest(), parts=len(parts)
        )
        meta = {
            "etag": etag, "size": total, "mod_time_ns": time.time_ns(),
            "meta": up_info.get("meta", {}),
        }
        self._write_meta(bucket, object_, meta)
        shutil.rmtree(d)
        return self._info(bucket, object_, meta)

    # --- heal / health (no-ops on a single disk, ref fs-v1.go) ---

    def heal_object(self, bucket, object_, version_id="",
                    remove_dangling=False) -> dict:
        self.get_object_info(bucket, object_)
        return {"healed": False, "backend": "fs"}

    def heal_bucket(self, bucket) -> dict:
        self.check_bucket(bucket)
        return {"healed": False, "backend": "fs"}

    def heal_format(self) -> dict:
        return {"backend": "fs"}

    def health(self) -> bool:
        return os.path.isdir(self.root)
