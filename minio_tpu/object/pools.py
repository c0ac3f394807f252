"""erasureServerPools — the top-level ObjectLayer: routes each object to a
pool (most free space for new objects, existence for reads), merges
listings and healing across pools.

Mirrors /root/reference/cmd/erasure-server-pool.go (getPoolIdx :293,
PutObject :731, GetObjectNInfo :593) plus the list_objects surface of the
reference's ListObjects path, simplified to the set-level raw-walk merge.
"""

from __future__ import annotations

import heapq
import io
import threading
import time

from ..storage.xlmeta import XLMeta
from ..utils.errors import (
    ErrBucketNotFound,
    ErrObjectNotFound,
    ErrVersionNotFound,
)
from .sets import ErasureSets
from .types import ListObjectsInfo, ObjectInfo, ObjectOptions

# `bucket_check_total{answer}`: the front end's bucket checks by where the
# answer came from, at 0 from `set_metrics` on.
BUCKET_CHECK_ANSWERS = ("memo", "drives")
_metrics = None


def set_metrics(registry) -> None:
    global _metrics
    _metrics = registry
    if registry is not None:
        for answer in BUCKET_CHECK_ANSWERS:
            registry.inc("bucket_check_total", 0, answer=answer)


def _count_check(answer: str) -> None:
    """One registry write, under the registry's own lock and no other; a
    check racing `set_metrics` counts into the old registry or none."""
    reg = _metrics
    if reg is not None:
        reg.inc("bucket_check_total", answer=answer)


class ErasureServerPools:
    """ObjectLayer over one or more ErasureSets pools."""

    def __init__(self, pools: list[ErasureSets]):
        if not pools:
            raise ValueError("need at least one pool")
        self.pools = pools
        # Metacache listing state: per-bucket mutation generation (bumped
        # on every write/delete) + the node-local cache of sorted listing
        # streams (ref cmd/metacache-server-pool.go:59; see metacache.py
        # for the design deltas).
        from .metacache import MetacacheManager

        self._list_gen: dict[str, int] = {}
        self._gen_lock = threading.Lock()
        self._metacache = MetacacheManager()
        # Optional DataUpdateTracker (background/tracker.py): every write
        # that invalidates listings also marks the changed bucket so the
        # scanner can skip unchanged ones (ref dataUpdateTracker hooks).
        self.update_tracker = None
        # Optional cross-node ListingCoordinator (distributed/listing.py):
        # when set, pages route to the listing's owner node and mutations
        # broadcast generation bumps to peers.
        self.listing_coordinator = None
        # Positive bucket-existence memo: a bucket check used to stat the
        # bucket volume on EVERY disk per object op (16 syscalls per PUT
        # on the batched path). Positives are safe to cache briefly —
        # delete_bucket forgets, here and (through the S3 handler's
        # broadcast) on every peer — and negatives are never cached, so
        # a just-created bucket is visible immediately.
        self._bucket_seen: dict[str, float] = {}
        self._bucket_seen_lock = threading.Lock()

    _BUCKET_SEEN_TTL_S = 2.0

    def _bump_gen(self, bucket: str):
        with self._gen_lock:
            self._list_gen[bucket] = self._list_gen.get(bucket, 0) + 1
        if self.update_tracker is not None:
            self.update_tracker.mark(bucket)
        if self.listing_coordinator is not None:
            self.listing_coordinator.notify_mutation(bucket)

    def invalidate_listings(self, bucket: str):
        """Peer-driven generation bump (a remote node mutated `bucket`).
        No tracker mark, no re-broadcast — just kill local caches."""
        with self._gen_lock:
            self._list_gen[bucket] = self._list_gen.get(bucket, 0) + 1

    def _page(self, bucket: str, prefix: str, gen: int, marker: str,
              count: int, stream_factory):
        """One metacache page, routed through the cross-node coordinator
        when configured (owner-node shared walks), else node-local."""
        if self.listing_coordinator is not None:
            return self.listing_coordinator.page(
                bucket, prefix, gen, marker, count, stream_factory
            )
        return self._metacache.page(
            bucket, prefix, gen, marker, count, stream_factory
        )

    # --- pool routing ---

    def _pool_with_object(self, bucket: str, object_: str,
                          opts: ObjectOptions | None) -> int | None:
        for i, pool in enumerate(self.pools):
            try:
                pool.get_object_info(bucket, object_, opts)
                return i
            except (ErrObjectNotFound, ErrVersionNotFound):
                continue
        return None

    def _pool_for_put(self, bucket: str, object_: str,
                      opts: ObjectOptions | None) -> int:
        """Existing object keeps its pool; new objects go to the pool with
        the most free space (ref getPoolIdx, cmd/erasure-server-pool.go:293)."""
        if len(self.pools) == 1:
            return 0
        existing = self._pool_with_object(bucket, object_, opts)
        if existing is not None:
            return existing
        best, best_free = 0, -1
        for i, pool in enumerate(self.pools):
            free = 0
            for disk in pool.disks:
                if disk is None:
                    continue
                try:
                    free += disk.disk_info().free
                except Exception:  # noqa: BLE001
                    continue
            if free > best_free:
                best, best_free = i, free
        return best

    # --- bucket ops ---

    def make_bucket(self, bucket: str, opts: ObjectOptions | None = None):
        for pool in self.pools:
            pool.make_bucket(bucket)
        if self.update_tracker is not None:
            self.update_tracker.mark(bucket)

    def delete_bucket(self, bucket: str, force: bool = False):
        self.forget_bucket(bucket)
        for pool in self.pools:
            pool.delete_bucket(bucket, force=force)
        # Forget AGAIN after the volumes are gone: a bucket check racing
        # the deletes above can observe the still-present bucket and
        # re-cache it; this second invalidation closes that window.
        self.forget_bucket(bucket)
        self._metacache.invalidate_bucket(bucket)
        self._list_gen.pop(bucket, None)
        if self.update_tracker is not None:
            self.update_tracker.mark(bucket)

    def bucket_exists(self, bucket: str) -> bool:
        return any(p.bucket_exists(bucket) for p in self.pools)

    def get_bucket_info(self, bucket: str):
        for pool in self.pools:
            for b in pool.list_buckets():
                if b.name == bucket:
                    return b
        raise ErrBucketNotFound(bucket)

    def list_buckets(self):
        seen = {}
        for pool in self.pools:
            for b in pool.list_buckets():
                seen.setdefault(b.name, b)
        return [seen[k] for k in sorted(seen)]

    def check_bucket(self, bucket: str):
        """Raise ErrBucketNotFound unless `bucket` exists: the S3 front
        end's check before every request that names a bucket. Counted once
        a call by where the answer came from; the object ops below check
        again through `_check_bucket`, uncounted, and find the memo the
        front end has just filled."""
        answer = "drives"  # a negative comes from the drives, and raises
        try:
            answer = self._check_bucket(bucket)
        finally:
            _count_check(answer)

    def _check_bucket(self, bucket: str) -> str:
        """The memo while it holds a positive younger than the TTL, else
        every drive (`bucket_exists`); returns which answered. A negative
        raises and is never kept."""
        now = time.monotonic()
        with self._bucket_seen_lock:
            seen = self._bucket_seen.get(bucket, 0.0)
        if now - seen < self._BUCKET_SEEN_TTL_S:
            return "memo"
        if not self.bucket_exists(bucket):
            raise ErrBucketNotFound(bucket)
        with self._bucket_seen_lock:
            self._bucket_seen[bucket] = now
        return "drives"

    def forget_bucket(self, bucket: str):
        with self._bucket_seen_lock:
            self._bucket_seen.pop(bucket, None)

    # --- object ops ---

    def put_object(self, bucket, object_, reader, size, opts=None):
        self._check_bucket(bucket)
        idx = self._pool_for_put(bucket, object_, opts)
        oi = self.pools[idx].put_object(bucket, object_, reader, size, opts)
        self._bump_gen(bucket)
        return oi

    def get_object(self, bucket, object_, writer, offset=0, length=-1, opts=None):
        self._check_bucket(bucket)
        last_exc = None
        for pool in self.pools:
            try:
                return pool.get_object(bucket, object_, writer, offset, length, opts)
            except (ErrObjectNotFound, ErrVersionNotFound) as exc:
                last_exc = exc
        raise last_exc or ErrObjectNotFound(f"{bucket}/{object_}")

    def get_object_bytes(self, bucket, object_, offset=0, length=-1, opts=None) -> bytes:
        buf = io.BytesIO()
        self.get_object(bucket, object_, buf, offset, length, opts)
        return buf.getvalue()

    def get_object_info(self, bucket, object_, opts=None) -> ObjectInfo:
        self._check_bucket(bucket)
        last_exc = None
        for pool in self.pools:
            try:
                return pool.get_object_info(bucket, object_, opts)
            except (ErrObjectNotFound, ErrVersionNotFound) as exc:
                last_exc = exc
        raise last_exc or ErrObjectNotFound(f"{bucket}/{object_}")

    def delete_object(self, bucket, object_, opts=None):
        self._check_bucket(bucket)
        last_exc = None
        for pool in self.pools:
            try:
                out = pool.delete_object(bucket, object_, opts)
                self._bump_gen(bucket)
                return out
            except (ErrObjectNotFound, ErrVersionNotFound) as exc:
                last_exc = exc
        raise last_exc or ErrObjectNotFound(f"{bucket}/{object_}")

    def delete_objects(self, bucket, objects, opts=None):
        return [self._del_one(bucket, o, opts) for o in objects]

    def _del_one(self, bucket, o, opts):
        try:
            self.delete_object(bucket, o, opts)
            return None
        except Exception as exc:  # noqa: BLE001
            return exc

    # --- listing (metacache-served; ref cmd/erasure-server-pool.go:876,
    # --- cmd/metacache-server-pool.go:59-239) ---

    def _merged_stream_factory(self, bucket: str, prefix: str):
        """Factory of the deduplicated cross-pool sorted (name, xl.meta)
        stream — the single source both listing APIs cache from."""
        def factory():
            streams = [p.list_objects_raw(bucket, prefix) for p in self.pools]
            merged = heapq.merge(*streams, key=lambda t: t[0])

            def dedup():
                last = None
                for name, blob in merged:
                    if name == last:
                        continue
                    last = name
                    yield name, blob

            return dedup()

        return factory

    def list_objects(self, bucket: str, prefix: str = "", marker: str = "",
                     delimiter: str = "", max_keys: int = 1000) -> ListObjectsInfo:
        self._check_bucket(bucket)
        if max_keys <= 0:
            return ListObjectsInfo()  # S3: max-keys=0 -> empty, not truncated
        gen = self._list_gen.get(bucket, 0)
        stream_factory = self._merged_stream_factory(bucket, prefix)

        from .metacache import StaleListingCache

        out = ListObjectsInfo()
        prefixes: set[str] = set()
        cursor = marker
        while True:
            # Over-fetch: delimiter roll-up and delete markers consume
            # entries without emitting keys.
            try:
                entries, exhausted = self._page(
                    bucket, prefix, gen, cursor, max_keys + 1, stream_factory
                )
            except StaleListingCache:
                # Raced an invalidation (concurrent write/eviction): the
                # next page call builds a fresh cache at the new gen.
                gen = self._list_gen.get(bucket, 0)
                continue
            for name, meta_blob in entries:
                cursor = name
                if delimiter:
                    rest = name[len(prefix):]
                    if delimiter in rest:
                        prefixes.add(
                            prefix + rest.split(delimiter, 1)[0] + delimiter
                        )
                        continue
                try:
                    meta = XLMeta.from_bytes(meta_blob)
                    fi = meta.to_file_info(bucket, name, None)
                except Exception:  # noqa: BLE001 - skip unreadable entries
                    continue
                if fi.deleted:
                    continue  # latest is a delete marker
                if len(out.objects) >= max_keys:
                    out.is_truncated = True
                    out.next_marker = (
                        out.objects[-1].name if out.objects else name
                    )
                    break
                out.objects.append(ObjectInfo.from_file_info(fi, bucket, name))
            if out.is_truncated or exhausted or not entries:
                break
        out.prefixes = sorted(prefixes)
        return out

    def list_object_versions(self, bucket: str, prefix: str = "",
                             key_marker: str = "",
                             version_id_marker: str = "",
                             delimiter: str = "",
                             max_keys: int = 1000):
        """ListObjectVersions: every version (objects AND delete markers)
        of every key, keys ascending, versions newest-first within a key
        (ref cmd/bucket-listobjects-handlers.go:214-352 +
        erasure-server-pool.go ListObjectVersions). Served from the same
        metacache streams as list_objects — the xl.meta blobs carry the
        full version journal, so no extra disk reads are needed."""
        from ..storage.fileinfo import FileInfo
        from .metacache import StaleListingCache
        from .types import ListObjectVersionsInfo

        self._check_bucket(bucket)
        if max_keys <= 0:
            return ListObjectVersionsInfo()  # S3: empty, not truncated
        gen = self._list_gen.get(bucket, 0)
        stream_factory = self._merged_stream_factory(bucket, prefix)

        out = ListObjectVersionsInfo()
        prefixes: set[str] = set()
        # Page from the key BEFORE key_marker so version_id_marker can
        # resume mid-key.
        cursor = key_marker[:-1] if key_marker else ""
        vid_skip = version_id_marker
        truncated = False
        while not truncated:
            try:
                entries, exhausted = self._page(
                    bucket, prefix, gen, cursor, max_keys + 1, stream_factory
                )
            except StaleListingCache:
                gen = self._list_gen.get(bucket, 0)
                continue
            for name, meta_blob in entries:
                cursor = name
                if key_marker and name < key_marker:
                    continue
                if key_marker and name == key_marker and not vid_skip:
                    continue  # marker key fully consumed last page
                if delimiter:
                    rest = name[len(prefix):]
                    if delimiter in rest:
                        prefixes.add(
                            prefix + rest.split(delimiter, 1)[0] + delimiter
                        )
                        continue
                try:
                    meta = XLMeta.from_bytes(meta_blob)
                except Exception:  # noqa: BLE001
                    continue
                versions = meta.versions
                if key_marker and name == key_marker and vid_skip:
                    # resume after version_id_marker within this key
                    idx = next(
                        (i + 1 for i, v in enumerate(versions)
                         if (v["vid"] or "null") == vid_skip),
                        len(versions),
                    )
                    versions = versions[idx:]
                    vid_skip = ""
                for i, v in enumerate(versions):
                    if len(out.versions) >= max_keys:
                        truncated = True
                        out.is_truncated = True
                        last = out.versions[-1] if out.versions else None
                        out.next_key_marker = last.name if last else name
                        out.next_version_id_marker = (
                            (last.version_id or "null") if last else ""
                        )
                        break
                    fi = FileInfo.from_dict(v)
                    fi.volume, fi.name = bucket, name
                    fi.is_latest = meta.versions[0]["vid"] == v["vid"]
                    oi = ObjectInfo.from_file_info(fi, bucket, name,
                                                   versioned=True)
                    out.versions.append(oi)
                if truncated:
                    break
            if truncated or exhausted or not entries:
                break
        out.prefixes = sorted(prefixes)
        return out

    # --- multipart (single-pool routing for new uploads; existing uploads
    # --- are found by id in whichever pool holds them) ---

    def new_multipart_upload(self, bucket, object_, opts=None):
        self._check_bucket(bucket)
        idx = self._pool_for_put(bucket, object_, opts)
        return self.pools[idx].new_multipart_upload(bucket, object_, opts)

    def put_object_multipart(self, bucket, object_, source, size,
                             part_size=None, opts=None, parallel=None):
        """Parallel multipart PUT (parts encode+hash+MD5 concurrently,
        S3 etag-of-parts) — the high-throughput ingest path for large
        objects; see MultipartMixin.put_object_multipart."""
        self._check_bucket(bucket)
        idx = self._pool_for_put(bucket, object_, opts)
        oi = self.pools[idx].put_object_multipart(
            bucket, object_, source, size, part_size, opts, parallel
        )
        self._bump_gen(bucket)
        return oi

    def _pool_for_upload(self, bucket, object_, upload_id):
        from ..utils.errors import ErrInvalidUploadID

        for pool in self.pools:
            try:
                pool.get_hashed_set(object_)._upload_fi(bucket, object_, upload_id)
                return pool
            except ErrInvalidUploadID:
                continue
        raise ErrInvalidUploadID(upload_id)

    def put_object_part(self, bucket, object_, upload_id, part_number, reader,
                        size, opts=None):
        pool = self._pool_for_upload(bucket, object_, upload_id)
        return pool.put_object_part(
            bucket, object_, upload_id, part_number, reader, size, opts
        )

    def list_object_parts(self, bucket, object_, upload_id, part_marker=0,
                          max_parts=1000):
        pool = self._pool_for_upload(bucket, object_, upload_id)
        return pool.list_object_parts(
            bucket, object_, upload_id, part_marker, max_parts
        )

    def list_multipart_uploads(self, bucket, prefix=""):
        out = []
        for pool in self.pools:
            out.extend(pool.list_multipart_uploads(bucket, prefix))
        return out

    def abort_multipart_upload(self, bucket, object_, upload_id):
        pool = self._pool_for_upload(bucket, object_, upload_id)
        return pool.abort_multipart_upload(bucket, object_, upload_id)

    def complete_multipart_upload(self, bucket, object_, upload_id, parts,
                                  opts=None):
        pool = self._pool_for_upload(bucket, object_, upload_id)
        oi = pool.complete_multipart_upload(
            bucket, object_, upload_id, parts, opts
        )
        self._bump_gen(bucket)
        return oi

    def update_object_metadata(self, bucket, object_, version_id, updates,
                               replace_user_meta=False):
        last_exc = None
        for pool in self.pools:
            try:
                return pool.update_object_metadata(
                    bucket, object_, version_id, updates, replace_user_meta
                )
            except (ErrObjectNotFound, ErrVersionNotFound) as exc:
                last_exc = exc
        raise last_exc or ErrObjectNotFound(f"{bucket}/{object_}")

    def transition_object(self, bucket, object_, version_id, updates,
                          expected_mod_time_ns=None):
        last_exc = None
        for pool in self.pools:
            try:
                out = pool.transition_object(
                    bucket, object_, version_id, updates,
                    expected_mod_time_ns=expected_mod_time_ns)
                self._bump_gen(bucket)
                return out
            except (ErrObjectNotFound, ErrVersionNotFound) as exc:
                last_exc = exc
        raise last_exc or ErrObjectNotFound(f"{bucket}/{object_}")

    def restore_object(self, bucket, object_, version_id, reader, size,
                       updates):
        last_exc = None
        for pool in self.pools:
            try:
                out = pool.restore_object(bucket, object_, version_id,
                                          reader, size, updates)
                self._bump_gen(bucket)
                return out
            except (ErrObjectNotFound, ErrVersionNotFound) as exc:
                last_exc = exc
        raise last_exc or ErrObjectNotFound(f"{bucket}/{object_}")

    # --- heal ---

    def heal_object(self, bucket, object_, version_id="", remove_dangling=False):
        results = []
        for pool in self.pools:
            try:
                results.append(
                    pool.heal_object(bucket, object_, version_id, remove_dangling)
                )
            except (ErrObjectNotFound, ErrVersionNotFound):
                continue
        if not results:
            raise ErrObjectNotFound(f"{bucket}/{object_}")
        # Heal can rewrite xl.meta or purge dangling objects — both are
        # listing-visible mutations.
        self._bump_gen(bucket)
        return results[0] if len(results) == 1 else results

    def heal_bucket(self, bucket):
        return [p.heal_bucket(bucket) for p in self.pools]

    def health(self) -> bool:
        """Cluster can serve writes: every erasure set in every pool has at
        least write-quorum online disks (ref cmd/erasure-server-pool.go:
        1705-1786 Health maintenance check, simplified to the quorum
        predicate)."""
        for pool in self.pools:
            for es in pool.sets:
                online = 0
                for d in es.disks:
                    if d is None:
                        continue
                    try:
                        if d.is_online():
                            online += 1
                    except Exception:  # noqa: BLE001 - offline disk probe
                        continue
                write_quorum = len(es.disks) - es.default_parity
                if es.default_parity == len(es.disks) - es.default_parity:
                    write_quorum += 1
                if online < write_quorum:
                    return False
        return True

    def heal_format(self):
        for pool in self.pools:
            pool.init_format()
