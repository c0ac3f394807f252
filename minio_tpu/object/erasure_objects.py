"""ErasureObjects — one erasure set: object CRUD over k+m disks with
quorum semantics, the TPU-backed equivalent of the reference's
erasureObjects (/root/reference/cmd/erasure.go:50-78 and
cmd/erasure-object.go).

Write path mirrors putObject (cmd/erasure-object.go:595-817): shuffle
disks by the object's hash order, stage bitrot-framed shards under tmp,
batch-encode on the MXU, then rename-commit under write quorum. Read path
mirrors getObjectWithFileInfo (:236-356): quorum-pick xl.meta, k-of-n
shard reads with reconstruct-on-miss, heal hints queued MRF-style.
"""

from __future__ import annotations

import io
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

from ..erasure.bitrot import (
    BitrotAlgorithm,
    StreamingBitrotReader,
    StreamingBitrotWriter,
)
from ..erasure import registry as _codec_registry
from ..erasure.codec import Erasure
from ..erasure import repair as _repair
from ..erasure.streaming import decode_stream, encode_stream, heal_stream
from ..storage.fileinfo import ChecksumInfo, ErasureInfo, FileInfo, new_uuid
from ..storage import local as _local_storage
from ..storage.local import SYSTEM_META_BUCKET
from ..utils.errors import (
    OBJECT_OP_IGNORED_ERRS,
    ErrBadDigest,
    ErrDiskNotFound,
    ErrErasureReadQuorum,
    ErrErasureWriteQuorum,
    ErrFileNotFound,
    ErrFileVersionNotFound,
    ErrInvalidArgument,
    ErrLessData,
    ErrMethodNotAllowed,
    ErrObjectNotFound,
    ErrPreconditionFailed,
    ErrVersionNotFound,
    ErrVolumeNotFound,
    ErrBucketNotFound,
    reduce_read_quorum_errs,
    reduce_write_quorum_errs,
)
from .metadata import (
    find_file_info_in_quorum,
    common_mod_time,
    hash_order,
    object_quorum_from_meta,
    read_all_file_info,
    shuffle_disks,
    shuffle_disks_and_parts_metadata,
)
from .types import ObjectInfo, ObjectOptions, TeeMD5Reader

BLOCK_SIZE_V2 = 1 << 20  # erasure block size, ref cmd/object-api-common.go:39

_obj_pool = ThreadPoolExecutor(max_workers=64, thread_name_prefix="mtpu-obj")

from ..observability import carry as _obs_carry
from ..observability import ioflow as _ioflow
from ..observability import spans as _spans
from . import readtier as _readtier
from ..utils.fanout import StragglerCompensator
from ..utils.fanout import decode_slot as _decode_slot
from ..utils.fanout import encode_slot as _encode_slot
from ..utils.fanout import heal_slot as _heal_slot

# Commit/delete stragglers detached by _quorum_fanout keep occupying
# their _obj_pool worker until the hung call returns; compensate the
# ceiling meanwhile so healthy fan-outs keep full concurrency.
_obj_compensator = StragglerCompensator(_obj_pool)


def _close_sinks(sinks):
    """Best-effort close of every open sink — failure paths must never
    leave raw-fd (O_DIRECT) writers to the GC."""
    for s in sinks.values() if isinstance(sinks, dict) else sinks:
        if s is not None:
            try:
                s.close()
            except Exception:  # noqa: BLE001 - best effort
                pass


def _fanout(fn, n: int):
    """Run fn(i) for i in range(n) through the pool. Pool threads carry
    the caller's request-scoped observability context (span trace +
    byte-flow op tag) so metadata reads/writes attribute to the
    request. The caller's wait for all of them is a `fanout` span,
    label `all`, on the profiler's clock too."""
    with _spans.span("fanout", "all", mirror=True):
        list(_obj_pool.map(_obs_carry(fn), range(n)))


def _quorum_fanout(attempt, n: int, errs: list, quorum: int,
                   op_deadline_s: float | None = None,
                   straggler_grace_s: float | None = None) -> None:
    """Quorum-wait fan-out for commit/delete paths: run attempt(i)
    (which RAISES on failure) for i in range(n), recording errs[i], and
    return as soon as `quorum` successes land plus a short straggler
    grace. Disks still in flight past that are detached: errs[i]
    becomes ErrDiskOpTimeout (quorum-ignored, like an offline disk) and
    a late result is discarded — the caller's MRF/heal machinery repairs
    whatever the straggler missed. A hung drive therefore bounds a
    commit at (op deadline + straggler grace) instead of wedging it
    (ref the per-op deadlines of cmd/xl-storage-disk-id-check.go).

    Known window: a detached straggler's rename can land AFTER the
    caller released its per-object write lock, so one disk may briefly
    carry metadata a racing newer write already superseded. Both commit
    callers queue the object in MRF whenever errs is non-nil, and MRF
    heal rewrites the minority disk to the quorum mod-time — the stale
    copy never survives past the next drain."""
    from ..erasure.streaming import record_stat
    from ..storage.diskcheck import ROBUST
    from ..utils.errors import ErrDiskOpTimeout
    from ..utils.fanout import QuorumFanout

    deadline_s = (op_deadline_s if op_deadline_s is not None
                  else ROBUST.op_deadline_s)
    grace_s = (straggler_grace_s if straggler_grace_s is not None
               else ROBUST.straggler_grace_s)
    pending = set(range(n))

    def record(i, err):
        if err is not None:
            errs[i] = err

    def on_detach(i):
        errs[i] = ErrDiskOpTimeout(
            f"disk {i} straggling past quorum commit"
        )

    QuorumFanout(_obj_pool, _obj_compensator).dispatch(
        attempt, pending, quorum, deadline_s, grace_s,
        count_ok=lambda: sum(1 for j in range(n)
                             if errs[j] is None and j not in pending),
        record=record,
        on_detach=on_detach,
        on_stragglers=lambda k: record_stat("fanout_stragglers_total", k),
    )


from .multipart import MultipartMixin


class ErasureObjects(MultipartMixin):
    """One erasure set of len(disks) shards (4..16 in the reference)."""

    def __init__(self, disks: list, default_parity: int | None = None,
                 set_index: int = 0, pool_index: int = 0):
        if len(disks) < 2:
            raise ErrInvalidArgument("erasure set needs >= 2 disks")
        self.disks = list(disks)
        self.set_drive_count = len(disks)
        self.default_parity = (
            default_parity if default_parity is not None else len(disks) // 2
        )
        self.set_index = set_index
        self.pool_index = pool_index
        # MRF-style queue of (bucket, object, version_id) needing heal
        # (ref mrfOpCh, cmd/erasure.go:75). Enqueue times ride in a
        # parallel list (same lock, same order) feeding the heal
        # scoreboard's age-of-oldest gauge without changing the entry
        # shape drain callers and tests consume.
        self._mrf: list[tuple[str, str, str]] = []
        self._mrf_times: list[float] = []  # guarded-by: _mrf_lock
        self._mrf_lock = threading.Lock()
        # Namespace locks for this set (ref nsMutex, cmd/erasure.go:60).
        from ..utils.nslock import NamespaceLock

        self._ns_lock = NamespaceLock()
        # Cluster-wide lockers (dsync plane): when the server joins a
        # multi-node deployment it installs the cluster's locker set
        # here, and namespace locks become quorum DRWMutexes — a write
        # on node A and node B of one object serialize cluster-wide
        # (ref nsLockMap with distributed dsync, cmd/namespace-lock.go).
        self.dist_lockers = None
        self.dist_owner = ""

    # ------------------------------------------------------------------
    # helpers

    # Lock acquisition is bounded so a lock cycle (e.g. two opposing
    # cross-object copies) degrades to a retriable 503, never a wedged
    # worker thread (the reference's dsync acquisition timeout).
    NS_LOCK_TIMEOUT_S = 120.0

    from contextlib import contextmanager as _ctxmgr

    @_ctxmgr
    def _dist_lock(self, bucket: str, object_: str, writer: bool):
        """Cluster-wide quorum lock when dsync lockers are installed."""
        from ..distributed.dsync import DRWMutex
        from ..utils.errors import ErrOperationTimedOut

        mu = DRWMutex(self.dist_lockers, f"{bucket}/{object_}",
                      owner=self.dist_owner)
        ok = (mu.lock(timeout=self.NS_LOCK_TIMEOUT_S) if writer
              else mu.rlock(timeout=self.NS_LOCK_TIMEOUT_S))
        if not ok:
            raise ErrOperationTimedOut(f"dsync {bucket}/{object_}")
        try:
            yield
            if mu.lost.is_set():
                # Refresh quorum vanished mid-operation (locker restart
                # or expiry): another writer may have been admitted, so
                # the operation must FAIL rather than report success on
                # possibly-interleaved state (ref dsync canceling the
                # op context on lost refresh quorum).
                raise ErrOperationTimedOut(
                    f"dsync lock lost during {bucket}/{object_}"
                )
        finally:
            mu.unlock()

    @_ctxmgr
    def _locked_write(self, bucket: str, object_: str):
        with self._held(bucket, object_, writer=True):
            yield

    @_ctxmgr
    def _locked_read(self, bucket: str, object_: str):
        with self._held(bucket, object_, writer=False):
            yield

    @_ctxmgr
    def _held(self, bucket: str, object_: str, writer: bool):
        """The object's namespace lock, held for the block: the quorum
        `DRWMutex` where dsync lockers are installed, the set's own
        `NamespaceLock` where not. Its acquisition alone is a `lock`
        span, on the profiler's clock as `mtpu:lock` where nothing
        mirrored is open on the thread (at a request's entry nothing
        is; a remote locker's call under it is an unmirrored `rpc`)."""
        from contextlib import ExitStack

        from ..utils.errors import ErrOperationTimedOut

        label = "write" if writer else "read"
        if self.dist_lockers:
            with ExitStack() as stack:
                with _spans.span("lock", label, mirror=True):
                    stack.enter_context(
                        self._dist_lock(bucket, object_, writer))
                yield
            return
        key = f"{bucket}/{object_}"
        take = self._ns_lock.write if writer else self._ns_lock.read
        try:
            with ExitStack() as stack:
                with _spans.span("lock", label, mirror=True):
                    stack.enter_context(
                        take(key, timeout=self.NS_LOCK_TIMEOUT_S))
                yield
        except TimeoutError as exc:
            raise ErrOperationTimedOut(key) from exc

    def _object_erasure(self, k: int, m: int, codec: str = "") -> Erasure:
        # (geometry, codec)-keyed shared instance: PUT/GET/heal of one
        # erasure set reuse the same coder (matrices, device engine
        # caches) instead of re-deriving them per object — the per-PUT
        # setup cost the pool-batched path measured. "" = dense default
        # (pre-registry metadata that never stamped a codec id).
        from ..erasure.codec import cached_erasure

        return cached_erasure(k, m, BLOCK_SIZE_V2,
                              codec or _codec_registry.DEFAULT_CODEC)

    def _tmp_path(self, tmp_id: str) -> str:
        return f"tmp/{tmp_id}"

    def queue_mrf(self, bucket: str, object_: str, version_id: str = "",
                  enqueued_at: float | None = None):
        """enqueued_at: pass the ORIGINAL drain_mrf timestamp when
        re-queueing a failed heal, so mrf_oldest_age_seconds keeps
        aging a stuck repair instead of resetting every drain pass."""
        with self._mrf_lock:
            self._mrf.append((bucket, object_, version_id))
            self._mrf_times.append(
                time.monotonic() if enqueued_at is None else enqueued_at
            )

    def drain_mrf(self, with_times: bool = False) -> list[tuple]:
        with self._mrf_lock:
            out, self._mrf = self._mrf, []
            times, self._mrf_times = self._mrf_times, []
        if with_times:
            return [(b, o, v, t) for (b, o, v), t in zip(out, times)]
        return out

    def mrf_stats(self) -> dict:
        """Heal-scoreboard snapshot: backlog depth + age of the oldest
        queued entry (seconds). min() scan, not index 0: a failed heal
        re-queues with its ORIGINAL timestamp, which can land after
        fresher entries — O(backlog) at scoreboard cadence is cheap."""
        with self._mrf_lock:
            depth = len(self._mrf)
            oldest = min(self._mrf_times) if self._mrf_times else None
        return {
            "pending": depth,
            "oldest_age_s": (round(time.monotonic() - oldest, 3)
                             if oldest is not None else 0.0),
        }

    # ------------------------------------------------------------------
    # bucket ops (ref cmd/erasure-bucket.go)

    def make_bucket(self, bucket: str):
        errs: list = [None] * len(self.disks)

        def do(i):
            try:
                if self.disks[i] is None:
                    raise ErrDiskNotFound(f"disk {i}")
                self.disks[i].make_vol(bucket)
            except Exception as exc:  # noqa: BLE001
                errs[i] = exc

        list(_obj_pool.map(_obs_carry(do),
                           range(len(self.disks))))
        write_quorum = len(self.disks) // 2 + 1
        from ..utils.errors import ErrVolumeExists

        real_errs = [None if isinstance(e, ErrVolumeExists) else e for e in errs]
        err = reduce_write_quorum_errs(real_errs, OBJECT_OP_IGNORED_ERRS, write_quorum)
        if err is not None:
            raise err

    def delete_bucket(self, bucket: str, force: bool = False):
        errs: list = [None] * len(self.disks)

        def do(i):
            try:
                if self.disks[i] is None:
                    raise ErrDiskNotFound(f"disk {i}")
                self.disks[i].delete_vol(bucket, force_delete=force)
            except Exception as exc:  # noqa: BLE001
                errs[i] = exc

        list(_obj_pool.map(_obs_carry(do),
                           range(len(self.disks))))
        write_quorum = len(self.disks) // 2 + 1
        real_errs = [None if isinstance(e, ErrVolumeNotFound) else e for e in errs]
        err = reduce_write_quorum_errs(real_errs, OBJECT_OP_IGNORED_ERRS, write_quorum)
        if err is not None:
            raise err

    def bucket_exists(self, bucket: str) -> bool:
        ok = 0
        for d in self.disks:
            if d is None:
                continue
            try:
                d.stat_vol(bucket)
                ok += 1
            except Exception:  # noqa: BLE001
                continue
        return ok >= (len(self.disks) // 2)

    # ------------------------------------------------------------------
    # put (ref cmd/erasure-object.go:595-817)

    def put_object(self, bucket: str, object_: str, reader, size: int,
                   opts: ObjectOptions | None = None) -> ObjectInfo:
        opts = opts or ObjectOptions()
        if opts.no_lock:
            oi = self._put_object(bucket, object_, reader, size, opts)
        else:
            # Serialize concurrent writers of one object so rename_data /
            # write_metadata cannot interleave across disks into a
            # mixed-mod-time quorum state (ref NSLock at
            # cmd/erasure-object.go:741-749).
            with self._locked_write(bucket, object_):
                oi = self._put_object(bucket, object_, reader, size, opts)
        # Source-payload bytes of a COMMITTED put: the denominator of
        # the write-amplification series (aborted puts never count).
        _ioflow.logical(oi.size)
        # Hot-tier hygiene: dead versions stop holding block-cache
        # quota (correctness never depends on this — cache keys pin the
        # version-id + etag read fresh per GET).
        _readtier.invalidate(bucket, object_)
        return oi

    def _put_object(self, bucket: str, object_: str, reader, size: int,
                    opts: ObjectOptions) -> ObjectInfo:
        # The object layer's span: admission, stream and commit are its
        # children, the rest (set-up, sinks, md5, xl.meta) its self time.
        with _spans.span("object", "put"):
            return self._put_object_inner(bucket, object_, reader, size,
                                          opts)

    def _put_object_inner(self, bucket: str, object_: str, reader, size: int,
                          opts: ObjectOptions) -> ObjectInfo:
        n = self.set_drive_count
        parity = self.default_parity
        if opts.parity is not None:
            # Storage-class override (ref GetParityForSC applied at
            # cmd/erasure-object.go:611-618); data must never be
            # outnumbered by parity.
            if not 0 < opts.parity <= n // 2:
                raise ErrInvalidArgument(
                    f"parity {opts.parity} invalid for {n} drives"
                )
            parity = opts.parity
        data_blocks = n - parity
        write_quorum = data_blocks + (1 if data_blocks == parity else 0)

        # Codec identity is fixed at PUT time and persisted in xl.meta:
        # forced header > MTPU_CODEC env > measured probe > dense.
        codec_id = _codec_registry.select_codec(data_blocks, parity,
                                                forced=opts.codec)
        wire_algo = _codec_registry.get(codec_id).wire_algorithm
        erasure = self._object_erasure(data_blocks, parity, codec_id)
        distribution = hash_order(f"{bucket}/{object_}", n)
        disks_by_shard = shuffle_disks(self.disks, distribution)

        shard_file_size = erasure.shard_file_size(size) if size >= 0 else -1
        inline = 0 <= shard_file_size <= _local_storage.small_file_threshold()

        tmp_id = new_uuid()
        data_dir = new_uuid()
        tee = TeeMD5Reader(reader, size=size)

        # Physical per-shard file size (erasure shard + bitrot frames):
        # known up front for sized PUTs, lets O_DIRECT disks fallocate.
        from ..erasure.bitrot import bitrot_shard_file_size

        phys_shard = (
            bitrot_shard_file_size(
                shard_file_size, erasure.shard_size(),
                BitrotAlgorithm.HIGHWAYHASH256S,
            ) if shard_file_size >= 0 else -1
        )
        writers: list = [None] * n
        sinks: list = [None] * n
        for i, disk in enumerate(disks_by_shard):
            if disk is None:
                continue
            try:
                if inline:
                    sinks[i] = io.BytesIO()
                else:
                    sinks[i] = disk.create_file_writer(
                        SYSTEM_META_BUCKET,
                        f"{self._tmp_path(tmp_id)}/part.1",
                        size=phys_shard,
                    )
                writers[i] = StreamingBitrotWriter(
                    sinks[i], BitrotAlgorithm.HIGHWAYHASH256S
                )
            except Exception:  # noqa: BLE001 - offline disk at open time
                writers[i] = None

        try:
            # The one admission point: the slot covers the encode alone,
            # so set-up and commit I/O of queued PUTs overlap it.
            with _encode_slot():
                total = encode_stream(erasure, tee, writers, write_quorum,
                                      telemetry="put")
        except Exception:
            # Close abandoned sinks BEFORE the tmp cleanup: raw-fd
            # (O_DIRECT) sinks hold an fd + staging buffer that GC may
            # not finalize promptly — aborted uploads must not leak them.
            _close_sinks(sinks)
            if not inline:  # inline PUTs never stage tmp files
                self._cleanup_tmp(disks_by_shard, tmp_id)
            raise
        if size >= 0 and total != size:
            _close_sinks(sinks)
            if not inline:
                self._cleanup_tmp(disks_by_shard, tmp_id)
            raise ErrLessData(f"read {total} bytes, expected {size}")
        size = total

        if not inline:
            for s in sinks:
                if s is not None:
                    try:
                        s.close()
                    except Exception:  # noqa: BLE001
                        pass

        mod_time_ns = opts.mod_time_ns or time.time_ns()
        version_id = opts.version_id or (new_uuid() if opts.versioned else "")
        etag = tee.md5_hex()
        if opts.want_md5_hex and etag != opts.want_md5_hex:
            # Digest verified against the encode stream BEFORE the commit
            # rename: a BadDigest must leave nothing behind (ref
            # pkg/hash/reader.go inline verification).
            if not inline:
                self._cleanup_tmp(disks_by_shard, tmp_id)
            raise ErrBadDigest(
                f"content md5 {etag} != declared {opts.want_md5_hex}"
            )

        metadata = dict(opts.user_defined)
        metadata["etag"] = etag
        metadata.setdefault("content-type", "application/octet-stream")

        # Commit: RenameData tmp -> final (or metadata-only for inline).
        # One PUT's per-disk journals differ only in the shard index, so
        # the fan-out shares ONE serialized xl.meta (stamped per disk)
        # instead of re-packing it 16 times; disks with an existing
        # journal (overwrites) or inline data decline the pack and merge
        # normally (storage/xlmeta.FanoutMetaPack).
        from ..storage.xlmeta import FanoutMetaPack

        meta_pack = FanoutMetaPack()
        errs: list = [None] * n

        def commit(i):
            disk = disks_by_shard[i]
            if disk is None or writers[i] is None:
                raise ErrDiskNotFound(f"disk {i}")
            fi = FileInfo(
                volume=bucket,
                name=object_,
                version_id=version_id,
                data_dir="" if inline else data_dir,
                mod_time_ns=mod_time_ns,
                size=size,
                metadata=dict(metadata),
                erasure=ErasureInfo(
                    algorithm=wire_algo,
                    data_blocks=data_blocks,
                    parity_blocks=parity,
                    block_size=BLOCK_SIZE_V2,
                    index=i + 1,
                    distribution=list(distribution),
                    checksums=[ChecksumInfo(1, BitrotAlgorithm.HIGHWAYHASH256S.value)],
                    codec=codec_id,
                ),
            )
            fi.add_part(1, size, size)
            fi.fanout_pack = meta_pack
            if inline:
                # Inline commit: the shard bytes ride INSIDE xl.meta, so
                # the whole commit is ONE metadata journal write — no
                # staged tmp files, no rename. write_metadata is the
                # direct journal entry point (rename_data would only add
                # the no-op data-dir move on top of the same write).
                fi.data = {1: sinks[i].getvalue()}
                disk.write_metadata(bucket, object_, fi)
            else:
                disk.rename_data(
                    SYSTEM_META_BUCKET, self._tmp_path(tmp_id), fi,
                    bucket, object_,
                )

        # Commit fan-out waits for write quorum + straggler grace, not
        # for every disk: a drive hung in rename_data is detached (its
        # errs slot becomes a timeout) and the missed commit heals via
        # the MRF queue below.
        # The disk ops run on the fan-out pool; this thread's leaf on the
        # profiler's clock is the commit itself.
        with _spans.span("commit", mirror=True):
            _quorum_fanout(commit, n, errs, write_quorum)
        err = reduce_write_quorum_errs(errs, OBJECT_OP_IGNORED_ERRS, write_quorum)
        if err is not None:
            # Undo the renames that DID land (ref undoRename /
            # cmd/erasure-object.go:484): a sub-quorum commit must not
            # leave a readable object behind on the minority disks.
            # Detached stragglers (ErrDiskOpTimeout) are included: their
            # rename may have landed between detach and now, and a
            # best-effort delete is deadline-bounded by the health
            # wrapper. A rename that lands LATER still leaves a
            # sub-quorum dangling version — the scanner's heal pass
            # removes those (isObjectDangling semantics).
            from ..utils.errors import ErrDiskOpTimeout as _ErrTimeout

            undo_fi = FileInfo(volume=bucket, name=object_,
                               version_id=version_id)
            for i, e in enumerate(errs):
                if disks_by_shard[i] is None:
                    continue
                if e is not None and not isinstance(e, _ErrTimeout):
                    continue  # definite failure: nothing landed
                try:
                    disks_by_shard[i].delete_version(bucket, object_, undo_fi)
                except Exception:  # noqa: BLE001 - best effort
                    pass
            if not inline:
                self._cleanup_tmp(disks_by_shard, tmp_id)
            raise err
        # Partial write (quorum met, some disks failed): queue MRF heal
        # (ref cmd/erasure-object.go:798-804 addPartial).
        if any(e is not None for e in errs):
            self.queue_mrf(bucket, object_, version_id)

        fi = FileInfo(
            volume=bucket, name=object_, version_id=version_id,
            mod_time_ns=mod_time_ns, size=size, metadata=metadata,
            erasure=ErasureInfo(
                algorithm=wire_algo,
                data_blocks=data_blocks, parity_blocks=parity,
                block_size=BLOCK_SIZE_V2, distribution=list(distribution),
                codec=codec_id,
            ),
        )
        fi.num_versions = 1
        return ObjectInfo.from_file_info(fi, bucket, object_, opts.versioned)

    def update_object_metadata(self, bucket: str, object_: str,
                               version_id: str, updates: dict,
                               replace_user_meta: bool = False) -> None:
        """Merge `updates` into a version's user metadata on all online
        disks (the reference's updateObjectMeta, used by replication to
        flip X-Amz-Replication-Status, cmd/bucket-replication.go:700+).
        `replace_user_meta` drops existing x-amz-meta-* keys first and
        stamps a fresh mod time (metadata-REPLACE self-copy; AWS bumps
        LastModified). Returns the new mod time ns, or None when the mod
        time was left untouched."""
        # Read-modify-write of every disk's xl.meta: exclusive lock so a
        # concurrent put/heal can't interleave (ref updateObjectMeta under
        # the caller-held NSLock).
        with self._locked_write(bucket, object_):
            out = self._update_object_metadata(bucket, object_, version_id,
                                               updates, replace_user_meta)
            _readtier.invalidate(bucket, object_)
            return out

    def _update_object_metadata(self, bucket: str, object_: str,
                                version_id: str, updates: dict,
                                replace_user_meta: bool = False) -> int | None:
        # read_data=True: the per-disk FileInfo carries inline small-object
        # shards; rewriting the version without them would destroy data.
        fi, fis, _ = self._read_quorum_file_info(
            bucket, object_, version_id, read_data=True
        )
        if replace_user_meta:
            new_meta = {k: v for k, v in fi.metadata.items()
                        if not k.startswith("x-amz-meta-")}
        else:
            new_meta = dict(fi.metadata)
        new_meta.update(updates)
        new_mod_time = time.time_ns() if replace_user_meta else None

        def do(i):
            disk = self.disks[i]
            meta = fis[i]
            if disk is None or meta is None:
                return
            m = FileInfo.from_dict(meta.to_dict())
            m.volume, m.name = bucket, object_
            m.metadata = dict(new_meta)
            if new_mod_time is not None:
                m.mod_time_ns = new_mod_time
            try:
                disk.update_metadata(bucket, object_, m)
            except Exception:  # noqa: BLE001 - best effort per disk
                pass

        list(_obj_pool.map(_obs_carry(do),
                           range(len(self.disks))))
        return new_mod_time

    # ------------------------------------------------------------------
    # ILM tiering primitives (ref transitionObject / RestoreTransitioned,
    # cmd/bucket-lifecycle.go:296+): the TierEngine ships stored bytes
    # to/from the remote tier; these two rewrite local state.

    def transition_object(self, bucket: str, object_: str, version_id: str,
                          updates: dict,
                          expected_mod_time_ns: int | None = None) -> None:
        """Free the version's local shard data, keep its xl.meta with
        `updates` merged in (a None value deletes the key).

        `expected_mod_time_ns` is the optimistic-concurrency guard for
        the tier engine: the upload happened OUTSIDE the lock, so if the
        version changed meanwhile the commit must abort (the uploaded
        remote blob is stale). Metadata commits BEFORE part deletion —
        a crash between the two steps leaves orphaned part files, never
        a version whose data is gone with no tier pointer."""
        with self._locked_write(bucket, object_):
            fi, fis, _ = self._read_quorum_file_info(
                bucket, object_, version_id, read_data=True
            )
            if (expected_mod_time_ns is not None
                    and fi.mod_time_ns != expected_mod_time_ns):
                raise ErrInvalidArgument(
                    f"{bucket}/{object_} changed during transition"
                )
            new_meta = dict(fi.metadata)
            for k, v in updates.items():
                if v is None:
                    new_meta.pop(k, None)
                else:
                    new_meta[k] = v

            committed: list = [False] * len(self.disks)

            def commit_meta(i):
                disk = self.disks[i]
                meta = fis[i]
                if disk is None or meta is None:
                    return
                m = FileInfo.from_dict(meta.to_dict())
                m.volume, m.name = bucket, object_
                m.metadata = dict(new_meta)
                m.data = {}
                try:
                    disk.update_metadata(bucket, object_, m)
                    committed[i] = True
                except Exception:  # noqa: BLE001 - best effort per disk
                    pass

            def drop_parts(i):
                disk = self.disks[i]
                meta = fis[i]
                if disk is None or meta is None or not committed[i]:
                    return
                if meta.data_dir:
                    for part in meta.parts:
                        try:
                            disk.delete(
                                bucket,
                                f"{object_}/{meta.data_dir}/part.{part.number}",
                            )
                        except Exception:  # noqa: BLE001 - best effort
                            pass

            list(_obj_pool.map(_obs_carry(commit_meta),
                               range(len(self.disks))))
            list(_obj_pool.map(_obs_carry(drop_parts),
                               range(len(self.disks))))
        # The version's local shard data is gone: any decoded blocks
        # the hot tier holds for it are dead weight now.
        _readtier.invalidate(bucket, object_)

    def restore_object(self, bucket: str, object_: str, version_id: str,
                       reader, size: int, updates: dict) -> None:
        """Write the version's stored bytes back locally (temporary
        restore of a transitioned object), preserving its metadata and
        version id, with `updates` merged in."""
        fi, _, _ = self._read_quorum_file_info(bucket, object_, version_id)
        meta = dict(fi.metadata)
        meta.update(updates)
        opts = ObjectOptions(
            version_id=version_id or "",
            versioned=bool(version_id),
            user_defined={k: v for k, v in meta.items() if k != "etag"},
            mod_time_ns=fi.mod_time_ns,
        )
        self.put_object(bucket, object_, reader, size, opts)

    def _cleanup_tmp(self, disks: list, tmp_id: str):
        for disk in disks:
            if disk is None:
                continue
            try:
                disk.delete(SYSTEM_META_BUCKET, self._tmp_path(tmp_id), recursive=True)
            except Exception:  # noqa: BLE001 - best effort
                pass

    # ------------------------------------------------------------------
    # get (ref cmd/erasure-object.go:135-356, :390-453)

    def _read_quorum_file_info(self, bucket: str, object_: str, version_id: str,
                               read_data: bool = False):
        fis, errs = read_all_file_info(
            self.disks, bucket, object_, version_id, read_data
        )
        if all(fi is None for fi in fis):
            err = reduce_read_quorum_errs(errs, OBJECT_OP_IGNORED_ERRS, 1)
            raise self._to_object_err(err, bucket, object_, version_id)
        try:
            read_quorum, _ = object_quorum_from_meta(fis, errs, self.default_parity)
        except ErrErasureReadQuorum:
            raise self._to_object_err(
                ErrErasureReadQuorum(), bucket, object_, version_id
            ) from None
        err = reduce_read_quorum_errs(errs, OBJECT_OP_IGNORED_ERRS, read_quorum)
        if err is not None:
            raise self._to_object_err(err, bucket, object_, version_id)
        mt, dd = common_mod_time(fis)
        fi = find_file_info_in_quorum(fis, mt, dd, read_quorum)
        return fi, fis, errs

    @staticmethod
    def _to_object_err(err, bucket, object_, version_id=""):
        if isinstance(err, ErrFileNotFound):
            return ErrObjectNotFound(f"{bucket}/{object_}")
        if isinstance(err, ErrFileVersionNotFound):
            return ErrVersionNotFound(f"{bucket}/{object_} ({version_id})")
        if isinstance(err, ErrVolumeNotFound):
            return ErrBucketNotFound(bucket)
        return err if err is not None else ErrErasureReadQuorum()

    def get_object_info(self, bucket: str, object_: str,
                        opts: ObjectOptions | None = None) -> ObjectInfo:
        opts = opts or ObjectOptions()
        fi, _, _ = self._read_quorum_file_info(bucket, object_, opts.version_id)
        if fi.deleted:
            if not opts.version_id:
                raise ErrObjectNotFound(f"{bucket}/{object_}")
            raise ErrMethodNotAllowed("delete marker")
        return ObjectInfo.from_file_info(
            fi, bucket, object_, opts.versioned or bool(opts.version_id)
        )

    def get_object(self, bucket: str, object_: str, writer,
                   offset: int = 0, length: int = -1,
                   opts: ObjectOptions | None = None) -> ObjectInfo:
        opts = opts or ObjectOptions()
        if opts.no_lock:
            return self._get_object(bucket, object_, writer, offset,
                                    length, opts)
        # Shared read lock: a concurrent put/heal of the same object must
        # not swap data dirs mid-stream (ref cmd/erasure-object.go:145-165).
        with self._locked_read(bucket, object_):
            return self._get_object(bucket, object_, writer, offset,
                                    length, opts)

    def _get_object(self, bucket: str, object_: str, writer,
                    offset: int, length: int,
                    opts: ObjectOptions) -> ObjectInfo:
        # The object layer's span, as a PUT and a heal have it: the read
        # slot's wait (`admission`), the read tier's answer (`readtier`)
        # and `stream` are its children, the rest (the metadata quorum,
        # opening the shard readers) its self time.
        with _spans.span("object", "get"):
            return self._get_object_inner(bucket, object_, writer, offset,
                                          length, opts)

    def _get_object_inner(self, bucket: str, object_: str, writer,
                          offset: int, length: int,
                          opts: ObjectOptions) -> ObjectInfo:
        fi, fis, errs = self._read_quorum_file_info(
            bucket, object_, opts.version_id, read_data=True
        )
        if fi.deleted:
            if not opts.version_id:
                raise ErrObjectNotFound(f"{bucket}/{object_}")
            raise ErrMethodNotAllowed("delete marker")
        if (opts.expected_etag
                and fi.metadata.get("etag", "") != opts.expected_etag):
            # The object changed between the caller's header fetch and
            # this locked read: abort with ZERO bytes written rather
            # than stream a different object under the advertised ETag.
            raise ErrPreconditionFailed(
                f"{bucket}/{object_}: etag changed"
            )

        total = fi.size
        if length == -1:
            length = total - offset
        if offset < 0 or length < 0 or offset + length > total:
            raise ErrInvalidArgument("invalid range")

        erasure = self._object_erasure(
            fi.erasure.data_blocks, fi.erasure.parity_blocks,
            fi.erasure.codec
        )

        if length == 0 or not fi.parts:
            return ObjectInfo.from_file_info(fi, bucket, object_, opts.versioned)

        # Hot-object tier (ISSUE 19): sketch-hot keys are served off
        # the decoded-block cache or coalesced onto another request's
        # in-flight decode. A None return is a binding guarantee that
        # zero bytes were written — the legacy path below then streams
        # the identical bytes (tier off / cold key / late join).
        served = None
        rt = _readtier.tier()
        if rt is not None:
            served = rt.serve(self, bucket, object_, fi, fis, erasure,
                              writer, offset, length)
        if served is not None:
            heal_hint = served[1]
        else:
            # The whole decode+verify section runs under a READ
            # admission slot (ISSUE 11): GET clients flow through the
            # same per-client caps / round-robin fairness / queue-depth
            # 503s as PUT clients, against a separate slot pool so
            # neither plane can starve the other.
            with _decode_slot():
                heal_hint = self._decode_range(
                    bucket, object_, fi, fis, erasure, writer, offset,
                    length,
                )

        if heal_hint is not None:
            # On-read heal trigger (ref cmd/erasure-object.go:319-338).
            self.queue_mrf(bucket, object_, fi.version_id)
            _codec_registry.note_read("get_mrf_queued_total")
        return ObjectInfo.from_file_info(fi, bucket, object_, opts.versioned)

    def _decode_range(self, bucket: str, object_: str, fi, fis, erasure,
                      writer, offset: int, length: int):
        """One decode pipeline for object byte range [offset,
        offset+length): the part loop (ref getObjectWithFileInfo
        :277-353), slot-free — callers hold the read-admission slot
        (the legacy GET path and the hot-tier's single-flight leader;
        coalesced followers never get here). Returns the heal hint."""
        disks_by_shard, metas_by_shard = shuffle_disks_and_parts_metadata(
            self.disks, fis, fi
        )
        part_index, part_offset = fi.to_object_part_index(offset)
        remaining = length
        heal_hint = None
        for p in range(part_index, len(fi.parts)):
            if remaining <= 0:
                break
            part = fi.parts[p]
            part_length = min(part.size - part_offset, remaining)
            till_offset = erasure.shard_file_offset(
                part_offset, part_length, part.size
            )
            readers: list = [None] * len(disks_by_shard)
            for i, disk in enumerate(disks_by_shard):
                meta = metas_by_shard[i]
                if disk is None or meta is None:
                    continue
                readers[i] = self._shard_reader(
                    disk, meta, bucket, object_, fi, part.number,
                    till_offset, erasure.shard_size(),
                )
            if any(r is None
                   for r in readers[:erasure.data_blocks]):
                # A DATA shard is already known missing from the
                # metadata phase (offline/wiped disk): this GET
                # reconstructs from parity from byte zero, and the
                # read-time retag (a present reader failing
                # mid-stream) would never fire. A missing parity
                # shard alone degrades nothing — the data path
                # reads around it.
                _ioflow.retag_degraded()
            _, hint = decode_stream(
                erasure, writer, readers, part_offset, part_length,
                part.size, telemetry="get",
            )
            if hint is not None and heal_hint is None:
                heal_hint = hint
            remaining -= part_length
            part_offset = 0
        return heal_hint

    def _shard_reader(self, disk, meta: FileInfo, bucket: str, object_: str,
                      fi: FileInfo, part_number: int, till_offset: int,
                      shard_size: int):
        inline = meta.data.get(part_number)
        if inline is not None:
            buf = inline

            def open_inline(off, ln, b=buf):
                return io.BytesIO(b[off : off + ln])

            return StreamingBitrotReader(open_inline, till_offset,
                                         shard_size)
        path = f"{object_}/{fi.data_dir}/part.{part_number}"

        def open_stream(off, ln, d=disk, p=path):
            return d.read_file_stream(bucket, p, off, ln)

        return StreamingBitrotReader(open_stream, till_offset, shard_size)

    def _repair_sources(self, avail_by_shard: list, metas_by_shard: list,
                        bucket: str, object_: str, fi, part_number: int):
        """SymbolSource per surviving shard position for the repair
        plane — the disk plus the shard file's bitrot frame geometry.
        Survivors framed with a non-streaming bitrot algorithm have no
        interleaved digests to offset past, so β-slice offsets would be
        wrong: refuse and let the dense path (which reads through the
        algorithm-aware StreamingBitrotReader) handle them."""
        sources: list = [None] * len(avail_by_shard)
        path = f"{object_}/{fi.data_dir}/part.{part_number}"
        for s, disk in enumerate(avail_by_shard):
            if disk is None:
                continue
            algo = BitrotAlgorithm.from_string(
                metas_by_shard[s].erasure.get_checksum_info(
                    part_number
                ).algorithm
            )
            if not algo.streaming:
                raise _repair.RepairUnavailable(
                    f"survivor {s} uses non-streaming bitrot "
                    f"{algo.value!r}"
                )
            sources[s] = _repair.SymbolSource(
                disk=disk, volume=bucket, path=path,
                digest_size=algo.digest_size,
            )
        return sources

    # ------------------------------------------------------------------
    # delete (ref cmd/erasure-object.go:901-1050 DeleteObject(s))

    def delete_object(self, bucket: str, object_: str,
                      opts: ObjectOptions | None = None) -> ObjectInfo:
        opts = opts or ObjectOptions()
        if opts.no_lock:
            oi = self._delete_object(bucket, object_, opts)
        else:
            with self._locked_write(bucket, object_):
                oi = self._delete_object(bucket, object_, opts)
        _readtier.invalidate(bucket, object_)
        return oi

    def _delete_object(self, bucket: str, object_: str,
                       opts: ObjectOptions) -> ObjectInfo:
        n = self.set_drive_count
        write_quorum = n // 2 + 1

        if opts.versioned and not opts.version_id:
            # Versioned delete without a version: write a delete marker.
            marker = FileInfo(
                volume=bucket, name=object_, version_id=new_uuid(),
                deleted=True,
                mod_time_ns=opts.mod_time_ns or time.time_ns(),
            )
            errs: list = [None] * n

            def write_marker(i):
                if self.disks[i] is None:
                    raise ErrDiskNotFound(f"disk {i}")
                self.disks[i].write_metadata(bucket, object_, marker)

            _quorum_fanout(write_marker, n, errs, write_quorum)
            err = reduce_write_quorum_errs(errs, OBJECT_OP_IGNORED_ERRS, write_quorum)
            if err is not None:
                raise err
            if any(e is not None for e in errs):
                # A straggler/offline disk missed the marker: queue MRF
                # for the MARKER's version id so heal replicates that
                # exact version — queueing "" (latest) would no-op if a
                # newer write lands before the drain, leaving the
                # marker permanently missing from that disk's history.
                self.queue_mrf(bucket, object_, marker.version_id)
            oi = ObjectInfo(bucket=bucket, name=object_,
                            version_id=marker.version_id, delete_marker=True)
            return oi

        fi = FileInfo(volume=bucket, name=object_,
                      version_id=opts.version_id, deleted=False)
        errs = [None] * n

        def do(i):
            if self.disks[i] is None:
                raise ErrDiskNotFound(f"disk {i}")
            self.disks[i].delete_version(bucket, object_, fi)

        # Quorum-wait: a hung drive must not wedge DELETEs either; the
        # straggler's stale version is invisible (quorum reads pick the
        # deleted majority) and heals on the next MRF/scanner pass.
        _quorum_fanout(do, n, errs, write_quorum)
        err = reduce_write_quorum_errs(errs, OBJECT_OP_IGNORED_ERRS, write_quorum)
        if err is not None:
            raise self._to_object_err(err, bucket, object_, opts.version_id)
        if any(not isinstance(e, (type(None), ErrFileNotFound,
                                  ErrFileVersionNotFound)) for e in errs):
            # A straggler/offline disk still holds the version the
            # quorum deleted: queue MRF so heal (dangling removal)
            # purges it before later failures could resurrect it.
            self.queue_mrf(bucket, object_, opts.version_id)
        return ObjectInfo(bucket=bucket, name=object_, version_id=opts.version_id)

    def delete_objects(self, bucket: str, objects: list[str],
                       opts: ObjectOptions | None = None) -> list:
        out = []
        for o in objects:
            try:
                self.delete_object(bucket, o, opts)
                out.append(None)
            except Exception as exc:  # noqa: BLE001
                out.append(exc)
        return out

    # ------------------------------------------------------------------
    # listing (set-level raw walk merge; metacache layers on top)

    def list_objects_raw(self, bucket: str, prefix: str = ""):
        """Merged, de-duplicated sorted stream of (name, xl.meta bytes)
        across this set's disks — the listPathRaw analog
        (ref cmd/metacache-set.go:816-973). Streams a k-way merge of each
        disk's sorted walk (prefix pushed down to the deepest directory),
        so listing cost scales with entries consumed, not bucket size."""
        import heapq

        base_dir = prefix.rsplit("/", 1)[0] if "/" in prefix else ""

        def disk_stream(disk):
            try:
                for name, meta in disk.walk_dir(bucket, base_dir=base_dir,
                                                forward_to=prefix):
                    if prefix and not name.startswith(prefix):
                        if name > prefix:
                            return  # sorted: nothing later can match
                        continue
                    yield name, meta
            except Exception:  # noqa: BLE001 - tolerate offline disks
                return

        streams = [disk_stream(d) for d in self.disks if d is not None]
        last = None
        for name, meta in heapq.merge(*streams, key=lambda t: t[0]):
            if name == last:
                continue
            last = name
            yield name, meta

    # ------------------------------------------------------------------
    # heal (ref cmd/erasure-healing.go:234-519)

    def heal_object(self, bucket: str, object_: str, version_id: str = "",
                    remove_dangling: bool = False) -> dict:
        # Exclusive lock: healing rewrites shards + metadata, so it must
        # not race a foreground put/delete of the same object
        # (ref healObject takes the write NSLock, cmd/erasure-healing.go).
        # Byte-flow choke point: EVERY heal — admin sequence, MRF drain,
        # scanner sampling, fresh-disk sweep — passes here, so the tag
        # is set once and the ledger's heal read/write ratio (bytes read
        # per byte healed) is complete by construction.
        # Pace slot BEFORE the object lock: a heal yielding to
        # foreground pressure must not do so while holding the write
        # lock a foreground PUT of the same object needs.
        # A heal sequence's thread carries no request: the heal is its
        # own root (background: it stays out of the S3 requests' p99).
        # Under an S3 trace the outer root is kept.
        with _spans.request_trace("heal_object", background=True,
                                  path=f"/{bucket}/{object_}"), \
                _ioflow.tag("heal", bucket=bucket), _heal_slot(), \
                self._locked_write(bucket, object_):
            with _spans.span("object", "heal"):
                out = self._heal_object(bucket, object_, version_id,
                                        remove_dangling)
            _readtier.invalidate(bucket, object_)
            return out

    def _heal_object(self, bucket: str, object_: str, version_id: str,
                     remove_dangling: bool) -> dict:
        fis, errs = read_all_file_info(
            self.disks, bucket, object_, version_id, read_data=True
        )
        valid = [fi for fi in fis if fi is not None]
        if not valid:
            raise self._to_object_err(
                reduce_read_quorum_errs(errs, OBJECT_OP_IGNORED_ERRS, 1),
                bucket, object_, version_id,
            )
        mt, dd = common_mod_time(fis)
        ref_fi = next(
            fi for fi in valid if fi.mod_time_ns == mt and fi.data_dir == dd
        )
        data_blocks = ref_fi.erasure.data_blocks
        parity = ref_fi.erasure.parity_blocks

        # Classify disks (ref disksWithAllParts / shouldHealObjectOnDisk).
        available = [False] * len(self.disks)
        for i, fi in enumerate(fis):
            if fi is None or self.disks[i] is None:
                continue
            if fi.mod_time_ns != mt or fi.data_dir != dd or fi.deleted != ref_fi.deleted:
                continue
            try:
                if not fi.deleted:
                    self.disks[i].check_parts(bucket, object_, fi)
                available[i] = True
            except Exception:  # noqa: BLE001 - part missing/corrupt
                continue

        n_avail = sum(available)
        if n_avail < data_blocks and not ref_fi.deleted:
            # Dangling object (ref isObjectDangling :776).
            if remove_dangling:
                try:
                    # no_lock: the heal wrapper already holds the write lock.
                    self.delete_object(
                        bucket, object_,
                        ObjectOptions(version_id=version_id, no_lock=True),
                    )
                except (ErrObjectNotFound, ErrVersionNotFound):
                    pass  # already gone on most disks — purge complete
                return {"healed": [], "dangling": True}
            raise ErrErasureReadQuorum(
                f"only {n_avail} of {data_blocks} shards available"
            )

        stale = [i for i, ok in enumerate(available)
                 if not ok and self.disks[i] is not None]
        if not stale:
            return {"healed": [], "dangling": False}

        distribution = ref_fi.erasure.distribution
        disks_by_shard = shuffle_disks(self.disks, distribution)
        avail_by_shard = shuffle_disks(
            [self.disks[i] if available[i] else None for i in range(len(self.disks))],
            distribution,
        )
        metas_by_shard = shuffle_disks(
            [fis[i] if available[i] else None for i in range(len(self.disks))],
            distribution,
        )
        # shard indices to regenerate = positions whose disk is stale.
        stale_shards = [
            s for s in range(len(disks_by_shard))
            if avail_by_shard[s] is None and disks_by_shard[s] is not None
        ]

        tmp_id = new_uuid()
        inline = bool(ref_fi.data)
        healed_inline: dict[int, dict[int, bytes]] = {s: {} for s in stale_shards}

        if not ref_fi.deleted:
            # Codec only for DATA heals: a delete-marker version carries
            # no erasure geometry (data=parity=0) — building one would
            # raise and leave the marker permanently un-replicable on
            # the disks its write fan-out missed (found by the PR15
            # chaos soak's MRF-dry invariant).
            # The heal MUST rebuild with the codec the object was
            # written under — fresh parity from a different matrix
            # would verify against nothing.
            erasure = self._object_erasure(data_blocks, parity,
                                           ref_fi.erasure.codec)
            # Regenerating repair plane (erasure/repair.py): serves a
            # SINGLE stale shard when the codec declares a repair plan
            # for it and every other shard survives (the plan needs all
            # d = n−1 helpers). Each survivor then reads only its
            # β-slice instead of the whole shard — (n−1)/m bytes of
            # disk read per byte healed vs k dense. Anything else —
            # two stale shards, a missing survivor, inline data, a
            # plan-less codec, MTPU_REPAIR=0, or a mid-repair failure —
            # falls back to the dense read-k-shards path below,
            # byte-identical output either way.
            use_repair = (
                not inline
                and len(stale_shards) == 1
                and _repair.enabled()
                and all(avail_by_shard[s] is not None
                        for s in range(len(disks_by_shard))
                        if s != stale_shards[0])
                and _repair.plan_for(erasure, stale_shards[0]) is not None
            )
            for part in ref_fi.parts:
                from ..erasure.bitrot import bitrot_shard_file_size

                phys_shard = bitrot_shard_file_size(
                    erasure.shard_file_size(part.size),
                    erasure.shard_size(),
                    BitrotAlgorithm.HIGHWAYHASH256S,
                )

                def _open_sinks():
                    ws: list = [None] * len(disks_by_shard)
                    sk: dict[int, object] = {}
                    for s in stale_shards:
                        if inline:
                            sk[s] = io.BytesIO()
                        else:
                            sk[s] = disks_by_shard[s].create_file_writer(
                                SYSTEM_META_BUCKET,
                                f"{self._tmp_path(tmp_id)}/part.{part.number}",
                                size=phys_shard,
                            )
                        ws[s] = StreamingBitrotWriter(
                            sk[s], BitrotAlgorithm.HIGHWAYHASH256S
                        )
                    return ws, sk

                repaired = False
                writers: list = []
                sinks: dict[int, object] = {}
                if use_repair and part.size > 0:
                    target = stale_shards[0]
                    try:
                        sources = self._repair_sources(
                            avail_by_shard, metas_by_shard, bucket,
                            object_, ref_fi, part.number,
                        )
                        writers, sinks = _open_sinks()
                        _repair.repair_part(
                            erasure, target, sources, writers[target],
                            part.size,
                        )
                        repaired = True
                    except Exception:  # noqa: BLE001 - dense path heals
                        # Partial repair output must not survive: the
                        # dense retry re-creates (truncates) the same
                        # tmp shard paths.
                        _close_sinks(sinks)
                        sinks = {}
                if not repaired:
                    till = erasure.shard_file_offset(
                        0, part.size, part.size
                    )
                    readers: list = [None] * len(disks_by_shard)
                    for s in range(len(disks_by_shard)):
                        if avail_by_shard[s] is None:
                            continue
                        readers[s] = self._shard_reader(
                            avail_by_shard[s], metas_by_shard[s], bucket,
                            object_, ref_fi, part.number, till,
                            erasure.shard_size(),
                        )
                    try:
                        writers, sinks = _open_sinks()
                        heal_stream(erasure, writers, readers, part.size,
                                    telemetry="heal")
                    except Exception:
                        # Writer creation OR the heal itself failed:
                        # close whatever sinks exist (O_DIRECT fds must
                        # not wait for GC) and drop the staged tmp
                        # shards.
                        if not inline:
                            _close_sinks(sinks)
                        self._cleanup_tmp(disks_by_shard, tmp_id)
                        raise
                for s in stale_shards:
                    if inline:
                        healed_inline[s][part.number] = sinks[s].getvalue()
                    else:
                        sinks[s].close()

        # Commit healed shards + metadata on stale disks, one after the
        # other on this thread: its disk spans are the mirrored leaves.
        healed = []
        with _spans.span("commit"):
            for s in stale_shards:
                disk = disks_by_shard[s]
                fi = FileInfo.from_dict(ref_fi.to_dict())
                fi.volume, fi.name = bucket, object_
                fi.erasure.index = s + 1
                if inline:
                    fi.data = healed_inline[s]
                try:
                    if inline or ref_fi.deleted:
                        disk.write_metadata(bucket, object_, fi)
                    else:
                        fi.data = {}
                        disk.rename_data(
                            SYSTEM_META_BUCKET, self._tmp_path(tmp_id), fi,
                            bucket, object_,
                        )
                    healed.append(disk.endpoint())
                except Exception:  # noqa: BLE001 - best-effort per disk
                    continue
        return {"healed": healed, "dangling": False}

    def heal_bucket(self, bucket: str) -> dict:
        """Recreate the bucket volume on disks missing it
        (ref healBucket, cmd/erasure-healing.go:57)."""
        healed = []
        for disk in self.disks:
            if disk is None:
                continue
            try:
                disk.stat_vol(bucket)
            except ErrVolumeNotFound:
                try:
                    disk.make_vol(bucket)
                    healed.append(disk.endpoint())
                except Exception:  # noqa: BLE001
                    continue
            except Exception:  # noqa: BLE001
                continue
        return {"healed": healed}
