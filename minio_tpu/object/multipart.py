"""Multipart upload lifecycle for an erasure set — the equivalent of
/root/reference/cmd/erasure-multipart.go: uploads staged under
.mtpu.sys/multipart/<sha256(bucket/object)>/<uploadID>/, each part erasure
coded to part.N shard files, committed by renaming the upload dir into the
object's data dir (CompleteMultipartUpload :736).
"""

from __future__ import annotations

import hashlib
import io
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

from ..erasure import registry as _codec_registry
from ..erasure.bitrot import BitrotAlgorithm, StreamingBitrotWriter
from ..observability import carry as obs_carry
from ..observability import ioflow
from ..erasure.codec import Erasure
from ..erasure.streaming import encode_stream
from ..storage.fileinfo import ChecksumInfo, ErasureInfo, FileInfo, new_uuid
from ..utils.fanout import encode_slot as _encode_slot
from ..storage.local import SYSTEM_META_BUCKET
from ..utils.errors import (
    OBJECT_OP_IGNORED_ERRS,
    ErrBadDigest,
    ErrDiskNotFound,
    ErrErasureWriteQuorum,
    ErrInvalidPart,
    ErrInvalidUploadID,
    ErrLessData,
    reduce_read_quorum_errs,
    reduce_write_quorum_errs,
)
from .metadata import (
    find_file_info_in_quorum,
    common_mod_time,
    hash_order,
    read_all_file_info,
    shuffle_disks,
)
from .types import (
    CompletePart,
    MultipartInfo,
    ObjectInfo,
    ObjectOptions,
    PartInfo,
    TeeMD5Reader,
)

_mp_pool = ThreadPoolExecutor(max_workers=32, thread_name_prefix="mtpu-mp")
# The parallel-part driver runs whole put_object_part calls on its OWN
# executor: those calls fan out journal writes through _mp_pool, so
# running them on _mp_pool too would deadlock it against itself once
# enough drivers are in flight.
_part_pool = ThreadPoolExecutor(max_workers=16,
                                thread_name_prefix="mtpu-mp-part")

# Part number ceiling (ref cmd/utils.go:161 globalMaxPartID = 10000).
MAX_PART_ID = 10000


class _SliceReader:
    """Zero-copy reader over one part's slice of a shared buffer:
    read() hands out memoryview sub-slices, readinto() fills the
    caller's strip row directly — either way the only copy of a
    payload byte is the one into the encode strip (the counted
    put.source_read floor)."""

    def __init__(self, mv: memoryview, offset: int, length: int):
        self._mv = mv[offset:offset + length]
        self._pos = 0

    def read(self, n: int = -1):
        left = len(self._mv) - self._pos
        if n is None or n < 0 or n > left:
            n = left
        out = self._mv[self._pos:self._pos + n]
        self._pos += n
        return out

    def readinto(self, b) -> int:
        view = memoryview(b)
        n = min(len(view), len(self._mv) - self._pos)
        view[:n] = self._mv[self._pos:self._pos + n]
        self._pos += n
        return n


class _PreadReader:
    """Per-part reader over a shared file descriptor: every part reads
    its own byte range via os.pread (positionless), so N concurrent
    part streams never fight over one file cursor."""

    def __init__(self, fd: int, offset: int, length: int):
        self._fd = fd
        self._off = offset
        self._left = length

    def read(self, n: int = -1) -> bytes:
        if n is None or n < 0 or n > self._left:
            n = self._left
        if n <= 0:
            return b""
        out = os.pread(self._fd, n, self._off)
        self._off += len(out)
        self._left -= len(out)
        return out

    def readinto(self, b) -> int:
        view = memoryview(b)
        n = min(len(view), self._left)
        if n <= 0:
            return 0
        got = os.pread(self._fd, n, self._off)
        view[:len(got)] = got
        self._off += len(got)
        self._left -= len(got)
        return len(got)


def _part_reader_factory(source):
    """(offset, length) -> reader for one part of `source`, choosing
    the cheapest access path the source supports (see
    put_object_multipart). Generic streams are staged: the factory is
    called IN SUBMISSION ORDER from the driver loop, so sequential
    reads off the shared cursor land in the right part."""
    try:
        # cast("B"): part offsets are BYTE offsets — a uint64 ndarray
        # source would otherwise be sliced in 8-byte elements. Non-C-
        # contiguous buffers refuse the cast and take the staged path.
        mv = memoryview(source).cast("B")
    except TypeError:
        mv = None
    if mv is not None:
        return lambda off, ln: _SliceReader(mv, off, ln)
    fileno = getattr(source, "fileno", None)
    if fileno is not None:
        try:
            fd = fileno()
            # Part offsets are relative to the source's CURRENT
            # position (a caller that consumed a header expects the
            # upload to start where the cursor is, like read() would).
            # The logical tell() — not the raw fd offset, which a
            # BufferedReader's read-ahead has already moved.
            tell = getattr(source, "tell", None)
            base = tell() if tell is not None else os.lseek(
                fd, 0, os.SEEK_CUR)
        except (OSError, io.UnsupportedOperation):
            fd = None
        if fd is not None:
            return lambda off, ln: _PreadReader(fd, base + off, ln)

    def staged(off, ln):
        # One stage copy per byte for cursor-only sources — counted,
        # never silent (the zero-copy floor applies to buffer/fd
        # sources; a socket body cannot be sliced in place).
        from ..pipeline.buffers import copy_add

        buf = bytearray(ln)
        view = memoryview(buf)
        got = 0
        while got < ln:
            n = source.readinto(view[got:]) if hasattr(source, "readinto") \
                else None
            if n is None:
                chunk = source.read(ln - got)
                n = len(chunk)
                if n:
                    view[got:got + n] = chunk
            if not n:
                break
            got += n
        copy_add("put.mp_stage", got)
        return _SliceReader(view, 0, got)

    return staged


def _upload_root(bucket: str, object_: str) -> str:
    sha = hashlib.sha256(f"{bucket}/{object_}".encode()).hexdigest()
    return f"multipart/{sha}"


class MultipartMixin:
    """Multipart methods; mixed into ErasureObjects."""

    def new_multipart_upload(self, bucket: str, object_: str,
                             opts: ObjectOptions | None = None) -> str:
        opts = opts or ObjectOptions()
        n = self.set_drive_count
        parity = self.default_parity
        if opts.parity is not None:
            # Storage-class override: the geometry stored with the
            # upload drives every subsequent part write + complete.
            if not 0 < opts.parity <= n // 2:
                from ..utils.errors import ErrInvalidArgument

                raise ErrInvalidArgument(
                    f"parity {opts.parity} invalid for {n} drives"
                )
            parity = opts.parity
        data_blocks = n - parity
        write_quorum = data_blocks + (1 if data_blocks == parity else 0)
        upload_id = new_uuid()
        upload_path = f"{_upload_root(bucket, object_)}/{upload_id}"

        # The codec is fixed at initiate time and journaled with the
        # upload geometry: every part write and the final complete
        # encode/stamp under the SAME codec id.
        codec_id = _codec_registry.select_codec(data_blocks, parity,
                                                forced=opts.codec)
        fi = FileInfo(
            volume=SYSTEM_META_BUCKET,
            name=upload_path,
            mod_time_ns=time.time_ns(),
            metadata={
                **opts.user_defined,
                "x-mtpu-internal-object": f"{bucket}/{object_}",
            },
            erasure=ErasureInfo(
                algorithm=_codec_registry.get(codec_id).wire_algorithm,
                data_blocks=data_blocks,
                parity_blocks=parity,
                block_size=self._object_erasure(
                    data_blocks, parity, codec_id).block_size,
                distribution=hash_order(f"{bucket}/{object_}", n),
                codec=codec_id,
            ),
        )
        errs: list = [None] * n

        def do(i):
            if self.disks[i] is None:
                errs[i] = ErrDiskNotFound(f"disk {i}")
                return
            f = FileInfo.from_dict(fi.to_dict())
            f.erasure.index = i + 1
            try:
                self.disks[i].write_metadata(SYSTEM_META_BUCKET, upload_path, f)
            except Exception as exc:  # noqa: BLE001
                errs[i] = exc

        list(_mp_pool.map(obs_carry(do), range(n)))
        err = reduce_write_quorum_errs(errs, OBJECT_OP_IGNORED_ERRS, write_quorum)
        if err is not None:
            raise err
        return upload_id

    def _upload_fi(self, bucket: str, object_: str, upload_id: str):
        upload_path = f"{_upload_root(bucket, object_)}/{upload_id}"
        fis, errs = read_all_file_info(self.disks, SYSTEM_META_BUCKET, upload_path)
        valid = [fi for fi in fis if fi is not None]
        if not valid:
            raise ErrInvalidUploadID(upload_id)
        mt, dd = common_mod_time(fis)
        read_quorum = valid[0].erasure.data_blocks or (len(self.disks) // 2)
        err = reduce_read_quorum_errs(errs, OBJECT_OP_IGNORED_ERRS, read_quorum)
        if err is not None:
            raise ErrInvalidUploadID(upload_id)
        fi = find_file_info_in_quorum(fis, mt, dd, read_quorum)
        return fi, fis, upload_path

    def put_object_part(self, bucket: str, object_: str, upload_id: str,
                        part_number: int, reader, size: int,
                        opts: ObjectOptions | None = None) -> PartInfo:
        if not 1 <= part_number <= MAX_PART_ID:
            raise ErrInvalidPart(f"part number {part_number}")
        pi = self._put_object_part_inner(
            bucket, object_, upload_id, part_number, reader, size, opts)
        # Source-payload bytes of a committed part (op=multipart): the
        # write-amplification denominator, like put_object's.
        ioflow.logical(pi.size)
        return pi

    def _put_object_part_inner(self, bucket: str, object_: str,
                               upload_id: str, part_number: int, reader,
                               size: int,
                               opts: ObjectOptions | None = None) -> PartInfo:
        fi, fis, upload_path = self._upload_fi(bucket, object_, upload_id)
        k, m = fi.erasure.data_blocks, fi.erasure.parity_blocks
        write_quorum = k + (1 if k == m else 0)
        erasure = self._object_erasure(k, m, fi.erasure.codec)
        disks_by_shard = shuffle_disks(self.disks, fi.erasure.distribution)

        tee = TeeMD5Reader(reader, size=size)
        # Stage under a tmp name: a re-upload of an existing part number
        # must not clobber the journaled shards until it fully verifies
        # (digest + length), or an aborted retry destroys committed data.
        tmp_part = f"part.{part_number}.tmp.{new_uuid()}"
        writers: list = [None] * len(disks_by_shard)
        sinks: list = [None] * len(disks_by_shard)
        from ..erasure.bitrot import bitrot_shard_file_size

        phys_shard = (
            bitrot_shard_file_size(
                erasure.shard_file_size(size), erasure.shard_size(),
                BitrotAlgorithm.HIGHWAYHASH256S,
            ) if size >= 0 else -1
        )
        for i, disk in enumerate(disks_by_shard):
            if disk is None:
                continue
            try:
                sinks[i] = disk.create_file_writer(
                    SYSTEM_META_BUCKET, f"{upload_path}/{tmp_part}",
                    size=phys_shard,
                )
                writers[i] = StreamingBitrotWriter(
                    sinks[i], BitrotAlgorithm.HIGHWAYHASH256S
                )
            except Exception:  # noqa: BLE001
                writers[i] = None

        def _drop_tmp():
            # Close any open sinks FIRST: raw-fd (O_DIRECT) writers hold
            # an fd + staging buffer that GC may not finalize promptly.
            for s in sinks:
                if s is not None:
                    try:
                        s.close()
                    except Exception:  # noqa: BLE001 - best effort
                        pass
            for disk in disks_by_shard:
                if disk is None:
                    continue
                try:
                    disk.delete(SYSTEM_META_BUCKET,
                                f"{upload_path}/{tmp_part}")
                except Exception:  # noqa: BLE001 - best effort
                    pass

        try:
            # Same admission as _put_object: part uploads take the PUT
            # slots around the encode alone.
            with _encode_slot():
                total = encode_stream(erasure, tee, writers, write_quorum,
                                      telemetry="multipart")
        except Exception:
            _drop_tmp()
            raise
        for s in sinks:
            if s is not None:
                try:
                    s.close()
                except Exception:  # noqa: BLE001
                    pass
        if size >= 0 and total != size:
            _drop_tmp()
            raise ErrLessData(f"read {total}, want {size}")

        etag = tee.md5_hex()
        if opts is not None and opts.want_md5_hex and etag != opts.want_md5_hex:
            # Bad digest: staged shards dropped before the journal (and the
            # previous part's shards) are ever touched (ref
            # pkg/hash/reader.go).
            _drop_tmp()
            raise ErrBadDigest(
                f"part md5 {etag} != declared {opts.want_md5_hex}"
            )
        # Verified: move into place on every disk that took the stream,
        # under the same write quorum as the stream itself — a part whose
        # renames mostly failed must NOT be journaled as uploaded.
        rename_errs: list = [None] * len(disks_by_shard)
        renamed: list[int] = []
        for i, disk in enumerate(disks_by_shard):
            if disk is None or writers[i] is None:
                rename_errs[i] = ErrDiskNotFound(f"disk {i}")
                continue
            try:
                disk.rename_file(
                    SYSTEM_META_BUCKET, f"{upload_path}/{tmp_part}",
                    SYSTEM_META_BUCKET, f"{upload_path}/part.{part_number}",
                )
                renamed.append(i)
            except Exception as exc:  # noqa: BLE001 - reduced below
                rename_errs[i] = exc
        if len(renamed) < write_quorum:
            # Leave the renamed shards in place (deleting them could
            # destroy the only >=k copies of a re-uploaded part), but the
            # part is now a MIX of old and new shard generations across
            # disks — so invalidate its journal entry: a subsequent
            # complete must fail InvalidPart instead of assembling mixed
            # shards into a corrupt object. The client's failed upload
            # means "retry this part" either way.
            _drop_tmp()
            if any(p.number == part_number for p in fi.parts):
                self._journal_remove_part(upload_path, part_number,
                                          write_quorum)
            err = reduce_write_quorum_errs(
                rename_errs, OBJECT_OP_IGNORED_ERRS, write_quorum
            )
            raise err if err else ErrErasureWriteQuorum(
                f"part {part_number}: {len(renamed)} renames succeeded"
            )
        # Journal the part on every disk's upload xl.meta. The journal
        # update is a read-modify-write, so concurrent part uploads for the
        # same upload id are serialized per upload (the reference holds the
        # upload-id nsLock here, cmd/erasure-multipart.go:380+).
        errs: list = [None] * len(self.disks)

        def journal(i):
            if self.disks[i] is None:
                errs[i] = ErrDiskNotFound(f"disk {i}")
                return
            try:
                f = self.disks[i].read_version(SYSTEM_META_BUCKET, upload_path)
                f.add_part(part_number, total, total)
                f.metadata[f"x-mtpu-internal-part-etag-{part_number}"] = etag
                f.erasure.checksums = [
                    c for c in f.erasure.checksums if c.part_number != part_number
                ] + [ChecksumInfo(part_number, BitrotAlgorithm.HIGHWAYHASH256S.value)]
                self.disks[i].write_metadata(SYSTEM_META_BUCKET, upload_path, f)
            except Exception as exc:  # noqa: BLE001
                errs[i] = exc

        with self._ns_lock.write(f"{SYSTEM_META_BUCKET}/{upload_path}"):
            list(_mp_pool.map(obs_carry(journal),
                              range(len(self.disks))))
        err = reduce_write_quorum_errs(errs, OBJECT_OP_IGNORED_ERRS, write_quorum)
        if err is not None:
            raise err
        return PartInfo(part_number=part_number, etag=etag, size=total,
                        actual_size=total, mod_time_ns=time.time_ns())

    def _journal_remove_part(self, upload_path: str, part_number: int,
                             write_quorum: int) -> None:
        """Best-effort removal of a part from every disk's upload journal
        (a failed re-upload left its shard files in a mixed state)."""

        def drop(i):
            if self.disks[i] is None:
                return
            try:
                f = self.disks[i].read_version(SYSTEM_META_BUCKET, upload_path)
                f.parts = [p for p in f.parts if p.number != part_number]
                f.metadata.pop(
                    f"x-mtpu-internal-part-etag-{part_number}", None
                )
                f.erasure.checksums = [
                    c for c in f.erasure.checksums
                    if c.part_number != part_number
                ]
                self.disks[i].write_metadata(
                    SYSTEM_META_BUCKET, upload_path, f
                )
            except Exception:  # noqa: BLE001 - best effort
                pass

        with self._ns_lock.write(f"{SYSTEM_META_BUCKET}/{upload_path}"):
            list(_mp_pool.map(obs_carry(drop),
                              range(len(self.disks))))

    def list_object_parts(self, bucket: str, object_: str, upload_id: str,
                          part_marker: int = 0, max_parts: int = 1000) -> list[PartInfo]:
        fi, _, _ = self._upload_fi(bucket, object_, upload_id)
        out = []
        for p in fi.parts:
            if p.number <= part_marker:
                continue
            out.append(PartInfo(
                part_number=p.number,
                etag=fi.metadata.get(f"x-mtpu-internal-part-etag-{p.number}", ""),
                size=p.size, actual_size=p.actual_size,
            ))
            if len(out) >= max_parts:
                break
        return out

    def list_multipart_uploads(self, bucket: str, prefix: str = "") -> list[MultipartInfo]:
        out = []
        seen = set()
        for disk in self.disks:
            if disk is None:
                continue
            try:
                for name, meta_blob in disk.walk_dir(SYSTEM_META_BUCKET, "multipart"):
                    if name in seen:
                        continue
                    seen.add(name)
                    from ..storage.xlmeta import XLMeta

                    fi = XLMeta.from_bytes(meta_blob).to_file_info(
                        SYSTEM_META_BUCKET, name, None
                    )
                    target = fi.metadata.get("x-mtpu-internal-object", "")
                    if "/" not in target:
                        continue
                    b, o = target.split("/", 1)
                    if b != bucket or (prefix and not o.startswith(prefix)):
                        continue
                    out.append(MultipartInfo(
                        bucket=b, object=o, upload_id=name.rsplit("/", 1)[-1],
                        user_defined=fi.metadata,
                    ))
            except Exception:  # noqa: BLE001
                continue
        return out

    def abort_multipart_upload(self, bucket: str, object_: str, upload_id: str):
        _, _, upload_path = self._upload_fi(bucket, object_, upload_id)

        def do(i):
            if self.disks[i] is None:
                return
            try:
                self.disks[i].delete(SYSTEM_META_BUCKET, upload_path, recursive=True)
            except Exception:  # noqa: BLE001
                pass

        list(_mp_pool.map(obs_carry(do),
                           range(len(self.disks))))

    def complete_multipart_upload(self, bucket: str, object_: str, upload_id: str,
                                  parts: list[CompletePart],
                                  opts: ObjectOptions | None = None) -> ObjectInfo:
        opts = opts or ObjectOptions()
        fi, fis, upload_path = self._upload_fi(bucket, object_, upload_id)
        k, m = fi.erasure.data_blocks, fi.erasure.parity_blocks
        write_quorum = k + (1 if k == m else 0)

        # Validate requested parts against the journal (ref :736-860):
        # part numbers must be strictly ascending and unique, like the
        # reference's sorted-parts check (ErrInvalidPartOrder).
        if not parts:
            raise ErrInvalidPart("no parts given")
        for a, b in zip(parts, parts[1:]):
            if b.part_number <= a.part_number:
                raise ErrInvalidPart(
                    f"part order invalid: {a.part_number} then {b.part_number}"
                )
        by_number = {p.number: p for p in fi.parts}
        md5s = []
        total_size = 0
        final_parts = []
        for cp in parts:
            jp = by_number.get(cp.part_number)
            want_etag = fi.metadata.get(
                f"x-mtpu-internal-part-etag-{cp.part_number}", ""
            )
            if jp is None or (cp.etag and cp.etag != want_etag):
                raise ErrInvalidPart(f"part {cp.part_number}")
            # All but the last part must meet the S3 minimum (5 MiB); we
            # keep the rule but relax it for tiny test parts when a single
            # part completes the object.
            md5s.append(bytes.fromhex(want_etag))
            total_size += jp.size
            final_parts.append(jp)

        from .types import compute_parts_etag

        etag = compute_parts_etag(md5s)
        mod_time_ns = time.time_ns()
        version_id = opts.version_id or (new_uuid() if opts.versioned else "")
        data_dir = new_uuid()

        metadata = {kk: v for kk, v in fi.metadata.items()
                    if not kk.startswith("x-mtpu-internal-")}
        metadata["etag"] = etag
        metadata.setdefault("content-type", "application/octet-stream")

        errs: list = [None] * len(self.disks)
        disks_by_shard = shuffle_disks(self.disks, fi.erasure.distribution)

        def commit(shard_i):
            disk = disks_by_shard[shard_i]
            if disk is None:
                raise ErrDiskNotFound(f"shard {shard_i}")
            f = FileInfo(
                volume=bucket, name=object_, version_id=version_id,
                data_dir=data_dir, mod_time_ns=mod_time_ns, size=total_size,
                metadata=dict(metadata),
                erasure=ErasureInfo(
                    algorithm=fi.erasure.algorithm,
                    data_blocks=k, parity_blocks=m,
                    block_size=fi.erasure.block_size, index=shard_i + 1,
                    distribution=list(fi.erasure.distribution),
                    checksums=[
                        ChecksumInfo(p.number, BitrotAlgorithm.HIGHWAYHASH256S.value)
                        for p in final_parts
                    ],
                    codec=fi.erasure.codec,
                ),
            )
            for p in final_parts:
                f.add_part(p.number, p.size, p.actual_size)
            try:
                # Remove the upload journal so only part files move.
                disk.delete(SYSTEM_META_BUCKET, f"{upload_path}/xl.meta")
            except Exception:  # noqa: BLE001
                pass
            disk.rename_data(SYSTEM_META_BUCKET, upload_path, f, bucket, object_)

        # The final rename_data fan-out commits the destination object's
        # xl.meta: hold the same per-object write lock as put_object so a
        # racing PutObject can't interleave into a mixed-mod-time quorum
        # (ref CompleteMultipartUpload NSLock, cmd/erasure-multipart.go:736).
        # Quorum-wait: the commit returns at write quorum + straggler
        # grace; a drive hung in rename_data is detached and its missed
        # shard heals via MRF.
        from .erasure_objects import _quorum_fanout

        with self._locked_write(bucket, object_):
            _quorum_fanout(commit, len(disks_by_shard), errs, write_quorum)
        err = reduce_write_quorum_errs(errs, OBJECT_OP_IGNORED_ERRS, write_quorum)
        if err is not None:
            raise err
        if any(e is not None for e in errs):
            # Partial commit (quorum met, stragglers/failures behind):
            # queue MRF so the missing shards are rebuilt (ref
            # addPartial, cmd/erasure-multipart.go).
            self.queue_mrf(bucket, object_, version_id)
        # Hot-tier hygiene: the multipart commit just replaced the
        # object's latest version (see _put_object_inner for the same
        # hook on the single-shot path).
        from . import readtier as _readtier

        _readtier.invalidate(bucket, object_)

        out = FileInfo(
            volume=bucket, name=object_, version_id=version_id,
            mod_time_ns=mod_time_ns, size=total_size, metadata=metadata,
            erasure=ErasureInfo(algorithm=fi.erasure.algorithm,
                                data_blocks=k, parity_blocks=m,
                                codec=fi.erasure.codec),
        )
        return ObjectInfo.from_file_info(out, bucket, object_, opts.versioned)

    # Default part size for the parallel driver: big enough that the
    # per-part journal/commit overhead amortizes, small enough that
    # even a modest object splits into several concurrently-hashed
    # parts (the whole point: per-part MD5s run in parallel, then
    # compose into the etag-of-parts — the sanctioned route around the
    # ~0.66 GB/s single-stream MD5 wall).
    PARALLEL_PART_SIZE = 16 << 20

    def put_object_multipart(self, bucket: str, object_: str, source,
                             size: int, part_size: int | None = None,
                             opts: ObjectOptions | None = None,
                             parallel: int | None = None) -> ObjectInfo:
        """Server-side parallel multipart PUT: slice `source` into
        parts and run their encode + bitrot-hash + MD5 CONCURRENTLY
        through the ordinary put_object_part path, completing with the
        standard S3 etag-of-parts. Every part is a full independent
        stream through the streaming drivers (its own TeeMD5Reader, its
        own admission slot), so with W admitted parts the content
        hashing runs W-wide — single-stream PUT can never do that
        without breaking the plain-md5 etag contract.

        `source` is consumed zero-copy when possible:
        - buffer-protocol objects (bytes/bytearray/memoryview/ndarray):
          parts are memoryview slices;
        - readers with a real file descriptor (`fileno()`): parts read
          via os.pread at their own offsets, no shared cursor;
        - anything else: parts are staged into part-sized buffers as
          the stream arrives (the stage copy is counted), submissions
          overlapping with the reads.

        On any part failure the upload is aborted — no journal or
        staged shards survive."""
        opts = opts or ObjectOptions()
        part_size = part_size or self.PARALLEL_PART_SIZE
        if size < 0:
            raise ErrInvalidPart("parallel multipart needs a sized source")
        # Never exceed the S3 part-count ceiling: grow the part size
        # instead (rounded up to 1 MiB so erasure blocks stay aligned).
        min_part = -(-size // MAX_PART_ID) if size else part_size
        if min_part > part_size:
            part_size = -(-min_part // (1 << 20)) * (1 << 20)
        n_parts = max(1, -(-size // part_size)) if size else 1
        parts_geom = [
            (i + 1, i * part_size, min(part_size, size - i * part_size))
            for i in range(n_parts)
        ]
        if size == 0:
            parts_geom = [(1, 0, 0)]

        upload_id = self.new_multipart_upload(bucket, object_, opts)
        window = threading.BoundedSemaphore(
            max(1, parallel if parallel is not None
                else min(8, os.cpu_count() or 1))
        )
        results: dict[int, PartInfo] = {}
        part_reader = _part_reader_factory(source)
        # Executor threads carry an EMPTY contextvar context: re-tag
        # each part with the caller's admission identity, or every
        # multipart part would pool into the anonymous client and
        # bypass the per-tenant caps/fairness. current_client() returns
        # the COMPOSED identity (key, or key\x1fbucket under
        # MTPU_ADMISSION_TENANT=bucket); with no bucket var set in the
        # executor thread it passes through verbatim, so parts keep the
        # caller's exact tenant.
        from ..pipeline.admission import client_context, current_client

        caller = current_client()

        def upload_part(num: int, reader, ln: int):
            try:
                with client_context(caller):
                    results[num] = self.put_object_part(
                        bucket, object_, upload_id, num, reader, ln
                    )
            finally:
                window.release()

        futures = []
        try:
            for num, off, ln in parts_geom:
                window.acquire()
                if any(f.done() and not f.cancelled() and f.exception()
                       for f in futures):
                    window.release()
                    break  # a part already failed: stop feeding
                # Readers are built HERE, in part order — staged
                # (cursor-only) sources depend on it; sliced/pread
                # sources don't care.
                reader = part_reader(off, ln)
                futures.append(_part_pool.submit(
                    obs_carry(upload_part),
                    num, reader, ln,
                ))
            errs = [f.exception() for f in futures]
            err = next((e for e in errs if e is not None), None)
            if err is not None:
                raise err
            if len(results) != len(parts_geom):
                raise ErrInvalidPart("parallel upload incomplete")
            return self.complete_multipart_upload(
                bucket, object_, upload_id,
                [CompletePart(num, results[num].etag)
                 for num, _, _ in parts_geom],
                opts,
            )
        except Exception:
            for f in futures:
                f.cancel()
            # Settle the in-flight parts before dropping the upload dir
            # under them, then abort (best effort — the stale-upload
            # sweeper catches anything a hung disk strands).
            for f in futures:
                if not f.cancelled():
                    f.exception()
            try:
                self.abort_multipart_upload(bucket, object_, upload_id)
            except Exception:  # noqa: BLE001 - best effort
                pass
            raise

    def cleanup_stale_uploads(self, expiry_ns: int):
        """Drop multipart uploads older than expiry
        (ref cleanupStaleUploads, cmd/erasure-multipart.go:100)."""
        now = time.time_ns()
        for mp in self.list_multipart_uploads_all():
            if now - mp[1] > expiry_ns:
                try:
                    self.abort_multipart_upload(*mp[0])
                except Exception:  # noqa: BLE001
                    pass

    def list_multipart_uploads_all(self):
        out = []
        for disk in self.disks:
            if disk is None:
                continue
            try:
                for name, meta_blob in disk.walk_dir(SYSTEM_META_BUCKET, "multipart"):
                    from ..storage.xlmeta import XLMeta

                    fi = XLMeta.from_bytes(meta_blob).to_file_info(
                        SYSTEM_META_BUCKET, name, None
                    )
                    target = fi.metadata.get("x-mtpu-internal-object", "")
                    if "/" not in target:
                        continue
                    b, o = target.split("/", 1)
                    out.append(((b, o, name.rsplit("/", 1)[-1]), fi.mod_time_ns))
                break
            except Exception:  # noqa: BLE001
                continue
        return out
