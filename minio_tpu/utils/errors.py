"""Canonical error types for the TPU-native object store.

Mirrors the error classes of the reference implementation
(/root/reference/cmd/typed-errors.go, cmd/storage-errors.go) so that quorum
reduction and heal-trigger semantics can be expressed identically, while
remaining idiomatic Python exceptions.
"""

from __future__ import annotations


class StorageError(Exception):
    """Base class for all storage-layer errors."""


class ErrDiskNotFound(StorageError):
    """Disk is offline / not found (ref: cmd/storage-errors.go errDiskNotFound)."""


class ErrDiskFaulty(ErrDiskNotFound):
    """Disk latched faulty by the health circuit breaker after repeated
    op timeouts (ref: errFaultyDisk, cmd/xl-storage-disk-id-check.go).
    Subclasses ErrDiskNotFound so every quorum reduction and fan-out
    path treats a faulty disk exactly like an offline one."""


class ErrDiskOpTimeout(ErrDiskFaulty):
    """One storage op exceeded its wall-clock deadline (ref: the per-op
    context deadlines of diskHealthTracker). The op may still complete
    in the background; the caller must treat the disk as failed for
    this op and let MRF/heal repair any missed write."""


class ErrFileNotFound(StorageError):
    """File not found on disk (ref: errFileNotFound) — triggers missing-part heal."""


class ErrFileVersionNotFound(StorageError):
    """Requested version not found (ref: errFileVersionNotFound)."""


class ErrFileCorrupt(StorageError):
    """Bitrot verification failed (ref: errFileCorrupt) — triggers bitrot heal."""


class ErrFileAccessDenied(StorageError):
    """Access denied on the path (ref: errFileAccessDenied)."""


class ErrVolumeNotFound(StorageError):
    """Volume (bucket dir) not found (ref: errVolumeNotFound)."""


class ErrVolumeExists(StorageError):
    """Volume already exists (ref: errVolumeExists)."""


class ErrVolumeNotEmpty(StorageError):
    """Volume not empty on delete (ref: errVolumeNotEmpty)."""


class ErrDiskFull(StorageError):
    """No space left (ref: errDiskFull)."""


class ErrCorruptedFormat(StorageError):
    """format.json unusable (ref: errCorruptedFormat)."""


class ErrUnformattedDisk(StorageError):
    """Fresh disk without format.json (ref: errUnformattedDisk)."""


class ErrErasureReadQuorum(StorageError):
    """Read quorum unavailable (ref: errErasureReadQuorum)."""


class ErrErasureWriteQuorum(StorageError):
    """Write quorum unavailable (ref: errErasureWriteQuorum)."""


class ErrLessData(StorageError):
    """Fewer bytes available than requested (ref: errLessData)."""


class ErrMoreData(StorageError):
    """More data was sent than advertised (ref: errMoreData)."""


class ErrInvalidArgument(StorageError):
    """Invalid arguments provided (ref: errInvalidArgument)."""


class ErrMethodNotAllowed(StorageError):
    """Operation not allowed (ref: errMethodNotAllowed)."""


class ErrObjectNotFound(StorageError):
    """Object does not exist (ref: cmd/object-api-errors.go ObjectNotFound)."""


class ErrVersionNotFound(StorageError):
    """Object version does not exist (ref: VersionNotFound)."""


class ErrBucketNotFound(StorageError):
    """Bucket does not exist (ref: BucketNotFound)."""


class ErrBucketExists(StorageError):
    """Bucket already owned/exists (ref: BucketAlreadyOwnedByYou)."""


class ErrBucketNotEmpty(StorageError):
    """Bucket not empty (ref: BucketNotEmpty)."""


class ErrInvalidUploadID(StorageError):
    """Multipart upload id not found (ref: InvalidUploadID)."""


class ErrInvalidPart(StorageError):
    """Multipart part missing/mismatched etag (ref: InvalidPart)."""


class ErrObjectExistsAsDirectory(StorageError):
    """Object name collides with a directory prefix (ref: ObjectExistsAsDirectory)."""


class ErrBadDigest(StorageError):
    """Content digest mismatch detected before commit (ref: hash.Reader
    SHA256/MD5 mismatch, /root/reference/pkg/hash/reader.go)."""


class ErrQuotaExceeded(StorageError):
    """Hard bucket quota would be exceeded (ref: BucketQuotaExceeded,
    cmd/bucket-quota.go:check)."""


class ErrRemoteTier(StorageError):
    """Remote tier unreachable / remote blob missing (ref the tiering
    error paths in cmd/bucket-lifecycle.go) — retriable 503."""


class ErrPreconditionFailed(StorageError):
    """The object changed between the caller's metadata fetch and the
    locked data read (expected_etag mismatch): retriable race loss."""


class ErrOperationTimedOut(StorageError):
    """Namespace-lock acquisition timed out (ref: OperationTimedOut,
    cmd/typed-errors.go) — surfaces as a retriable 503 instead of a
    permanently wedged request."""


# --- Reed-Solomon codec errors (mirror klauspost/reedsolomon, used by
# --- cmd/erasure-coding.go:44-48) ---

class RSError(Exception):
    """Base class for Reed-Solomon codec errors."""


class ErrInvShardNum(RSError):
    """data/parity shard count <= 0."""


class ErrMaxShardNum(RSError):
    """data+parity > 256 shards."""


class ErrShortData(RSError):
    """Not enough data to fill the requested shards."""


class ErrTooFewShards(RSError):
    """Too few shards present to reconstruct."""


class ErrShardSize(RSError):
    """Shards are not identically sized."""


class ErrReconstructRequired(RSError):
    """A data shard is missing; reconstruction needed before join."""


# Errors ignored during per-disk error reduction; the reference treats these
# as "the disk is fine, the object simply isn't there"
# (ref: cmd/object-api-utils.go objectOpIgnoredErrs = baseIgnoredErrs +
#  errDiskAccessDenied + errUnformattedDisk).
OBJECT_OP_IGNORED_ERRS = (
    ErrDiskNotFound,
    ErrUnformattedDisk,
)


def count_errs(errs, match: type | None) -> int:
    """Count occurrences of error class `match` (None counts successes).

    Ref: cmd/erasure-metadata-utils.go:25-37 countErrs.
    """
    n = 0
    for e in errs:
        if match is None:
            n += e is None
        else:
            n += isinstance(e, match)
    return n


def reduce_errs(errs, ignored_errs=()):
    """Return (count, err) for the maximally-occurring outcome (None =
    success counts too); ignored error types are skipped entirely, and
    ties prefer success. Mirrors reduceErrs,
    cmd/erasure-metadata-utils.go:36-58.
    """
    counts: dict[object, int] = {}
    keys: dict[object, object] = {}
    ignored = tuple(ignored_errs)

    for e in errs:
        if e is not None and ignored and isinstance(e, ignored):
            continue
        k = None if e is None else type(e)
        counts[k] = counts.get(k, 0) + 1
        keys.setdefault(k, e)

    max_k, max_n = None, 0
    for k, n in counts.items():
        if n > max_n:
            max_k, max_n = k, n
        elif n == max_n and k is None:
            # Prefer nil over errors with the same count.
            max_k = k
    return max_n, keys.get(max_k)


def reduce_quorum_errs(errs, ignored_errs, quorum: int, quorum_err: StorageError):
    """Return None if the max-occurring outcome reaches quorum, else an error.

    Ref: cmd/erasure-metadata-utils.go:73-99 reduceQuorumErrs.
    """
    max_count, max_err = reduce_errs(errs, ignored_errs)
    if max_count >= quorum:
        return max_err
    return quorum_err


def reduce_read_quorum_errs(errs, ignored_errs, read_quorum: int):
    """Ref: cmd/erasure-metadata-utils.go:73-78 reduceReadQuorumErrs."""
    return reduce_quorum_errs(errs, ignored_errs, read_quorum, ErrErasureReadQuorum())


def reduce_write_quorum_errs(errs, ignored_errs, write_quorum: int):
    """Ref: cmd/erasure-metadata-utils.go:81-86 reduceWriteQuorumErrs."""
    return reduce_quorum_errs(errs, ignored_errs, write_quorum, ErrErasureWriteQuorum())
