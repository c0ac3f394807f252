"""Object-layer metadata helpers: distribution order, parallel xl.meta
reads, quorum agreement, and shuffle-by-distribution.

Mirrors /root/reference/cmd/erasure-metadata-utils.go (hashOrder :101,
readAllFileInfo, shuffle helpers) and cmd/erasure-metadata.go
(findFileInfoInQuorum :235, objectQuorumFromMeta :318).
"""

from __future__ import annotations

import hashlib
import zlib
from concurrent.futures import ThreadPoolExecutor

from ..observability import carry as obs_carry
from ..storage.fileinfo import FileInfo
from ..utils.errors import (
    OBJECT_OP_IGNORED_ERRS,
    ErrDiskNotFound,
    ErrErasureReadQuorum,
    reduce_read_quorum_errs,
    reduce_write_quorum_errs,
)

_meta_pool = ThreadPoolExecutor(max_workers=64, thread_name_prefix="mtpu-meta")


def hash_order(key: str, cardinality: int) -> list[int]:
    """Consistent 1-based shard rotation for an object key
    (ref cmd/erasure-metadata-utils.go:101-115)."""
    if cardinality <= 0:
        return []
    key_crc = zlib.crc32(key.encode()) & 0xFFFFFFFF
    start = key_crc % cardinality
    return [1 + ((start + i) % cardinality) for i in range(1, cardinality + 1)]


def read_all_file_info(disks: list, bucket: str, object_: str,
                       version_id: str = "", read_data: bool = False):
    """Read xl.meta from every disk in parallel; returns (fis, errs) with
    None placeholders (ref readAllFileInfo)."""
    fis: list[FileInfo | None] = [None] * len(disks)
    errs: list = [None] * len(disks)

    def do(i):
        if disks[i] is None:
            errs[i] = ErrDiskNotFound(f"disk {i}")
            return
        try:
            fis[i] = disks[i].read_version(bucket, object_, version_id, read_data)
        except Exception as exc:  # noqa: BLE001 - collected for quorum
            errs[i] = exc

    from .erasure_objects import _fanout

    _fanout(do, len(disks))
    return fis, errs


def _meta_hash(fi: FileInfo) -> str:
    h = hashlib.sha256()
    for part in fi.parts:
        h.update(f"part.{part.number}".encode())
    h.update(str(fi.erasure.distribution).encode())
    # Codec identity is quorum-relevant: disks disagreeing on the codec
    # must never be merged into one readable version (their parity bytes
    # come from different matrices).
    h.update(fi.erasure.codec.encode())
    h.update(str(len(fi.data)).encode())
    return h.hexdigest()


def find_file_info_in_quorum(metas: list, mod_time_ns: int, data_dir: str,
                             quorum: int) -> FileInfo:
    """Pick the FileInfo agreed on by >= quorum disks
    (ref cmd/erasure-metadata.go:235-283)."""
    hashes = [None] * len(metas)
    for i, fi in enumerate(metas):
        if fi is not None and fi.mod_time_ns == mod_time_ns and fi.data_dir == data_dir:
            hashes[i] = _meta_hash(fi)
    counts: dict[str, int] = {}
    for h in hashes:
        if h:
            counts[h] = counts.get(h, 0) + 1
    max_hash, max_count = "", 0
    for h, c in counts.items():
        if c > max_count:
            max_hash, max_count = h, c
    if max_count < quorum:
        raise ErrErasureReadQuorum(f"meta quorum {max_count} < {quorum}")
    for i, h in enumerate(hashes):
        if h == max_hash:
            return metas[i]
    raise ErrErasureReadQuorum("no meta in quorum")


def common_mod_time(metas: list) -> tuple[int, str]:
    """(mod_time_ns, data_dir) occurring most often
    (ref commonTime/commonDataDir in cmd/erasure-healing-common.go)."""
    counts: dict[tuple[int, str], int] = {}
    for fi in metas:
        if fi is None:
            continue
        key = (fi.mod_time_ns, fi.data_dir)
        counts[key] = counts.get(key, 0) + 1
    if not counts:
        raise ErrErasureReadQuorum("no valid metadata")
    (mt, dd), _ = max(counts.items(), key=lambda kv: kv[1])
    return mt, dd


def object_quorum_from_meta(metas: list, errs: list,
                            default_parity: int) -> tuple[int, int]:
    """(read_quorum, write_quorum) for an existing object
    (ref cmd/erasure-metadata.go:318-338)."""
    valid_any = [fi for fi in metas if fi is not None]
    if not valid_any:
        err = reduce_read_quorum_errs(errs, OBJECT_OP_IGNORED_ERRS, 1)
        raise err if err else ErrErasureReadQuorum("no valid metadata")
    mt, dd = common_mod_time(metas)
    latest = next(
        (fi for fi in valid_any if fi.mod_time_ns == mt and fi.data_dir == dd),
        valid_any[0],
    )
    if latest.erasure.data_blocks <= 0:
        # Delete markers carry no erasure config; majority quorum applies
        # (the reference's delete-marker FileInfo has zero Erasure too).
        half = len(metas) // 2
        return half, half + 1
    data_blocks = latest.erasure.data_blocks
    parity = latest.erasure.parity_blocks or default_parity
    write_quorum = data_blocks
    if data_blocks == parity:
        write_quorum += 1
    return data_blocks, write_quorum


def shuffle_disks(disks: list, distribution: list[int]) -> list:
    """Order disks by shard index: result[shard] = disk holding shard+1
    (ref shuffleDisks, cmd/erasure-metadata-utils.go)."""
    if not distribution:
        return list(disks)
    shuffled = [None] * len(disks)
    for i, block_index in enumerate(distribution):
        shuffled[block_index - 1] = disks[i]
    return shuffled


def shuffle_disks_and_parts_metadata(disks: list, metas: list,
                                     fi: FileInfo) -> tuple[list, list]:
    """Order disks+metas into shard order, dropping entries whose metadata
    is inconsistent with fi (ref shuffleDisksAndPartsMetadataByIndex)."""
    distribution = fi.erasure.distribution
    shuffled_disks = [None] * len(disks)
    shuffled_metas: list = [None] * len(disks)
    for i, block_index in enumerate(distribution):
        if metas[i] is None:
            continue
        if metas[i].mod_time_ns != fi.mod_time_ns or metas[i].data_dir != fi.data_dir:
            continue
        shuffled_disks[block_index - 1] = disks[i]
        shuffled_metas[block_index - 1] = metas[i]
    return shuffled_disks, shuffled_metas


def write_unique_file_info(disks: list, bucket: str, prefix: str,
                           files: list, quorum: int) -> list:
    """Write per-disk xl.meta in parallel under write quorum; returns disks
    with failed entries nil'd (ref writeUniqueFileInfo,
    cmd/erasure-metadata.go:288-316)."""
    errs: list = [None] * len(disks)

    def do(i):
        if disks[i] is None:
            errs[i] = ErrDiskNotFound(f"disk {i}")
            return
        fi = files[i]
        fi.erasure.index = i + 1
        try:
            disks[i].write_metadata(bucket, prefix, fi)
        except Exception as exc:  # noqa: BLE001 - collected for quorum
            errs[i] = exc

    list(_meta_pool.map(obs_carry(do),
                        range(len(disks))))
    err = reduce_write_quorum_errs(errs, OBJECT_OP_IGNORED_ERRS, quorum)
    if err is not None:
        raise err
    return [d if errs[i] is None else None for i, d in enumerate(disks)]
