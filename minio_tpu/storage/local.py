"""Local disk implementation of StorageAPI — the equivalent of the
reference's xlStorage (/root/reference/cmd/xl-storage.go).

On-disk layout per disk root (mirrors the reference's):

    <root>/<volume>/<object...>/xl.meta          version journal
    <root>/<volume>/<object...>/<dataDir>/part.N shard data (bitrot-framed)
    <root>/.mtpu.sys/tmp/<uuid>                  staged writes
    <root>/.mtpu.sys/format.json                 disk identity/format

Writes are staged under tmp and committed with atomic rename
(RenameData, ref cmd/xl-storage.go:1825); small objects inline their
shard bytes in xl.meta instead of a part file (smallFileThreshold 128 KiB,
ref cmd/xl-storage.go:66). Python's file IO replaces the reference's
O_DIRECT/fdatasync tuning; durability points (fsync before rename-commit)
are preserved behind the `fsync` flag.
"""

from __future__ import annotations

import contextlib
import io
import os
import shutil
import threading
import time

from ..observability import ioflow
from ..utils.errors import (
    ErrDiskNotFound,
    ErrFileAccessDenied,
    ErrFileCorrupt,
    ErrFileNotFound,
    ErrInvalidArgument,
    ErrVolumeExists,
    ErrVolumeNotEmpty,
    ErrVolumeNotFound,
)
from ..erasure.bitrot import BitrotAlgorithm, bitrot_shard_file_size, bitrot_verify
from .fileinfo import FileInfo
from .interface import DiskInfo, FileInfoVersions, StorageAPI, VolInfo
from .xlmeta import XLMeta

# Reserved system volume (reference: .minio.sys, cmd/object-api-utils.go).
SYSTEM_META_BUCKET = ".mtpu.sys"
SYSTEM_TMP = SYSTEM_META_BUCKET + "/tmp"
SYSTEM_MULTIPART = SYSTEM_META_BUCKET + "/multipart"
XL_META_FILE = "xl.meta"

# The ops that take their object path's metadata lock
# (`LocalStorage._path_lock`).
_LOCKED_OPS = ("rename_data", "write_metadata", "update_metadata",
               "delete_version")

# How many times an object directory's making and the move or write into
# it are tried when a delete of another path's last object removes the
# directory or a parent in between (reliableMkdirAll / reliableRename,
# ref cmd/os-reliable.go).
_DIR_TRIES = 8

# Shard files at or below this size are inlined into xl.meta
# (smallFileThreshold, ref cmd/xl-storage.go:66): a small PUT becomes
# ONE metadata write per disk instead of shard-write + rename-commit.
SMALL_FILE_THRESHOLD = 128 << 10


def small_file_threshold() -> int:
    """Effective inline threshold: MTPU_INLINE_THRESHOLD (bytes; 0
    disables inlining) read at call time so operators and tests can
    retune a live process; falls back to the module default (which
    tests may monkeypatch directly)."""
    env = os.environ.get("MTPU_INLINE_THRESHOLD", "")
    if env:
        try:
            return int(env)
        except ValueError:
            pass
    return SMALL_FILE_THRESHOLD


def _check_path(p: str):
    if p.startswith("/") or ".." in p.split("/"):
        raise ErrInvalidArgument(f"unsafe path {p!r}")


class LocalStorage(StorageAPI):
    """POSIX StorageAPI over one directory tree ("disk")."""

    def __init__(self, root: str, endpoint: str = "", fsync: bool = False,
                 metrics=None):
        self.root = os.path.abspath(root)
        self._endpoint = endpoint or self.root
        self._fsync = fsync
        self._disk_id = ""
        # One metadata lock an object path, taken through `_path_lock`,
        # which counts the waits for it into `metrics` (the registry the
        # drive guard raises disk_ops_total with); their series stand
        # at 0. An entry lives while some op holds or waits for it.
        self._paths_mu = threading.Lock()
        self._path_locks: dict[str, list] = {}  # dir -> [Lock, users]
        self._metrics = metrics
        if metrics is not None:
            for op in _LOCKED_OPS:
                metrics.inc("drive_lock_wait_seconds_total", 0.0, op=op)
                metrics.inc("drive_lock_waits_total", 0.0, op=op)
        self._online = True
        os.makedirs(os.path.join(self.root, *SYSTEM_TMP.split("/")), exist_ok=True)
        # O_DIRECT shard writes (ref cmd/xl-storage.go:1089 + fallocate):
        # opt-in (MTPU_ODIRECT=1) and probed per disk root — tmpfs and
        # other cache-only filesystems fall back to buffered writes.
        self._odirect = False
        if os.environ.get("MTPU_ODIRECT", "0") == "1":
            from .directio import supports_odirect

            self._odirect = supports_odirect(self.root)

    # --- helpers ---

    @contextlib.contextmanager
    def _path_lock(self, op: str, volume: str, path: str):
        """Hold the metadata lock of `volume`/`path` on this drive for
        `op`: ops on one object path exclude each other, ops on other
        paths do not wait. Free, it is one non-blocking acquire and
        nothing recorded; held by another thread, the blocking acquire
        is timed into drive_lock_wait_seconds_total{op} and
        drive_lock_waits_total{op}: counters and not spans, since a
        remote drive's ops run on the storage plane's threads, where no
        trace is active."""
        key = self._file_path(volume, path)
        with self._paths_mu:
            entry = self._path_locks.get(key)
            if entry is None:
                entry = self._path_locks[key] = [threading.Lock(), 0]
            entry[1] += 1
        lock = entry[0]
        try:
            # lock-ok: one object path's lock, released below: what it
            # makes atomic is that path's data-dir move and xl.meta merge
            if not lock.acquire(blocking=False):
                t0 = time.monotonic_ns()
                lock.acquire()  # lock-ok: as above
                metrics = self._metrics
                if metrics is not None:
                    metrics.inc("drive_lock_wait_seconds_total",
                                (time.monotonic_ns() - t0) / 1e9, op=op)
                    metrics.inc("drive_lock_waits_total", op=op)
            try:
                yield
            finally:
                lock.release()
        finally:
            with self._paths_mu:
                entry[1] -= 1
                if not entry[1]:
                    del self._path_locks[key]

    @staticmethod
    def _into_dir(dir_path: str, act) -> bool:
        """Make the directory `dir_path` (one `mkdir`; its parents only
        where they lack), then `act()` into it; True where `dir_path`
        was not there, so that it holds no journal of this path (the
        caller holds the path's lock). A delete of another path's last
        object removes the directories it leaves empty under that
        path's lock alone: where it removes `dir_path` or a parent
        between the making and `act`, `act` raises FileNotFoundError
        and both are tried again, up to `_DIR_TRIES` times
        (reliableMkdirAll / reliableRename, ref cmd/os-reliable.go)."""
        tries = 1
        while True:
            try:
                try:
                    os.mkdir(dir_path)
                    made = True
                except FileExistsError:
                    made = False
                except FileNotFoundError:
                    os.makedirs(dir_path, exist_ok=True)
                    made = True
                act()
                return made
            except FileNotFoundError:
                if tries == _DIR_TRIES:
                    raise
                tries += 1

    def _vol_path(self, volume: str) -> str:
        _check_path(volume)
        return os.path.join(self.root, volume)

    def _file_path(self, volume: str, path: str) -> str:
        _check_path(path)
        return os.path.join(self._vol_path(volume), *path.split("/"))

    def _require_online(self):
        if not self._online:
            raise ErrDiskNotFound(self._endpoint)

    def set_online(self, online: bool):
        """Test/fault-injection hook (stands in for network disconnect)."""
        self._online = online

    # --- identity ---

    def ping(self) -> None:
        """Liveness probe for the disk monitor: online flag + the root
        directory still being there (a pulled mount raises)."""
        self._require_online()
        os.stat(self.root)

    def is_online(self) -> bool:
        return self._online

    def is_local(self) -> bool:
        return True

    def hostname(self) -> str:
        return ""

    def endpoint(self) -> str:
        return self._endpoint

    def get_disk_id(self) -> str:
        self._require_online()
        return self._disk_id

    def set_disk_id(self, disk_id: str) -> None:
        self._disk_id = disk_id

    def disk_info(self) -> DiskInfo:
        self._require_online()
        st = shutil.disk_usage(self.root)
        return DiskInfo(
            total=st.total, free=st.free, used=st.used,
            endpoint=self._endpoint, mount_path=self.root, id=self._disk_id,
        )

    def drive_perf(self, size_bytes: int = 4 << 20,
                   io_bytes: int = 1 << 20) -> dict:
        """Size-bounded sequential read/write probe of this drive — the
        madmin.DrivePerfInfo analog the OBD health bundle embeds
        (ref /root/reference/cmd/healthinfo.go:66-90): GB/s plus per-op
        latency for `size_bytes` of `io_bytes` IOs against a tmp file
        on THIS filesystem. O_DIRECT when the filesystem accepts it
        (the honest number — no page cache); otherwise buffered with an
        fsync folded into the write time and a posix_fadvise(DONTNEED)
        before the read pass, reported as direct=False so operators
        know the read figure may include cache."""
        import mmap
        import statistics as _stats

        self._require_online()
        size_bytes = max(io_bytes, min(size_bytes, 64 << 20))
        n_ops = size_bytes // io_bytes
        path = os.path.join(
            self.root, *SYSTEM_TMP.split("/"),
            f"drive-perf-{os.getpid()}-{time.monotonic_ns()}",
        )
        # mmap allocations are page-aligned, satisfying O_DIRECT's
        # buffer alignment; the buffer must be entropy END TO END — a
        # partially-zero block hands compressing/zero-detecting storage
        # (lz4 ZFS, VDO, thin SANs) a severalfold flattering write rate.
        buf = mmap.mmap(-1, io_bytes)
        buf[:] = os.urandom(io_bytes)
        direct = True
        try:
            try:
                fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC
                             | os.O_DIRECT, 0o600)
            except OSError:
                direct = False
                fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC,
                             0o600)
            w_lat: list[float] = []
            mv = memoryview(buf)
            t_w0 = time.perf_counter()
            try:
                for _ in range(n_ops):
                    t0 = time.perf_counter()
                    # Short-write resume: GB/s computed from n_ops *
                    # io_bytes must count only bytes that actually
                    # landed (a near-full disk otherwise inflates the
                    # figure silently; ENOSPC/EFBIG raise instead).
                    off = 0
                    while off < io_bytes:
                        off += os.write(fd, mv[off:])
                    w_lat.append(time.perf_counter() - t0)
                if not direct:
                    os.fsync(fd)
            finally:
                t_write = time.perf_counter() - t_w0
                mv.release()  # an exported view would break buf.close()
                os.close(fd)
            try:
                fd = os.open(path, os.O_RDONLY
                             | (os.O_DIRECT if direct else 0))
            except OSError:
                direct = False
                fd = os.open(path, os.O_RDONLY)
            r_lat: list[float] = []
            read_bytes = 0
            t_r0 = time.perf_counter()
            try:
                if not direct:
                    try:  # drop what the write pass cached
                        os.posix_fadvise(fd, 0, 0,
                                         os.POSIX_FADV_DONTNEED)
                    except OSError:
                        pass
                for _ in range(n_ops):
                    t0 = time.perf_counter()
                    got = os.readv(fd, [buf])
                    r_lat.append(time.perf_counter() - t0)
                    read_bytes += got
                    if got < io_bytes:
                        break
            finally:
                t_read = time.perf_counter() - t_r0
                os.close(fd)
        finally:
            buf.close()
            try:
                os.unlink(path)
            except OSError:
                pass
        # GB/s over the bytes actually moved: n_ops*io_bytes can be
        # less than the requested size (io_bytes not dividing it), and
        # a short read ends the read pass early — dividing the nominal
        # probe size by the elapsed time would overstate throughput.
        wrote_bytes = n_ops * io_bytes
        return {
            "direct": direct,
            "probe_bytes": wrote_bytes,
            "io_bytes": io_bytes,
            "write_gbps": round(wrote_bytes / t_write / 1e9, 3),
            "write_lat_us": round(_stats.median(w_lat) * 1e6),
            "read_gbps": round(read_bytes / t_read / 1e9, 3),
            "read_lat_us": round(_stats.median(r_lat) * 1e6),
        }

    # --- volumes ---

    def make_vol(self, volume: str) -> None:
        self._require_online()
        p = self._vol_path(volume)
        if os.path.isdir(p):
            raise ErrVolumeExists(volume)
        os.makedirs(p, exist_ok=True)

    def make_vol_bulk(self, *volumes: str) -> None:
        for v in volumes:
            try:
                self.make_vol(v)
            except ErrVolumeExists:
                pass

    def list_vols(self) -> list[VolInfo]:
        self._require_online()
        out = []
        for name in sorted(os.listdir(self.root)):
            p = os.path.join(self.root, name)
            if os.path.isdir(p):
                out.append(VolInfo(name=name, created_ns=int(os.stat(p).st_ctime_ns)))
        return out

    def stat_vol(self, volume: str) -> VolInfo:
        self._require_online()
        p = self._vol_path(volume)
        if not os.path.isdir(p):
            raise ErrVolumeNotFound(volume)
        return VolInfo(name=volume, created_ns=int(os.stat(p).st_ctime_ns))

    def delete_vol(self, volume: str, force_delete: bool = False) -> None:
        self._require_online()
        p = self._vol_path(volume)
        if not os.path.isdir(p):
            raise ErrVolumeNotFound(volume)
        if force_delete:
            shutil.rmtree(p)
            return
        try:
            os.rmdir(p)
        except OSError as exc:
            raise ErrVolumeNotEmpty(volume) from exc

    def purge_stale_tmp(self) -> int:
        """Boot-time crash recovery (ref formatErasureCleanupTmp,
        cmd/format-erasure.go): drop every staged write under
        <root>/.mtpu.sys/tmp. Every entry there is a PUT/heal staging
        dir whose owner died before its rename-commit — by the time a
        boot path calls this, no writer can still own one. Multipart
        uploads stage under .mtpu.sys/multipart and are NOT touched
        (they resume across restarts). Returns entries purged."""
        base = os.path.join(self._vol_path(SYSTEM_META_BUCKET), "tmp")
        if not os.path.isdir(base):
            return 0
        purged = 0
        for name in os.listdir(base):
            full = os.path.join(base, name)
            try:
                if os.path.isdir(full):
                    shutil.rmtree(full)
                else:
                    os.remove(full)
                purged += 1
            except OSError:
                continue  # raced cleanup / permissions: leave for next boot
        return purged

    # --- listing ---

    def list_dir(self, volume: str, dir_path: str, count: int = -1) -> list[str]:
        self._require_online()
        p = self._file_path(volume, dir_path) if dir_path else self._vol_path(volume)
        if not os.path.isdir(self._vol_path(volume)):
            raise ErrVolumeNotFound(volume)
        if not os.path.isdir(p):
            raise ErrFileNotFound(dir_path)
        entries = []
        for name in sorted(os.listdir(p)):
            full = os.path.join(p, name)
            entries.append(name + "/" if os.path.isdir(full) else name)
            if 0 < count <= len(entries):
                break
        return entries

    def walk_dir(self, volume: str, base_dir: str = "", recursive: bool = True,
                 report_notfound: bool = False, forward_to: str = ""):
        """Yield (object_path, xl_meta_bytes) sorted lexically — the local
        producer behind metacache listing (ref cmd/metacache-walk.go:333).
        Directories containing xl.meta are objects; others recurse."""
        self._require_online()
        vol = self._vol_path(volume)
        if not os.path.isdir(vol):
            raise ErrVolumeNotFound(volume)

        def walk(rel: str):
            p = os.path.join(vol, *rel.split("/")) if rel else vol
            try:
                names = sorted(os.listdir(p))
            except FileNotFoundError:
                return
            if XL_META_FILE in names:
                with open(os.path.join(p, XL_META_FILE), "rb") as f:
                    raw = f.read()
                ioflow.account(self._endpoint, "rmeta", len(raw))
                yield rel, raw
                return
            if "xl.json" in names:
                # Legacy v1 object: surface it to listings/scanner/heal
                # as a CONVERTED modern journal so consumers need no
                # legacy awareness.
                from .xlmeta_v1 import legacy_to_xlmeta

                try:
                    with open(os.path.join(p, "xl.json"), "rb") as f:
                        meta = legacy_to_xlmeta(f.read(), volume, rel)
                    yield rel, meta.to_bytes()
                except Exception:  # noqa: BLE001 - unreadable legacy doc
                    pass
                return
            for name in names:
                child = f"{rel}/{name}" if rel else name
                if os.path.isdir(os.path.join(p, name)):
                    if recursive:
                        yield from walk(child)
                    else:
                        yield child + "/", b""

        start = base_dir.strip("/")
        for item in walk(start):
            if forward_to and item[0] < forward_to:
                continue
            yield item

    # --- metadata ---

    def _read_meta(self, volume: str, path: str) -> XLMeta:
        meta_path = os.path.join(self._file_path(volume, path), XL_META_FILE)
        try:
            with open(meta_path, "rb") as f:
                raw = f.read()
            ioflow.account(self._endpoint, "rmeta", len(raw))
            return XLMeta.from_bytes(raw)
        except FileNotFoundError:
            # Legacy object (pre-2020 reference deployments migrated in
            # place): fall back to the v1 xl.json document
            # (ref cmd/xl-storage-format-v1.go readers).
            from .xlmeta_v1 import XL_JSON_FILE, legacy_to_xlmeta

            legacy = os.path.join(
                self._file_path(volume, path), XL_JSON_FILE
            )
            try:
                with open(legacy, "rb") as f:
                    raw = f.read()
            except FileNotFoundError:
                if not os.path.isdir(self._vol_path(volume)):
                    raise ErrVolumeNotFound(volume) from None
                raise ErrFileNotFound(f"{volume}/{path}") from None
            ioflow.account(self._endpoint, "rmeta", len(raw))
            return legacy_to_xlmeta(raw, volume, path)

    def _write_meta(self, volume: str, path: str, meta: XLMeta):
        self._write_meta_blob(volume, path, meta.to_bytes())

    def _write_meta_blob(self, volume: str, path: str, blob: bytes):
        """Write `blob` as the object's xl.meta through a tmp file and a
        rename, in three syscalls where `open` would take five."""
        obj_dir = self._file_path(volume, path)

        def write():
            tmp = os.path.join(
                obj_dir, f".xl.meta.tmp.{os.getpid()}.{time.monotonic_ns()}")
            fd = os.open(tmp, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o666)
            try:
                view = memoryview(blob)
                while view:
                    view = view[os.write(fd, view):]
                if self._fsync:
                    os.fsync(fd)
            finally:
                os.close(fd)
            os.replace(tmp, os.path.join(obj_dir, XL_META_FILE))

        self._into_dir(obj_dir, write)
        ioflow.account(self._endpoint, "wmeta", len(blob))

    def _fresh_meta_blob(self, volume: str, path: str, fi: FileInfo,
                         new_dir: bool = False) -> bytes | None:
        """Pre-serialized journal from the PUT's shared fan-out pack
        (xlmeta.FanoutMetaPack), usable only when this disk holds NO
        existing journal to merge with (xl.meta or legacy xl.json);
        `new_dir` says the caller has just made the object's directory,
        which then holds none."""
        pack = getattr(fi, "fanout_pack", None)
        if pack is None:
            return None
        if new_dir:
            return pack.bytes_for(fi)
        if not os.path.isdir(self._vol_path(volume)):
            return None  # slow path raises ErrVolumeNotFound as before
        obj_dir = self._file_path(volume, path)
        if os.path.exists(os.path.join(obj_dir, XL_META_FILE)):
            return None
        from .xlmeta_v1 import XL_JSON_FILE

        if os.path.exists(os.path.join(obj_dir, XL_JSON_FILE)):
            return None
        return pack.bytes_for(fi)

    def write_metadata(self, volume: str, path: str, fi: FileInfo) -> None:
        self._require_online()
        with self._path_lock("write_metadata", volume, path):
            blob = self._fresh_meta_blob(volume, path, fi)
            if blob is not None:
                self._write_meta_blob(volume, path, blob)
                return
            try:
                meta = self._read_meta(volume, path)
            except ErrFileNotFound:
                meta = XLMeta()
            meta.add_version(fi)
            self._write_meta(volume, path, meta)

    def update_metadata(self, volume: str, path: str, fi: FileInfo) -> None:
        self._require_online()
        with self._path_lock("update_metadata", volume, path):
            meta = self._read_meta(volume, path)
            meta.find_version(fi.version_id)  # must exist
            meta.add_version(fi)
            self._write_meta(volume, path, meta)

    def read_version(self, volume: str, path: str, version_id: str = "",
                     read_data: bool = False) -> FileInfo:
        self._require_online()
        meta = self._read_meta(volume, path)
        fi = meta.to_file_info(volume, path, version_id)
        if not read_data:
            fi.data = {}
        return fi

    def list_versions(self, volume: str, path: str) -> FileInfoVersions:
        self._require_online()
        meta = self._read_meta(volume, path)
        out = FileInfoVersions(volume=volume, name=path)
        for v in meta.versions:
            out.versions.append(meta.to_file_info(volume, path, v["vid"]))
        return out

    def delete_version(self, volume: str, path: str, fi: FileInfo,
                       force_del_marker: bool = False) -> None:
        """Remove one version; drop xl.meta + dirs when journal empties
        (ref cmd/xl-storage.go DeleteVersion)."""
        self._require_online()
        with self._path_lock("delete_version", volume, path):
            meta = self._read_meta(volume, path)
            data_dir = meta.delete_version(fi)
            if data_dir:
                shutil.rmtree(
                    os.path.join(self._file_path(volume, path), data_dir),
                    ignore_errors=True,
                )
            if meta.versions:
                self._write_meta(volume, path, meta)
            else:
                # Journal empty: NOTHING under the object dir is valid
                # anymore — including a legacy xl.json and its bare
                # part.N files (data_dir="" means no per-version dir to
                # rmtree above). Removing only xl.meta would resurrect
                # legacy objects via the fallback reader.
                obj_dir = self._file_path(volume, path)
                shutil.rmtree(obj_dir, ignore_errors=True)
                self._cleanup_empty_dirs(volume, path)

    def delete_versions(self, volume: str, versions: list[FileInfo]) -> list:
        errs = []
        for fi in versions:
            try:
                self.delete_version(volume, fi.name, fi)
                errs.append(None)
            except Exception as exc:  # noqa: BLE001 - collected per-version
                errs.append(exc)
        return errs

    def _cleanup_empty_dirs(self, volume: str, path: str):
        vol = self._vol_path(volume)
        cur = self._file_path(volume, path)
        while cur != vol and cur.startswith(vol):
            try:
                os.rmdir(cur)
            except FileNotFoundError:
                pass  # already removed (e.g. rmtree'd object dir)
            except OSError:
                break  # non-empty: stop climbing
            cur = os.path.dirname(cur)

    def rename_data(self, src_volume: str, src_path: str, fi: FileInfo,
                    dst_volume: str, dst_path: str) -> None:
        """Atomic commit: move staged data dir into place and journal the
        version (ref cmd/xl-storage.go:1825 RenameData)."""
        self._require_online()
        # The object path's metadata lock: the rename and the merge into
        # its xl.meta are atomic per object path on this drive; commits
        # of other objects to the drive do not wait for it.
        with self._path_lock("rename_data", dst_volume, dst_path):
            made = False
            if fi.data_dir:
                src_data = self._file_path(src_volume, src_path)
                if not os.path.isdir(src_data):
                    raise ErrFileNotFound(f"{src_volume}/{src_path}")
                dst_data = os.path.join(
                    self._file_path(dst_volume, dst_path), fi.data_dir)

                def move():
                    try:
                        os.replace(src_data, dst_data)
                    except FileNotFoundError:
                        raise  # the object's directory went: made again
                    except OSError:
                        # a data dir of this id is there (a heal's
                        # re-commit): the staged one takes its place whole
                        if not os.path.isdir(dst_data):
                            raise
                        shutil.rmtree(dst_data)
                        os.replace(src_data, dst_data)

                made = self._into_dir(
                    self._file_path(dst_volume, dst_path), move)
            blob = self._fresh_meta_blob(dst_volume, dst_path, fi, made)
            if blob is not None:
                self._write_meta_blob(dst_volume, dst_path, blob)
                return
            try:
                meta = self._read_meta(dst_volume, dst_path)
            except ErrFileNotFound:
                meta = XLMeta()
            meta.add_version(fi)
            self._write_meta(dst_volume, dst_path, meta)

    # --- files ---

    def read_file(self, volume: str, path: str, offset: int, length: int) -> bytes:
        self._require_online()
        try:
            with open(self._file_path(volume, path), "rb") as f:
                f.seek(offset)
                buf = f.read(length)
        except FileNotFoundError:
            raise ErrFileNotFound(f"{volume}/{path}") from None
        except IsADirectoryError:
            raise ErrFileAccessDenied(f"{volume}/{path}") from None
        if len(buf) != length:
            raise ErrFileCorrupt(f"short read {volume}/{path}")
        ioflow.account(self._endpoint, "read", len(buf))
        return buf

    def read_repair_symbol(self, volume: str, path: str, *, stride: int,
                           digest_size: int, alpha: int, subs: list[int],
                           blocks: list[tuple[int, int]]) -> bytes:
        """Single-open variant of the StorageAPI default: one file handle
        and a seek per β-slice instead of an open per read_file call.
        Error mapping and per-byte ledger accounting mirror read_file."""
        self._require_online()
        out = bytearray()
        try:
            with open(self._file_path(volume, path), "rb") as f:
                for block, chunk_len in blocks:
                    if chunk_len % alpha:
                        raise ValueError(
                            f"repair chunk {chunk_len} not divisible "
                            f"by alpha {alpha}"
                        )
                    sub_len = chunk_len // alpha
                    base = block * stride + digest_size
                    for sub in subs:
                        f.seek(base + sub * sub_len)
                        buf = f.read(sub_len)
                        if len(buf) != sub_len:
                            raise ErrFileCorrupt(
                                f"short repair read {volume}/{path}"
                            )
                        out += buf
        except FileNotFoundError:
            raise ErrFileNotFound(f"{volume}/{path}") from None
        except IsADirectoryError:
            raise ErrFileAccessDenied(f"{volume}/{path}") from None
        ioflow.account(self._endpoint, "read", len(out))
        from ..pipeline.buffers import copy_add

        copy_add("repair.symbol_join", len(out))
        return bytes(out)  # copy-ok: repair.symbol_join

    def append_file(self, volume: str, path: str, buf: bytes) -> None:
        self._require_online()
        if not os.path.isdir(self._vol_path(volume)):
            raise ErrVolumeNotFound(volume)
        p = self._file_path(volume, path)
        os.makedirs(os.path.dirname(p), exist_ok=True)
        with open(p, "ab") as f:
            f.write(buf)
            if self._fsync:
                f.flush()
                os.fsync(f.fileno())
        ioflow.account(self._endpoint, "write", len(buf))

    def create_file(self, volume: str, path: str, size: int, reader) -> None:
        """Stream-write a file of `size` bytes (-1 = unknown), ref
        cmd/xl-storage.go:1487 CreateFile. Routes through
        create_file_writer so the storage-REST plane's writes (this is
        the server side of remote CreateFile, which always carries the
        exact length) get the same O_DIRECT + fallocate treatment as
        local shard writers."""
        self._require_online()
        if not os.path.isdir(self._vol_path(volume)):
            raise ErrVolumeNotFound(volume)
        w = self.create_file_writer(volume, path, size=size)
        written = 0
        try:
            while True:
                chunk = reader.read(1 << 20)
                if not chunk:
                    break
                w.write(chunk)
                written += len(chunk)
        finally:
            w.close()
        if size >= 0 and written != size:
            raise ErrLessDataOrMore(written, size)

    def create_file_writer(self, volume: str, path: str,
                           size: int = -1):
        self._require_online()
        if not os.path.isdir(self._vol_path(volume)):
            raise ErrVolumeNotFound(volume)
        p = self._file_path(volume, path)
        os.makedirs(os.path.dirname(p), exist_ok=True)
        if self._odirect:
            from .directio import DirectFileWriter

            try:
                # Durability handled inside (fsync after the tail write);
                # a known size preallocates extents (fallocate) so
                # commit-time ENOSPC becomes open-time.
                return DirectFileWriter(p, expected_size=size,
                                        fsync_on_close=self._fsync,
                                        drive=self._endpoint)
            except OSError:
                pass  # per-file fallback (e.g. fs quirk): buffered path
        # Unbuffered: shard writers emit one vectored framed write per
        # batch (write_frame_batches → writev), so Python's buffered-IO
        # layer would only add a full extra memcpy per write — measured
        # 1.4 vs 2.6 GB/s on the tmpfs bench host. The wrapper restores
        # the ONE buffered-IO behavior that matters: raw write() may
        # return short (e.g. near-ENOSPC), and a dropped count would
        # silently truncate a shard that still counts toward quorum.
        f = _FullWriter(open(p, "wb", buffering=0), drive=self._endpoint)
        if not self._fsync:
            return f
        return _FsyncOnClose(f)

    def read_file_stream(self, volume: str, path: str, offset: int, length: int):
        self._require_online()
        try:
            f = open(self._file_path(volume, path), "rb")
        except FileNotFoundError:
            raise ErrFileNotFound(f"{volume}/{path}") from None
        except IsADirectoryError:
            raise ErrFileAccessDenied(f"{volume}/{path}") from None
        f.seek(offset)
        return _LimitedReader(f, length, drive=self._endpoint)

    def rename_file(self, src_volume: str, src_path: str,
                    dst_volume: str, dst_path: str) -> None:
        self._require_online()
        src = self._file_path(src_volume, src_path)
        dst = self._file_path(dst_volume, dst_path)
        if not os.path.exists(src):
            raise ErrFileNotFound(f"{src_volume}/{src_path}")
        os.makedirs(os.path.dirname(dst), exist_ok=True)
        os.replace(src, dst)

    def check_parts(self, volume: str, path: str, fi: FileInfo) -> None:
        """Verify every part file exists with the right size
        (ref cmd/xl-storage.go CheckParts)."""
        self._require_online()
        for part in fi.parts:
            if part.number in fi.data:
                continue  # inlined
            p = os.path.join(
                self._file_path(volume, path), fi.data_dir, f"part.{part.number}"
            )
            want = bitrot_shard_file_size(
                fi.erasure.shard_file_size(part.size),
                fi.erasure.shard_size(),
                BitrotAlgorithm.from_string(
                    fi.erasure.get_checksum_info(part.number).algorithm
                ),
            )
            try:
                st = os.stat(p)
            except FileNotFoundError:
                raise ErrFileNotFound(f"{volume}/{path} part.{part.number}") from None
            if st.st_size != want:
                raise ErrFileCorrupt(
                    f"part.{part.number} size {st.st_size} != {want}"
                )

    def check_file(self, volume: str, path: str) -> None:
        self._require_online()
        obj_dir = self._file_path(volume, path)
        if not (os.path.isfile(os.path.join(obj_dir, XL_META_FILE))
                or os.path.isfile(os.path.join(obj_dir, "xl.json"))):
            raise ErrFileNotFound(f"{volume}/{path}")

    def delete(self, volume: str, path: str, recursive: bool = False) -> None:
        self._require_online()
        p = self._file_path(volume, path)
        if not os.path.exists(p):
            if not os.path.isdir(self._vol_path(volume)):
                raise ErrVolumeNotFound(volume)
            raise ErrFileNotFound(f"{volume}/{path}")
        if os.path.isdir(p):
            if recursive:
                shutil.rmtree(p)
            else:
                try:
                    os.rmdir(p)
                except OSError as exc:
                    raise ErrVolumeNotEmpty(f"{volume}/{path}") from exc
        else:
            os.remove(p)

    def verify_file(self, volume: str, path: str, fi: FileInfo) -> None:
        """Deep bitrot scan of every part (ref cmd/xl-storage.go:2151)."""
        self._require_online()
        algo = BitrotAlgorithm.from_string(
            fi.erasure.get_checksum_info(1).algorithm
        )
        for part in fi.parts:
            shard_size = fi.erasure.shard_size()
            part_size = fi.erasure.shard_file_size(part.size)
            if part.number in fi.data:
                stream = io.BytesIO(fi.data[part.number])
                file_size = len(fi.data[part.number])
            else:
                p = os.path.join(
                    self._file_path(volume, path), fi.data_dir, f"part.{part.number}"
                )
                try:
                    if self._odirect:
                        # Deep scans read EVERY byte of cold data once —
                        # exactly what must not evict the page cache
                        # (ref odirectReader, cmd/xl-storage.go:1089).
                        # Streaming: constant memory even for GiB parts.
                        from .directio import DirectReader

                        stream = DirectReader(p, drive=self._endpoint)
                        file_size = stream.size
                    else:
                        file_size = os.stat(p).st_size
                        stream = _LimitedReader(open(p, "rb"), file_size,
                                                drive=self._endpoint)
                except FileNotFoundError:
                    raise ErrFileNotFound(
                        f"{volume}/{path} part.{part.number}"
                    ) from None
                except OSError:
                    file_size = os.stat(p).st_size
                    stream = _LimitedReader(open(p, "rb"), file_size,
                                            drive=self._endpoint)
            try:
                ci = fi.erasure.get_checksum_info(part.number)
                bitrot_verify(
                    stream, file_size, part_size, algo, ci.hash, shard_size
                )
            finally:
                stream.close()

    def stat_info_file(self, volume: str, path: str):
        self._require_online()
        p = self._file_path(volume, path)
        try:
            return os.stat(p)
        except FileNotFoundError:
            raise ErrFileNotFound(f"{volume}/{path}") from None

    # --- small blobs ---

    def write_all(self, volume: str, path: str, data: bytes) -> None:
        self._require_online()
        if not os.path.isdir(self._vol_path(volume)):
            raise ErrVolumeNotFound(volume)
        p = self._file_path(volume, path)
        os.makedirs(os.path.dirname(p), exist_ok=True)
        tmp = p + f".tmp.{os.getpid()}.{time.monotonic_ns()}"
        with open(tmp, "wb") as f:
            f.write(data)
            if self._fsync:
                f.flush()
                os.fsync(f.fileno())
        os.replace(tmp, p)
        ioflow.account(self._endpoint, "wmeta", len(data))

    def read_all(self, volume: str, path: str) -> bytes:
        self._require_online()
        try:
            with open(self._file_path(volume, path), "rb") as f:
                raw = f.read()
        except FileNotFoundError:
            if not os.path.isdir(self._vol_path(volume)):
                raise ErrVolumeNotFound(volume) from None
            raise ErrFileNotFound(f"{volume}/{path}") from None
        ioflow.account(self._endpoint, "rmeta", len(raw))
        return raw


class _FullWriter:
    """Raw-fd writer that retries short writes until every byte lands or
    the OS raises — write() on an unbuffered FileIO is a single syscall
    and may legitimately return a short count."""

    def __init__(self, f, drive: str = ""):
        self._f = f
        self._drive = drive

    def write(self, b) -> int:
        mv = memoryview(b).cast("B") if not isinstance(b, bytes) else b
        total = len(mv)
        n = self._f.write(mv)
        if n is None or n >= total:
            # Ledger AFTER the syscalls succeed: a failed write must not
            # inflate the heal/put efficiency denominators.
            ioflow.account(self._drive, "write", total)
            return total
        mv = memoryview(mv)
        while n < total:
            wrote = self._f.write(mv[n:])
            if not wrote:
                raise OSError(f"write stalled at {n}/{total} bytes")
            n += wrote
        ioflow.account(self._drive, "write", total)
        return total

    def writev(self, buffers) -> int:
        """Vectored scatter-gather write: one writev(2) ships the whole
        [hash||chunk]* frame list straight out of the strip buffers —
        the zero-copy sink of StreamingBitrotWriter.write_frames_vec.
        Retries short writes (near-ENOSPC etc.) resuming mid-iovec."""
        total = sum(len(b) for b in buffers)
        if total == 0:
            return 0
        fd = self._f.fileno()
        written = 0
        pending = list(buffers)
        while True:
            n = os.writev(fd, pending[:1024])  # IOV_MAX bound
            written += n
            if written >= total:
                ioflow.account(self._drive, "write", total)
                return total
            if n == 0:
                raise OSError(f"writev stalled at {written}/{total} bytes")
            # Advance past fully-written buffers, slice the partial one.
            while n:
                ln = len(pending[0])
                if ln <= n:
                    n -= ln
                    pending.pop(0)
                else:
                    pending[0] = memoryview(pending[0])[n:]
                    n = 0

    def fileno(self):
        return self._f.fileno()

    def flush(self):
        self._f.flush()

    def close(self):
        self._f.close()


class _FsyncOnClose:
    """File wrapper that fsyncs before close — keeps the fsync-before-
    rename-commit durability point for streamed shard writes."""

    def __init__(self, f):
        self._f = f
        # Vectored writes pass through when the wrapped sink has them.
        if hasattr(f, "writev"):
            self.writev = f.writev

    def write(self, b):
        return self._f.write(b)

    def fileno(self):
        return self._f.fileno()

    def close(self):
        self._f.flush()
        os.fsync(self._f.fileno())
        self._f.close()


class _LimitedReader:
    """Read at most `limit` bytes from an underlying file, then EOF."""

    def __init__(self, f, limit: int, drive: str = ""):
        self._f = f
        self._left = limit
        self._drive = drive

    def read(self, n: int = -1) -> bytes:
        if self._left <= 0:
            return b""
        if n is None or n < 0 or n > self._left:
            n = self._left
        buf = self._f.read(n)
        self._left -= len(buf)
        ioflow.account(self._drive, "read", len(buf))
        return buf

    def readinto(self, b) -> int:
        """Zero-alloc fill — lets the bitrot readers recycle their read
        buffers instead of materializing fresh bytes per fetch."""
        if self._left <= 0:
            return 0
        view = memoryview(b)
        if len(view) > self._left:
            view = view[: self._left]
        n = self._f.readinto(view) or 0
        self._left -= n
        ioflow.account(self._drive, "read", n)
        return n

    def close(self):
        self._f.close()


class ErrLessDataOrMore(ErrInvalidArgument):
    def __init__(self, written: int, want: int):
        super().__init__(f"wrote {written} bytes, expected {want}")
