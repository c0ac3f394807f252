"""Native (C) runtime components, built on demand with the system
compiler and loaded via ctypes — the counterpart of the reference's
assembly-accelerated Go deps (SURVEY.md §2.9). Python fallbacks exist for
every entry point; set MTPU_NO_NATIVE=1 to force them.
"""

from __future__ import annotations

import contextlib
import ctypes
import glob
import hashlib
import os
import platform
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_BUILD_DIR = os.path.join(_DIR, "_build")
_SOURCES = ["highwayhash.c", "gfapply.c", "snappy.c"]
# -march=native unlocks GFNI/pshufb/AVX2 for the kernels; the ladder
# retries without it (scalar fallback paths in the C) on exotic
# toolchains.
_FLAG_LADDER = (["-march=native", "-fopenmp"], ["-fopenmp"], [])

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False


def _cpu_flags() -> str:
    """The host CPU's feature list — what -march=native compiles for."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return " ".join(sorted(line.split(":", 1)[1].split()))
    except OSError:
        pass
    return platform.machine()


def _build_key() -> str:
    """Hash of the C sources, the flag ladder and the host's CPU flags.
    The library's file name carries it, so a build made from other
    sources or carried over from another machine (the ISA dispatch is
    decided at compile time: loading it there is SIGILL, not an
    exception) is never loaded — it is simply not this build."""
    h = hashlib.sha256()
    for src in _SOURCES:
        with open(os.path.join(_DIR, src), "rb") as f:
            h.update(f.read())
    h.update(repr(_FLAG_LADDER).encode())
    h.update(_cpu_flags().encode())
    return h.hexdigest()[:16]


def _build() -> str | None:
    so_path = os.path.join(_BUILD_DIR, f"libmtpu_native-{_build_key()}.so")
    if os.path.exists(so_path):
        return so_path
    os.makedirs(_BUILD_DIR, exist_ok=True)
    srcs = [os.path.join(_DIR, s) for s in _SOURCES]
    tmp = so_path + f".tmp{os.getpid()}"
    for extra in _FLAG_LADDER:
        cmd = ["cc", "-O3", *extra, "-shared", "-fPIC", "-o", tmp, *srcs]
        try:
            subprocess.run(
                cmd, check=True, capture_output=True, timeout=120
            )
            os.replace(tmp, so_path)
        except (subprocess.SubprocessError, OSError):
            with contextlib.suppress(OSError):
                os.unlink(tmp)
            continue
        for stale in glob.glob(os.path.join(_BUILD_DIR, "libmtpu_native*.so")):
            if stale != so_path:
                with contextlib.suppress(OSError):
                    os.unlink(stale)
        return so_path
    return None


def load() -> ctypes.CDLL | None:
    """Build (if stale) and load the native library; None on failure."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    if os.environ.get("MTPU_NO_NATIVE", "0") == "1":
        return None
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        so_path = _build()
        if so_path is None:
            return None
        # GOMP's default active wait keeps every team member spinning
        # after a parallel region. Each request thread that calls in
        # owns a team, so on a busy or shared-core host the spinners
        # starve the workers and a sub-millisecond call takes tens of
        # milliseconds. libgomp reads the policy once, when it loads.
        os.environ.setdefault("OMP_WAIT_POLICY", "passive")
        try:
            lib = ctypes.CDLL(so_path)
        except OSError:
            return None
        u8p = ctypes.POINTER(ctypes.c_uint8)
        u64p = ctypes.POINTER(ctypes.c_uint64)
        lib.hh256_init.argtypes = [ctypes.c_char_p, u64p]
        lib.hh256_update.argtypes = [u64p, ctypes.c_char_p, ctypes.c_size_t]
        lib.hh256_final.argtypes = [
            u64p, ctypes.c_char_p, ctypes.c_size_t, u8p,
        ]
        lib.hh256_hash.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t, u8p,
        ]
        lib.hh256_hash_batch.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_size_t, u8p,
        ]
        lib.gf_apply.argtypes = [
            u8p, ctypes.c_int, ctypes.c_int, u8p, u8p,
            ctypes.c_size_t, ctypes.c_int,
        ]
        lib.gf_apply_batch.argtypes = [
            u8p, ctypes.c_int, ctypes.c_int, u8p, u8p,
            ctypes.c_size_t, ctypes.c_size_t, ctypes.c_int,
        ]
        lib.hh256_frame.argtypes = [
            ctypes.c_char_p, u8p, ctypes.c_size_t, ctypes.c_size_t, u8p,
        ]
        lib.hh256_verify_frames.argtypes = [
            ctypes.c_char_p, u8p, ctypes.c_size_t, ctypes.c_size_t,
        ]
        lib.hh256_verify_frames.restype = ctypes.c_int64
        lib.hh256_hash_strided.argtypes = [
            ctypes.c_char_p, u8p, ctypes.c_size_t, ctypes.c_size_t,
            ctypes.c_size_t, u8p,
        ]
        lib.gf_engine_kind.restype = ctypes.c_int
        lib.gf_apply_affine.argtypes = [
            u64p, ctypes.c_int, ctypes.c_int, u8p, u8p,
            ctypes.c_size_t, ctypes.c_int,
        ]
        lib.gf_apply_affine_batch.argtypes = [
            u64p, ctypes.c_int, ctypes.c_int, u8p, u8p,
            ctypes.c_size_t, ctypes.c_size_t, ctypes.c_int,
        ]
        lib.mtpu_snappy_max_compressed.argtypes = [ctypes.c_size_t]
        lib.mtpu_snappy_max_compressed.restype = ctypes.c_size_t
        lib.mtpu_snappy_compress.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, u8p,
        ]
        lib.mtpu_snappy_compress.restype = ctypes.c_size_t
        lib.mtpu_snappy_uncompressed_length.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t,
        ]
        lib.mtpu_snappy_uncompressed_length.restype = ctypes.c_int64
        lib.mtpu_snappy_decompress.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, u8p, ctypes.c_size_t,
        ]
        lib.mtpu_snappy_decompress.restype = ctypes.c_int64
        lib.mtpu_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
        lib.mtpu_crc32c.restype = ctypes.c_uint32
        _lib = lib
        return _lib


class NativeHighwayHash256:
    """hashlib-style streaming digest over the C engine."""

    digest_size = 32
    block_size = 32

    def __init__(self, key: bytes, lib: ctypes.CDLL):
        self._lib = lib
        self._key = key
        self._state = (ctypes.c_uint64 * 16)()
        self._buf = bytearray()
        lib.hh256_init(key, self._state)

    def update(self, data):
        data = bytes(data)
        if not self._buf:
            # Fast path (one big chunk per hasher in the bitrot writers):
            # feed the aligned prefix straight to C, buffer only the tail.
            n = len(data) // 32
            if n:
                self._lib.hh256_update(self._state, data, n)
            self._buf += data[n * 32:]
            return self
        self._buf += data
        n = len(self._buf) // 32
        if n:
            chunk = bytes(self._buf[: n * 32])
            self._lib.hh256_update(self._state, chunk, n)
            del self._buf[: n * 32]
        return self

    def digest(self) -> bytes:
        out = (ctypes.c_uint8 * 32)()
        tail = bytes(self._buf)
        self._lib.hh256_final(self._state, tail, len(tail), out)
        return bytes(out)

    def hexdigest(self) -> str:
        return self.digest().hex()

    def reset(self):
        self._lib.hh256_init(self._key, self._state)
        self._buf.clear()
        return self


def new_highwayhash256(key: bytes):
    """Native digest when available, else None (caller falls back)."""
    lib = load()
    if lib is None:
        return None
    return NativeHighwayHash256(key, lib)


def hash256(data: bytes, key: bytes):
    """One-shot native hash; None when the native lib is unavailable."""
    lib = load()
    if lib is None:
        return None
    out = (ctypes.c_uint8 * 32)()
    buf = bytes(data)
    lib.hh256_hash(key, buf, len(buf), out)
    return bytes(out)
