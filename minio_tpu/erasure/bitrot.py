"""Bitrot integrity framework: per-shard hashing in the streaming
interleaved layout of the reference ([hash || chunk]* per shard file,
/root/reference/cmd/bitrot-streaming.go) plus whole-file mode for the
legacy algorithms (cmd/bitrot-whole.go).

Four algorithms mirror cmd/bitrot.go:36-41 — SHA256, BLAKE2b-512,
HighwayHash256 (whole), HighwayHash256S (streaming, the default). The
HighwayHash implementation is our own bit-exact engine (ops/highwayhash.py)
with a batched TPU variant used by the fused verify path.
"""

from __future__ import annotations

import hashlib
import io
import threading
from enum import Enum

import numpy as np

from .. import native
from ..ops import highwayhash
from ..utils import ceil_frac
from ..utils.errors import ErrFileCorrupt, ErrLessData


class BitrotAlgorithm(Enum):
    SHA256 = "sha256"
    BLAKE2B512 = "blake2b"
    HIGHWAYHASH256 = "highwayhash256"
    HIGHWAYHASH256S = "highwayhash256S"

    @classmethod
    def default(cls) -> "BitrotAlgorithm":
        # DefaultBitrotAlgorithm, cmd/bitrot.go (HighwayHash256S).
        return cls.HIGHWAYHASH256S

    @classmethod
    def from_string(cls, s: str) -> "BitrotAlgorithm":
        for a in cls:
            if a.value == s:
                return a
        raise ValueError(f"unsupported bitrot algorithm {s!r}")

    def new(self):
        """hashlib-style digest for this algorithm (cmd/bitrot.go:44-61)."""
        if self is BitrotAlgorithm.SHA256:
            return hashlib.sha256()
        if self is BitrotAlgorithm.BLAKE2B512:
            return hashlib.blake2b(digest_size=64)
        # HighwayHash: native C engine when available (the reference uses
        # Go assembly here), numpy engine as fallback. The native import
        # lives at module scope: an in-function import here serializes
        # EVERY hasher creation on the interpreter's import lock (16
        # hashers per PUT — visible in profiles under contention).
        h = native.new_highwayhash256(highwayhash.MAGIC_KEY)
        if h is not None:
            return h
        return highwayhash.HighwayHash256(highwayhash.MAGIC_KEY)

    @property
    def digest_size(self) -> int:
        return 64 if self is BitrotAlgorithm.BLAKE2B512 else 32

    @property
    def streaming(self) -> bool:
        return self is BitrotAlgorithm.HIGHWAYHASH256S


def bitrot_shard_file_size(size: int, shard_size: int, algo: BitrotAlgorithm) -> int:
    """On-disk size of a shard file with interleaved checksums
    (cmd/bitrot.go:143-148)."""
    if not algo.streaming:
        return size
    if size < 0:
        return -1
    return ceil_frac(size, shard_size) * algo.digest_size + size


def bitrot_stream_offset(offset: int, shard_size: int, algo: BitrotAlgorithm) -> int:
    """Translate a logical shard offset (multiple of shard_size) to the
    physical offset in the interleaved stream
    (cmd/bitrot-streaming.go:135)."""
    return (offset // shard_size) * algo.digest_size + offset


class StreamingBitrotWriter:
    """Writes [H(chunk) || chunk] per chunk into an underlying byte sink.

    The reference pipes this into disk.CreateFile asynchronously
    (cmd/bitrot-streaming.go:83-99); here the sink is any .write()able.
    """

    def __init__(self, sink, algo: BitrotAlgorithm = BitrotAlgorithm.HIGHWAYHASH256S):
        self._sink = sink
        self._algo = algo
        self._h = algo.new()
        self.bytes_written = 0

    def write(self, chunk) -> int:
        chunk = bytes(chunk)
        if not chunk:
            return 0
        h = self._algo.new()
        h.update(chunk)
        self._sink.write(h.digest())
        self._sink.write(chunk)
        self.bytes_written += len(chunk)
        return len(chunk)

    @property
    def device_hashable(self) -> bool:
        """Only HighwayHash256S digests are computed on-device; other
        algorithms must keep hashing in write() (a foreign 32-byte digest
        would permanently mis-frame e.g. a BLAKE2b-512 shard file)."""
        return self._algo is BitrotAlgorithm.HIGHWAYHASH256S

    def write_frames_vec(self, chunks: list, digests=None) -> int:
        """Vectored zero-copy framing: emit [H(chunk)||chunk] for every
        chunk WITHOUT materializing the framed strip. `chunks` are
        buffer-protocol views (typically rows into the pooled block-major
        strip buffer); `digests` is an optional [n, 32] uint8 array of
        precomputed frame hashes (hash_strided_digests). With a vectored
        sink the scatter-gather list goes straight to writev — no data
        byte is copied in userspace; other sinks get paired write()
        calls (still copy-free for buffer-protocol-aware sinks like
        BytesIO and the raw-fd writers)."""
        n = len(chunks)
        if n == 0:
            return 0
        if digests is None or self._algo is not BitrotAlgorithm.HIGHWAYHASH256S:
            dig = []
            for c in chunks:
                h = self._algo.new()
                h.update(c)
                dig.append(h.digest())
        else:
            dig = digests
        sink = self._sink
        total = 0
        writev = getattr(sink, "writev", None)
        if writev is not None:
            iov: list = [None] * (2 * n)
            for i, c in enumerate(chunks):
                iov[2 * i] = memoryview(dig[i]).cast("B")
                iov[2 * i + 1] = c
                total += len(c)
            writev(iov)
        else:
            for i, c in enumerate(chunks):
                sink.write(memoryview(dig[i]).cast("B"))
                sink.write(c)
                total += len(c)
        self.bytes_written += total
        return total

    def write_with_digest(self, chunk, digest: bytes) -> int:
        """Frame a chunk whose HighwayHash256 was already computed on the
        device in the fused encode dispatch (codec.encode_batch_async) —
        the host hashing in write() is the per-shard hot cost this
        removes."""
        if not self.device_hashable:
            return self.write(chunk)
        chunk = bytes(chunk)
        if not chunk:
            return 0
        self._sink.write(digest)
        self._sink.write(chunk)
        self.bytes_written += len(chunk)
        return len(chunk)

    def close(self):
        if hasattr(self._sink, "close"):
            self._sink.close()


class WholeBitrotWriter:
    """Whole-file bitrot: plain passthrough writes, hash accumulated and
    read out via sum() for xl.meta (cmd/bitrot-whole.go:37-60)."""

    def __init__(self, sink, algo: BitrotAlgorithm):
        self._sink = sink
        self._h = algo.new()

    def write(self, chunk) -> int:
        chunk = bytes(chunk)
        self._h.update(chunk)
        self._sink.write(chunk)
        return len(chunk)

    def sum(self) -> bytes:
        return self._h.digest()

    def close(self):
        if hasattr(self._sink, "close"):
            self._sink.close()


class StreamingBitrotReader:
    """Sequential chunk-aligned read_at() with inline hash verification,
    mirroring streamingBitrotReader (cmd/bitrot-streaming.go:102-168).

    `open_stream(stream_offset, length)` is a callable returning a readable
    for the physical byte range — the seam where a local file, an inline
    xl.meta buffer, or a remote storage stream plugs in.
    """

    # Set by the caller when the underlying stream is a local file /
    # in-memory buffer: the ParallelReader runs local reads inline on
    # single-core hosts instead of paying pool-dispatch overhead.
    local = False

    # Below this framed-batch size a worker verify round trip costs
    # more than the (GIL-releasing) in-process native call it replaces.
    WORKER_VERIFY_MIN = 512 * 1024

    def __init__(self, open_stream, till_offset: int, shard_size: int,
                 algo: BitrotAlgorithm = BitrotAlgorithm.HIGHWAYHASH256S):
        self._open = open_stream
        self._algo = algo
        # chunk bytes that passed their digests; the stream that owns the
        # reader publishes the sum when it ends
        # (bitrot_verified_bytes_total), so a batch costs no lock here
        self.verified_bytes = 0
        self._shard_size = shard_size
        # Physical end offset incl. hash framing (cmd/bitrot-streaming.go:178)
        self._till = ceil_frac(till_offset, shard_size) * algo.digest_size + till_offset
        self._rc = None
        self._curr = 0
        self._ring: list | None = None
        self._ring_i = 0
        # Worker-verify plumbing (ISSUE 11): shm-backed ring slots, the
        # slot the last batch landed in, and an in-flight/deferred-
        # release handshake so a parked fan-out thread's late readinto
        # can never scribble a recycled segment.
        self._shm_backed = False
        self._last_shm = None
        self._inflight = 0
        self._release_pending = False
        self._ring_mu = threading.Lock()

    def reuse_buffers(self, depth: int = 2) -> None:
        """Opt into recycling read buffers: read_chunks fills a private
        ring of `depth` buffers round-robin (readinto, no fresh bytes
        per fetch) and returns memoryviews into them. ONLY valid when
        the consumer fully drains each batch's views before `depth`
        further batches are fetched — true for the serial decode/heal
        drivers, whose sinks consume (or copy) every chunk before the
        next reader fan-out. The pipelined GET path keeps several
        batches in flight and must NOT enable this.

        When the request-plane worker pool is armed (and the algo is
        the streaming default), the ring slots come from the pooled
        shared-memory ring segments instead of private bytearrays, so
        frame verification can run in a worker with zero payload bytes
        crossing the pipe. Callers that enable reuse should pair it
        with release_buffers() when the stream ends."""
        if self._ring is None:
            self._ring = [None] * max(2, depth)
            if self._algo is BitrotAlgorithm.HIGHWAYHASH256S:
                from ..pipeline import workers as _workers

                self._shm_backed = _workers.armed() is not None

    def release_buffers(self) -> None:
        """Return pooled shm ring slots to their pool (the decode/heal
        drivers call this in their finally). If a read is still in
        flight — a parked/abandoned fan-out thread — the release is
        deferred to that thread's exit instead, so a recycled segment
        is never scribbled by a stale readinto."""
        with self._ring_mu:
            self._release_pending = True
            if self._inflight == 0:
                self._release_now()

    def _release_now(self) -> None:
        ring, self._ring = self._ring, None
        self._ring_i = 0
        self._last_shm = None
        self._release_pending = False
        if not ring or not self._shm_backed:
            return
        from ..pipeline import workers as _workers

        for slot in ring:
            # Rings can mix shm and plain slots (the phys threshold
            # decides per batch); only LIVE shm slots go back to a
            # pool. A slot closed under us by workers.shutdown()
            # (view is None) is dropped — re-freelisting it would
            # seed the post-purge pool with a dead segment and crash
            # the next armed stream that acquires it.
            if (slot is not None and hasattr(slot, "view")
                    and slot.view is not None):
                _workers.ring_pool(slot.size).release(slot)

    def _enter_read(self) -> None:
        with self._ring_mu:
            self._inflight += 1

    def _exit_read(self) -> None:
        with self._ring_mu:
            self._inflight -= 1
            if (self._release_pending and self._inflight == 0
                    and self._ring is not None):
                self._release_now()

    def _read_phys(self, phys: int):
        """Read `phys` framed bytes; returns a memoryview over either a
        recycled ring buffer (readinto, no fresh bytes per fetch) or a
        fresh bytes object. Shm-backed rings record the slot the batch
        landed in (self._last_shm) for the worker verify path."""
        from ..pipeline.buffers import copy_add

        rc = self._rc
        self._last_shm = None
        if self._ring is not None and hasattr(rc, "readinto"):
            buf = self._ring[self._ring_i]
            # A live shm slot has a non-None view; a slot whose segment
            # was closed under us (workers.shutdown() racing an
            # in-flight stream) is treated as absent and replaced.
            slot_is_shm = (buf is not None
                           and getattr(buf, "view", None) is not None)
            if buf is not None and not slot_is_shm and hasattr(buf,
                                                              "view"):
                buf = None  # dead segment: drop, never reuse/release
                self._ring[self._ring_i] = None
            # A slot goes shm only when this batch is big enough for
            # the worker verify to engage (or an earlier batch already
            # paid for a big-enough segment): a small GET must not
            # allocate 256 KiB segments it can never use.
            if self._shm_backed and (
                    phys >= self.WORKER_VERIFY_MIN
                    or (slot_is_shm and buf.size >= phys)):
                from ..pipeline import workers as _workers

                if not slot_is_shm or buf.size < phys:
                    if slot_is_shm:
                        _workers.ring_pool(buf.size).release(buf)
                    # pool-ok: returned by release_buffers (the stream
                    # drivers' finally) or re-released on growth above
                    buf = _workers.ring_pool(
                        _workers.ring_capacity(phys)
                    ).acquire()
                    self._ring[self._ring_i] = buf
                view = memoryview(buf.view)[:phys]
                self._last_shm = buf
            else:
                if slot_is_shm:
                    # Shrinking stream landed on an undersized shm
                    # slot: hand it back, fall to a plain buffer.
                    from ..pipeline import workers as _workers

                    _workers.ring_pool(buf.size).release(buf)
                    buf = None
                    self._ring[self._ring_i] = None
                if buf is None or len(buf) < phys:
                    buf = bytearray(phys)
                    self._ring[self._ring_i] = buf
                view = memoryview(buf)[:phys]
            self._ring_i = (self._ring_i + 1) % len(self._ring)
            got = 0
            while got < phys:
                n = rc.readinto(view[got:])
                if not n:
                    break
                got += n
            copy_add("get.source_read", got)
            if got != phys:
                raise ErrFileCorrupt("short framed read")
            return view
        raw = rc.read(phys)
        copy_add("get.source_read", len(raw))
        if len(raw) != phys:
            raise ErrFileCorrupt("short framed read")
        return memoryview(raw)

    def read_at(self, offset: int, length: int):
        """Read+verify one chunk. With reuse_buffers enabled the chunk
        comes back as a memoryview into the recycled ring (same
        consumption contract as read_chunks); otherwise fresh bytes."""
        if offset % self._shard_size != 0:
            raise ValueError("offset must be shard-aligned")
        if self._rc is None:
            self._curr = offset
            stream_off = bitrot_stream_offset(offset, self._shard_size, self._algo)
            self._rc = self._open(stream_off, self._till - stream_off)
        if offset != self._curr:
            raise ValueError("non-sequential bitrot read")
        ds = self._algo.digest_size
        self._enter_read()
        try:
            if self._ring is not None and hasattr(self._rc, "readinto"):
                mv = self._read_phys(ds + length)
                hash_want = bytes(mv[:ds])
                buf = mv[ds:]
            else:
                hash_want = self._rc.read(ds)
                if len(hash_want) != ds:
                    raise ErrFileCorrupt("short hash read")
                buf = self._rc.read(length)
                if len(buf) != length:
                    raise ErrFileCorrupt("short chunk read")
        finally:
            self._exit_read()
        h = self._algo.new()
        h.update(buf)
        if h.digest() != hash_want:
            raise ErrFileCorrupt(
                f"content hash mismatch: want {hash_want.hex()}, got {h.digest().hex()}"
            )
        self._curr += length
        self.verified_bytes += length
        return buf

    def read_chunks(self, offset: int, lengths: list[int]) -> list:
        """Read + verify several consecutive chunks in ONE underlying read
        and (when native) ONE verify call — the batched read path that
        amortizes the per-chunk Python/syscall cost of read_at across a
        whole batch of blocks. Returns a list of memoryviews, one per
        requested chunk length."""
        if not lengths:
            return []
        if offset % self._shard_size != 0:
            raise ValueError("offset must be shard-aligned")
        if self._rc is None:
            self._curr = offset
            stream_off = bitrot_stream_offset(offset, self._shard_size, self._algo)
            self._rc = self._open(stream_off, self._till - stream_off)
        if offset != self._curr:
            raise ValueError("non-sequential bitrot read")
        ds = self._algo.digest_size
        phys = sum(lengths) + ds * len(lengths)
        self._enter_read()
        try:
            mv = self._read_phys(phys)
            # Chunk lengths in the physical layout are shard_size except
            # a trailing short one — exactly the whole-buffer framing
            # contract of hh256_verify_frames (worker or in-process).
            aligned = (
                self._algo is BitrotAlgorithm.HIGHWAYHASH256S
                and all(ln == self._shard_size for ln in lengths[:-1])
            )
            verified = False
            if (aligned and self._last_shm is not None
                    and phys >= self.WORKER_VERIFY_MIN):
                # Worker verify: the framed batch already lives in a
                # pooled shm ring segment, so the whole verification
                # runs in a child interpreter and the pipe carries one
                # int back. A busy/dead worker falls back to the
                # in-process pass below — same bytes, same verdict.
                # Note: verify time has been part of read_chunks (and
                # therefore of ParallelReader's stall/hedge window)
                # since the batched verify landed; under extreme CPU
                # saturation a slow verify — worker or in-process —
                # can trip the hedge and escalate to a parity reader,
                # which is the designed response to a slow source and
                # stays byte-identical (reconstruction).
                from ..pipeline import workers as _workers

                wpool = _workers.armed()
                if wpool is not None:
                    try:
                        bad = wpool.verify_frames(
                            self._last_shm, phys, self._shard_size
                        )
                        if bad >= 0:
                            raise ErrFileCorrupt(
                                f"streaming bitrot mismatch chunk {bad}"
                            )
                        verified = True
                    except (_workers.WorkerCrashed,
                            _workers.WorkerUnavailable):
                        wpool.note_fallback("verify")
            from .. import native as _native

            lib = _native.load()
            if not verified and aligned and lib is not None:
                # One native pass verifies every frame in-process.
                import ctypes

                import numpy as np

                arr = np.frombuffer(mv, dtype=np.uint8)
                bad = lib.hh256_verify_frames(
                    highwayhash.MAGIC_KEY,
                    arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                    phys, self._shard_size,
                )
                if bad >= 0:
                    raise ErrFileCorrupt(
                        f"streaming bitrot mismatch chunk {bad}"
                    )
                verified = True
            out = []
            off = 0
            if verified:
                for ln in lengths:
                    out.append(mv[off + ds: off + ds + ln])
                    off += ds + ln
            else:
                for ln in lengths:
                    hash_want = bytes(mv[off: off + ds])
                    chunk = mv[off + ds: off + ds + ln]
                    h = self._algo.new()
                    h.update(chunk)
                    if h.digest() != hash_want:
                        raise ErrFileCorrupt("streaming bitrot mismatch")
                    out.append(chunk)
                    off += ds + ln
            n = sum(lengths)
            self._curr += n
            # every branch above has verified or raised by here
            self.verified_bytes += n
            return out
        finally:
            self._exit_read()

    def close(self):
        if self._rc is not None and hasattr(self._rc, "close"):
            self._rc.close()
        self._rc = None


def bitrot_verify(stream, want_size: int, part_size: int,
                  algo: BitrotAlgorithm, want_sum: bytes, shard_size: int):
    """Verify a whole shard stream (cmd/bitrot.go:151-199). Raises
    ErrFileCorrupt on any mismatch."""
    if not algo.streaming:
        h = algo.new()
        n = 0
        while True:
            buf = stream.read(1 << 20)
            if not buf:
                break
            h.update(buf)
            n += len(buf)
        if n != want_size or h.digest() != want_sum:
            raise ErrFileCorrupt("whole-file bitrot mismatch")
        return

    if want_size != bitrot_shard_file_size(part_size, shard_size, algo):
        raise ErrFileCorrupt("bitrot file size mismatch")
    left = want_size
    chunk = shard_size
    while left > 0:
        hash_want = stream.read(algo.digest_size)
        if len(hash_want) != algo.digest_size:
            raise ErrLessData("short hash read")
        left -= len(hash_want)
        if left < chunk:
            chunk = left
        buf = stream.read(chunk)
        if len(buf) != chunk:
            raise ErrLessData("short chunk read")
        left -= len(buf)
        h = algo.new()
        h.update(buf)
        if h.digest() != hash_want:
            raise ErrFileCorrupt("streaming bitrot mismatch")


def hash_strided_digests(arr: np.ndarray, byte_offset: int, stride: int,
                         n: int, chunk: int,
                         out: np.ndarray | None = None) -> np.ndarray | None:
    """Frame digests for n chunk-sized slices at arr.base+offset+i*stride,
    computed in ONE native call with zero data copies — the hashing half
    of the vectored write path (write_frames_vec ships [digest||view]
    pairs via writev). The block-major strip layout puts shard j's
    consecutive bitrot chunks exactly at such a stride. Returns [n, 32]
    uint8, or None when the native engine is unavailable (callers fall
    back to per-chunk hashing inside write_frames_vec)."""
    from .. import native as _native

    lib = _native.load()
    if lib is None or n <= 0:
        return None
    import ctypes

    if out is None or out.shape[0] < n:
        out = np.empty((n, 32), dtype=np.uint8)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    base = ctypes.cast(arr.ctypes.data + byte_offset, u8p)
    lib.hh256_hash_strided(highwayhash.MAGIC_KEY, base, stride, n, chunk,
                           out.ctypes.data_as(u8p))
    return out[:n]


def hash_shard_chunks(shards: np.ndarray, shard_size: int) -> np.ndarray:
    """Device-batched framing helper: hash every shard_size chunk of every
    shard, matching the streaming writer's per-chunk hashes. shards
    [..., S] uint8; returns hashes [..., n_chunks, 32] uint8.

    The final partial chunk (if S % shard_size != 0) is hashed at its TRUE
    length in a separate dispatch — the reference hashes the short tail
    chunk as-is, never padded (cmd/bitrot-streaming.go:48-59)."""
    from ..ops.highwayhash_jax import hash256_batch_jax

    *lead, s = shards.shape
    n_full = s // shard_size
    tail = s - n_full * shard_size
    out = np.empty((*lead, n_full + (1 if tail else 0), 32), dtype=np.uint8)
    if n_full:
        full = shards[..., : n_full * shard_size].reshape(*lead, n_full, shard_size)
        out[..., :n_full, :] = np.asarray(hash256_batch_jax(full))
    if tail:
        out[..., n_full, :] = np.asarray(
            hash256_batch_jax(shards[..., n_full * shard_size :])
        )
    return out
