"""Streaming erasure pipelines: encode fan-out, k-of-n parallel decode with
reconstruct-on-miss, and heal — the equivalents of
/root/reference/cmd/erasure-encode.go, erasure-decode.go and
erasure-lowlevel-heal.go, re-shaped for a TPU backend.

Differences from the reference, by design:
- The reference encodes one 1 MiB block per call and fans out k+m
  goroutines per block. Here the encode loop can gather N blocks and
  dispatch them as one [N, k, S] batch to the MXU (Erasure.encode_batch),
  amortizing host<->device transfers; shard writes still fan out in a
  thread pool per disk.
- Quorum semantics (write tolerates failures down to write_quorum, read
  escalates to extra disks on error, heal writes with quorum 1) are
  preserved exactly.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from ..storage.diskcheck import ROBUST
from ..utils.errors import (
    OBJECT_OP_IGNORED_ERRS,
    ErrDiskNotFound,
    ErrDiskOpTimeout,
    ErrErasureReadQuorum,
    ErrFileCorrupt,
    ErrFileNotFound,
    ErrInvalidArgument,
    ErrLessData,
    reduce_read_quorum_errs,
    reduce_write_quorum_errs,
)
from .codec import Erasure

# Shared IO pool for shard fan-out (the reference spawns goroutines ad hoc;
# a pool keeps Python thread churn bounded).
_io_pool = ThreadPoolExecutor(max_workers=64, thread_name_prefix="mtpu-io")

from ..observability import ioflow as _ioflow
from ..observability import spans as _spans
from . import registry
from .device_engine import HostFeed
from .device_engine import to_host as _to_host
from ..utils.fanout import QuorumFanout, StragglerCompensator

# Robustness telemetry: module counters always tick (tests read them
# directly); a registry handle installed at server boot mirrors them
# onto the metrics endpoint (same pattern as pipeline/metrics.py).
_stats_lock = threading.Lock()
STATS = {"hedged_reads_total": 0, "fanout_stragglers_total": 0}
_metrics = None

# Detached stragglers keep occupying their _io_pool worker (possibly
# forever); the compensator raises the pool ceiling while they do, so
# healthy fan-outs never lose concurrency to a wedged drive.
_io_compensator = StragglerCompensator(_io_pool)


def set_metrics(registry) -> None:
    global _metrics
    _metrics = registry


def record_stat(name: str, n: int = 1) -> None:
    with _stats_lock:
        STATS[name] += n
    if _metrics is not None:
        _metrics.inc(name, n)


class ParallelWriter:
    """Write shard blocks to k+m writers in parallel, tolerating failures
    down to write_quorum (ref cmd/erasure-encode.go:29-70).

    Quorum-wait fan-out: each dispatch returns as soon as write-quorum
    successes land plus a short straggler grace; writers still in flight
    past that point are DETACHED — they finish (or hang) in background,
    their slot is nil'd so later blocks and the commit skip them, and
    the shard heals via MRF. A hung drive therefore costs a PUT at most
    (op deadline + straggler grace), never an unbounded stall (ref the
    diskHealthTracker deadlines of cmd/xl-storage-disk-id-check.go)."""

    def __init__(self, writers: list, write_quorum: int,
                 op_deadline_s: float | None = None,
                 straggler_grace_s: float | None = None):
        # NOTE: the caller's list is mutated — failed writers are nil'd in
        # place so upper layers (putObject commit, MRF) observe mid-stream
        # failures, exactly like the reference's shared writers slice
        # (cmd/erasure-encode.go:50, consumed at erasure-object.go:731+).
        self.writers = writers
        self.write_quorum = write_quorum
        self.errs: list = [None] * len(writers)
        self._op_deadline_s = op_deadline_s
        self._grace_s = straggler_grace_s
        # Persistent detach state: a writer detached on one block stays
        # detached for the rest of the stream.
        self._fan = QuorumFanout(_io_pool, _io_compensator)

    def write(self, blocks: list):
        self._fanout(lambda i: self.writers[i].write(blocks[i]))

    def _fanout(self, attempt):
        """Dispatch attempt(i) for every live writer through the pool.
        Waits for quorum + grace, not for every writer (QuorumFanout
        owns the detach protocol)."""
        deadline_s = (self._op_deadline_s if self._op_deadline_s is not None
                      else ROBUST.op_deadline_s)
        grace_s = (self._grace_s if self._grace_s is not None
                   else ROBUST.straggler_grace_s)
        pending: set[int] = set()
        for i, w in enumerate(self.writers):
            if i in self._fan.detached:
                continue  # straggler from an earlier block; errs latched
            if w is None:
                if self.errs[i] is None:
                    self.errs[i] = ErrDiskNotFound(f"writer {i}")
                continue
            pending.add(i)

        def record(i, err):
            if err is None:
                self.errs[i] = None
            else:
                self.errs[i] = err
                self.writers[i] = None

        def on_detach(i):
            # errs[i] stays a timeout (the writer missed later blocks
            # regardless) and the nil'd slot routes the shard to MRF.
            self.errs[i] = ErrDiskOpTimeout(
                f"writer {i} straggling past write quorum"
            )
            self.writers[i] = None

        self._fan.dispatch(
            attempt, pending, self.write_quorum, deadline_s, grace_s,
            count_ok=lambda: sum(
                1 for j in range(len(self.errs))
                if self.errs[j] is None and j not in pending
            ),
            record=record,
            on_detach=on_detach,
            skip=lambda i: self.writers[i] is None,
            on_stragglers=lambda n: record_stat(
                "fanout_stragglers_total", n
            ),
        )

        nil_count = sum(1 for e in self.errs if e is None)
        if nil_count >= self.write_quorum:
            return
        err = reduce_write_quorum_errs(
            self.errs, OBJECT_OP_IGNORED_ERRS, self.write_quorum
        )
        if err is not None:
            raise err

    def write_frame_batches(self, data_buf, parity, nb: int, k: int,
                            m: int, shard: int, digests=None):
        """Zero-copy batched fan-out over the block-major strip buffer:
        block bi's shard j lives at data_buf[bi, j*S:(j+1)*S] (parity at
        parity[bi, j-k]), so shard j's consecutive bitrot chunks sit at
        a fixed stride. Each writer's frame digests come from ONE native
        strided-hash call and the [digest||chunk] pairs ship via the
        sink's vectored writev — no data byte is copied between the
        strip buffer and the kernel. `digests` ([k+m, nb, 32], from the
        worker pool's shm segment) skips the in-process hash entirely —
        the worker already computed the identical strided digests."""
        from .bitrot import hash_strided_digests

        row = data_buf.shape[1]  # k * shard bytes per block row

        def attempt(i):
            w = self.writers[i]
            if i < k:
                chunks = [data_buf[bi, i * shard: (i + 1) * shard]
                          for bi in range(nb)]
                digs = (digests[i, :nb] if digests is not None
                        else hash_strided_digests(
                            data_buf, i * shard, row, nb, shard))
            else:
                pi = i - k
                chunks = [parity[bi, pi] for bi in range(nb)]
                digs = (digests[i, :nb] if digests is not None
                        else hash_strided_digests(
                            parity, pi * shard, m * shard, nb, shard))
            if hasattr(w, "write_frames_vec"):
                w.write_frames_vec(chunks, digs)
            else:
                for c in chunks:
                    w.write(c)

        self._fanout(attempt)


class _BlockFiller:
    """Reads a byte stream into block-major [B, k*S] strip buffers: row
    bi holds one whole erasure block's stream bytes followed by split()'s
    zero pad. Shared by the native-engine encode drivers so their
    tail/empty-object handling cannot drift.

    The block-major layout is what makes the downstream stages zero-copy
    and GIL-free: the md5 stage digests ONE contiguous block-sized view
    per block (hashlib releases the GIL for large updates), the GF
    encode runs as a [B, k, S] batch, and shard j's bitrot chunks sit at
    a fixed stride (row[j*S:(j+1)*S]) for the strided-hash + writev
    writers. readinto sources fill each block row with one scatter-free
    copy; others take the read()+copy fallback. A short trailing read
    comes back as `tail` bytes for the host encode_data path; a
    zero-byte stream yields the empty-object sentinel tail b"" exactly
    once."""

    def __init__(self, erasure: Erasure, src, batch_blocks: int):
        self.src = src
        self.batch_blocks = batch_blocks
        self.k = erasure.data_blocks
        self.shard = erasure.shard_size()
        self.block_size = erasure.block_size
        self.row = self.k * self.shard  # block_size + zero pad
        self.can_readinto = hasattr(src, "readinto")
        self.eof = False
        self.produced = False  # anything (blocks or tail) handed out yet

    def _fill_row(self, row: np.ndarray) -> int:
        """Read one block directly into row[:block_size]; returns bytes
        read (0 on EOF, < block_size on a short tail read)."""
        block_size = self.block_size
        if self.can_readinto:
            view = memoryview(row)[:block_size]
            got = 0
            while got < block_size:
                n = self.src.readinto(view[got:])
                if not n:
                    break
                got += n
            return got
        b = _read_full(self.src, block_size)
        if b:
            row[: len(b)] = np.frombuffer(b, dtype=np.uint8)
        return len(b)

    def fill(self, buf: np.ndarray) -> tuple[int, bytes | None]:
        """Fill up to batch_blocks block rows of `buf`; returns
        (nb, tail). Sets self.eof when the source is exhausted."""
        from ..pipeline.buffers import copy_add

        nb = 0
        tail: bytes | None = None
        block_size = self.block_size
        while nb < self.batch_blocks:
            row = buf[nb]
            got = self._fill_row(row)
            if got < block_size:
                self.eof = True
                if got or (not nb and not self.produced):
                    # copy-ok: put.tail_copy
                    tail = row[:got].tobytes() if got else b""
                    copy_add("put.tail_copy", got)
                break
            row[block_size:] = 0  # split's zero pad (buffers recycle)
            nb += 1
        if nb or tail is not None:
            self.produced = True
        copy_add("put.source_read",
                 nb * block_size + (len(tail) if tail else 0))
        return nb, tail


def encode_stream(erasure: Erasure, src, writers: list, quorum: int,
                  batch_blocks: int = 8, telemetry: str = "put") -> int:
    """Read the full stream, erasure-encode, fan out to bitrot writers.

    Returns total bytes consumed (ref Erasure.Encode,
    cmd/erasure-encode.go:73-109).

    Every engine runs on the staged pipeline (pipeline/executor.py):
    source-read ∥ md5 (delegated from TeeMD5Reader into its own stage)
    ∥ GF encode ∥ bitrot-frame+shard-write run as overlapped stages
    over pooled strip buffers, with bounded queues for backpressure and
    first-error cancellation. `telemetry` labels the per-stage counters
    ("put", "multipart", ...) on the metrics endpoint.
    """
    writer = ParallelWriter(writers, quorum)
    shard = erasure.shard_size()
    want_digests = any(
        getattr(w, "device_hashable", False) for w in writers if w is not None
    )
    engine = registry.select_engine(shard, erasure.total_shards,
                                    codec_id=erasure.codec_id)
    # One span over the whole stream, labelled by the driver that ran.
    with _spans.span("stream") as sp:
        if engine == "native":
            # Host-native engine: the batched strip path (one GFNI encode
            # + one framing call per shard per batch).
            from ..pipeline import workers as _workers

            wpool = (_workers.armed()
                     if registry.supports(erasure.codec_id, "worker")
                     else None)
            if wpool is not None:
                # Worker-pool path: the per-batch GF encode + strided
                # digests run in a child process over a shared-memory
                # strip — the main interpreter's GIL stays free for
                # fill/writev/commit, which is what lets N concurrent
                # clients scale.
                sp.relabel("native_workers")
                return _encode_stream_native_workers(
                    erasure, src, writer, batch_blocks, telemetry, wpool
                )
            sp.relabel("native_pipelined")
            return _encode_stream_native_pipelined(
                erasure, src, writer, batch_blocks, telemetry
            )
        sp.relabel("batched_pipelined")
        return _encode_stream_batched_pipelined(
            erasure, src, writer, batch_blocks, want_digests, engine,
            telemetry, sp
        )


# Process-wide H2D stage of the device engine (it is stateless): PUTs
# reuse it instead of constructing one per stream.
_HOST_FEED = HostFeed()


def _gather_batches(src, block_size: int, batch_blocks: int):
    """Yield (full_blocks, tail) gathers for the batched driver: up
    to batch_blocks full byte blocks per item, plus the short trailing
    read as `tail` (b"" is the empty-object sentinel, emitted exactly
    once; None when the stream ended on a block boundary).
    _BlockFiller is its strip-layout counterpart."""
    eof = False
    produced = False
    while not eof:
        bufs: list[bytes] = []
        while len(bufs) < batch_blocks:
            b = _read_full(src, block_size)
            if len(b) < block_size:
                eof = True
                if b or (not produced and not bufs):
                    bufs.append(b)  # short tail / empty-object sentinel
                break
            bufs.append(b)
        if not bufs:
            break
        produced = True
        full = [b for b in bufs if len(b) == block_size]
        tail = next((b for b in bufs if len(b) < block_size), None)
        yield (full, tail)


def _encode_stream_batched_pipelined(erasure: Erasure, src,
                                     writer: ParallelWriter,
                                     batch_blocks: int, want_digests: bool,
                                     engine: str, telemetry: str,
                                     sp=_spans.NULL) -> int:
    """Pipelined driver for the device/numpy engines: read → pack →
    host-feed (double-buffered H2D staging, device_engine.HostFeed) →
    fused dispatch → flush+write as overlapped stages. The H2D transfer
    of batch N+1 proceeds while the MXU computes batch N and the host
    writes batch N-1 — device feeding is no longer serialized on any
    single host thread."""
    from ..pipeline import SKIP, Pipeline, Stage, shared_pool

    block_size = erasure.block_size
    k = erasure.data_blocks
    shard = erasure.shard_size()
    md5_update = None
    if hasattr(src, "delegate_hashing"):
        src, md5_update = src.delegate_hashing()
    # Capacity covers the max in-flight window (one buffer per stage +
    # one per queue + the feeder's) so steady state never drops a
    # buffer past the freelist and re-faults it next batch.
    pool = shared_pool(
        ("blocks", batch_blocks, k, shard),
        lambda: np.empty((batch_blocks, k * shard), dtype=np.uint8),
        capacity=8, name="blocks",
    )
    totals = {"bytes": 0}

    # Post-pack items are mutable lists [buf, data, tail, parity_f,
    # hashes_f] with stable identity, so the executor's drop hook can
    # return an abandoned item's pooled buffer exactly once (pre-pack
    # gather tuples carry no buffer and are ignored by drop).
    def drop(item):
        if isinstance(item, list) and item and item[0] is not None:
            pool.release(item[0])
            item[0] = None

    def md5_stage(item):
        full, tail = item
        for b in full:
            md5_update(b)
        if tail:
            md5_update(tail)
        return item

    def pack(item):
        from ..pipeline.buffers import copy_add

        full, tail = item
        if not full:
            return [None, None, tail, None, None]
        buf = pool.acquire()
        try:
            for bi, b in enumerate(full):
                row = buf[bi]
                row[:block_size] = np.frombuffer(b, dtype=np.uint8)
                row[block_size:] = 0  # split zero pad (buffers recycle)
        except BaseException:
            # Not yet wrapped in an item: invisible to the drop hook.
            pool.release(buf)
            raise
        copy_add("put.pack_copy", len(full) * block_size)
        data = buf[: len(full)].reshape(len(full), k, shard)
        return [buf, data, tail, None, None]

    if engine == "device":
        feed = _HOST_FEED
    elif engine == "mesh":
        # Mesh staging shards the batch over the dp axis (one buffer
        # per dp-group); the feed declines ragged batches, which the
        # codec pads and stages itself.
        from ..parallel.mesh_engine import for_geometry as _mesh_geometry

        feed = _mesh_geometry(erasure.data_blocks, erasure.parity_blocks,
                              erasure.codec_id).host_feed()
    else:
        feed = None

    def h2d(item):
        if item[1] is None or feed is None:
            return item
        item[1] = feed(item[1])
        return item

    def dispatch(item):
        if item[1] is None:
            return item
        item[3], item[4] = erasure.encode_batch_async(
            item[1], with_hashes=want_digests
        )
        return item

    def flush(item):
        buf, data, tail, parity_f, hashes_f = item
        out = 0
        if data is not None:
            # D2H only the parity/hashes; the data shards are still
            # host-resident in the pooled buffer.
            parity, hashes = _to_host(parity_f, hashes_f)
            n = parity.shape[0]
            # One fan-out a batch, not one a block: each drive's task
            # ships its shard's n frames ([digest||chunk] pairs out of
            # the strip buffer, under the device's digests where it made
            # them) in one writev. A fan-out's cost is its sixteen
            # thread hand-overs, whatever they carry (PERF.md §6, PR 28).
            writer.write_frame_batches(
                buf, parity, n, k, erasure.parity_blocks, shard,
                digests=(None if hashes is None
                         else hashes.transpose(1, 0, 2)),
            )
            out += n * block_size
        if buf is not None:
            pool.release(buf)
            item[0] = None
        if tail is not None:
            writer.write(erasure.encode_data(tail))
            out += len(tail)
        totals["bytes"] += out
        return out or SKIP

    def run_inline(item):
        out = None
        try:
            if md5_update is not None:
                md5_stage(item)
            # Bind after each stage so a raise in h2d/dispatch still
            # leaves `out` holding the pooled buffer for drop().
            out = pack(item)
            out = h2d(out)
            out = dispatch(out)
            flush(out)
        finally:
            drop(out)  # no-op when flush released it

    # Single-batch streams gain nothing from a linear pipeline (the one
    # item passes through the stages back-to-back either way): run the
    # stages inline, no thread spin-up. The first gather alone decides
    # — a short gather (partial batch or tail present) means the stream
    # ended inside it, so no second serial read delays the pipeline.
    src_iter = _gather_batches(src, block_size, batch_blocks)
    try:
        first = next(src_iter)
    except StopIteration:
        return 0
    if len(first[0]) < batch_blocks or first[1] is not None:
        sp.relabel("inline")
        run_inline(first)
        return totals["bytes"]

    def source_from_peeked():
        yield first
        yield from src_iter

    stages = []
    if md5_update is not None:
        stages.append(Stage("md5", md5_stage, leaf=True,
                            bytes_of=lambda it: sum(len(b)
                                                    for b in it[0])))
    stages.append(Stage("pack", pack, leaf=True))
    if feed is not None:
        stages.append(Stage(feed.name, h2d,
                            bytes_of=lambda it: it[1].nbytes))
    stages += [
        Stage("dispatch", dispatch),
        Stage("flush-write", flush, bytes_of=int),
    ]
    Pipeline(telemetry, stages, queue_depth=1,
             pools=[pool], drop=drop).run(source_from_peeked())
    return totals["bytes"]


def _encode_stream_native_pipelined(erasure: Erasure, src,
                                    writer: ParallelWriter,
                                    batch_blocks: int,
                                    telemetry: str) -> int:
    """Pipelined strip driver for the host-native engine — the PUT hot
    path on every bench host. Overlapped stages over pooled block-major
    [B, k*S] strip buffers:

        source-read (feeder thread; one contiguous readinto per block)
          → md5 (delegated from TeeMD5Reader; one update per block row)
            → GF encode (native GFNI/SSSE3 [B, k, S] batch, GIL released)
              → frame-write (strided frame digests + writev scatter-
                gather straight from the strip buffer, zero data copies)

    so the md5/encode/frame/write stages, which once ran back-to-back,
    proceed concurrently;
    bounded queues give backpressure against a slow disk, and a write
    failure past quorum cancels the read/encode stages promptly.

    When `src` is a TeeMD5Reader it delegates hashing to a dedicated
    md5 stage that digests the pooled strip buffers directly (in
    stream order, zero copies) — the tee's own per-read snapshot+queue
    handoff measures SLOWER than the hash itself under GIL contention.
    The block-major layout gives that stage ONE contiguous block-sized
    update per block, so hashlib holds the strip for a single GIL-free
    update instead of k per-row slivers."""
    from ..ops import gf_native
    from ..pipeline import Pipeline, Stage, shared_pool

    k = erasure.data_blocks
    m = erasure.parity_blocks
    shard = erasure.shard_size()
    block_size = erasure.block_size
    md5_update = None
    if hasattr(src, "delegate_hashing"):
        src, md5_update = src.delegate_hashing()
    filler = _BlockFiller(erasure, src, batch_blocks)
    # Capacity covers the max in-flight window at queue_depth=1 (one
    # buffer per stage + one per queue + the feeder's) so steady state
    # never drops a buffer past the freelist and re-faults it.
    pool = shared_pool(
        ("blocks-major", k, batch_blocks, shard),
        lambda: np.empty((batch_blocks, k * shard), dtype=np.uint8),
        capacity=8, name="strips",
    )
    totals = {"bytes": 0}

    # Items are LISTS [buf, ...] and the releasing stage nils item[0]
    # after returning the buffer, so the executor's drop hook can return
    # abandoned items' buffers exactly once on error/cancel paths.
    def drop(item):
        if isinstance(item, list) and item and item[0] is not None:
            pool.release(item[0])
            item[0] = None

    # One mutable item list flows through every stage: [buf, nb, tail,
    # parity, tail_blocks]. Identity is preserved end to end, so the
    # buffer is owned by exactly one object and release/drop can nil
    # item[0] without aliasing.
    def fill_acquired(buf):
        """fill() with the acquire undone on a source-read error (client
        disconnect mid-upload) — a buffer not yet wrapped in an item is
        invisible to the executor's drop hook."""
        try:
            return filler.fill(buf)
        except BaseException:
            pool.release(buf)
            raise

    def strips_source():
        while not filler.eof:
            # pool-ok: fill_acquired releases on raise; afterwards the
            # buffer is wrapped in an item owned by the executor's drop
            # hook (released exactly once on stage-raise/cancel/drain)
            buf = pool.acquire()
            nb, tail = fill_acquired(buf)
            if nb == 0:
                pool.release(buf)
                if tail is None:
                    break
                yield [None, 0, tail, None, None]
            else:
                yield [buf, nb, tail, None, None]

    def md5_stage(item):
        # Digest the original stream bytes straight from the block-major
        # strip: row bi's first block_size bytes ARE block bi's stream
        # bytes, so this is one contiguous GIL-releasing update per
        # block — no per-row slivers, no reassembly copy.
        buf, nb, tail = item[0], item[1], item[2]
        for bi in range(nb):
            md5_update(buf[bi, :block_size])
        if tail:
            md5_update(tail)
        return item

    def encode(item):
        buf, nb, tail = item[0], item[1], item[2]
        if nb:
            item[3] = erasure.parity_apply_batch_native(
                buf[:nb].reshape(nb, k, shard)
            )
        item[4] = erasure.encode_data(tail) if tail is not None else None
        return item

    def frame_write(item):
        buf, nb, tail, parity, tail_blocks = item
        out = 0
        if nb:
            writer.write_frame_batches(buf, parity, nb, k, m, shard)
            out += nb * block_size
        # Success path release; on an exception above, the executor's
        # drop hook returns the buffer instead (item[0] still set).
        if buf is not None:
            pool.release(buf)
            item[0] = None
        if tail_blocks is not None:
            writer.write(tail_blocks)
            out += len(tail)
        totals["bytes"] += out
        return out

    # First batch fills on the CALLER's thread. If the whole stream fit
    # in it, a linear pipeline would process the single item through
    # its stages back-to-back anyway — zero overlap to win — so skip
    # the thread spin-up and run the stages inline (keeps small-object
    # PUT latency at the serial driver's level).
    # pool-ok: fill_acquired releases on raise; then the buffer lives in
    # `first`, released by the inline path's finally drop() or handed to
    # the pipeline whose drop hook owns it
    buf0 = pool.acquire()
    nb0, tail0 = fill_acquired(buf0)
    first = [buf0, nb0, tail0, None, None]
    if filler.eof:
        try:
            if nb0 or tail0 is not None:
                if md5_update is not None:
                    md5_stage(first)
                frame_write(encode(first))
            else:
                pool.release(buf0)
                first[0] = None
        finally:
            # lifetime-ok: drop() releases item[0] exactly once and
            # no-ops after the inline path nil'd it above
            drop(first)  # no-op when the inline path released it
        return totals["bytes"]

    def source_from_first():
        yield first
        yield from strips_source()

    stages = []
    if md5_update is not None:
        stages.append(Stage("md5", md5_stage, leaf=True,
                            bytes_of=lambda it: it[1] * block_size))
    stages += [Stage("encode", encode, leaf=True),
               Stage("frame-write", frame_write, bytes_of=int)]
    Pipeline(telemetry, stages, queue_depth=1, pools=[pool],
             drop=drop).run(source_from_first())
    return totals["bytes"]


def _encode_stream_native_workers(erasure: Erasure, src,
                                  writer: ParallelWriter,
                                  batch_blocks: int, telemetry: str,
                                  wpool) -> int:
    """Worker-pool strip driver: the shape of
    _encode_stream_native_pipelined, but the strip buffers are
    SHARED-MEMORY segments (pipeline/workers.ShmStrip) and the encode
    stage ships each batch to a child process that computes GF parity
    AND all k+m shards' frame digests into the same segment
    (gf_native.apply_matrix_batch(out=) + hash_strided_digests(out=)):

        source-read (one contiguous readinto per block, into shm)
          → md5 (delegated; host thread — hashlib releases the GIL)
            → worker encode+digest (child process; parent blocks on
              the pipe reply, GIL released)
              → frame-write (writev straight from the shm segment,
                digests precomputed — zero hashing on the parent)

    Copy accounting is IDENTICAL to the in-process driver (one
    source-read copy per input byte, nothing else): no payload byte
    crosses the pipe, and the parent never re-touches the batch
    beyond the writev scatter list. A worker failure mid-batch
    (WorkerCrashed/WorkerUnavailable) recomputes THAT batch in-process
    from the still-intact shm data — byte-identical output — and
    counts a fallback; the stream never notices."""
    from ..ops import gf_native
    from ..pipeline import Pipeline, Stage
    from ..pipeline import workers as _workers

    k = erasure.data_blocks
    m = erasure.parity_blocks
    shard = erasure.shard_size()
    block_size = erasure.block_size
    md5_update = None
    if hasattr(src, "delegate_hashing"):
        src, md5_update = src.delegate_hashing()
    filler = _BlockFiller(erasure, src, batch_blocks)
    pool = _workers.strip_pool(batch_blocks, k, m, shard)
    totals = {"bytes": 0}

    # Items are LISTS [strip, nb, tail, parity, tail_blocks, digests];
    # the executor's drop hook returns abandoned strips exactly once.
    def drop(item):
        if isinstance(item, list) and item and item[0] is not None:
            pool.release(item[0])
            item[0] = None

    def fill_acquired(strip):
        try:
            return filler.fill(strip.data)
        except BaseException:
            pool.release(strip)
            raise

    def strips_source():
        while not filler.eof:
            # pool-ok: fill_acquired releases on raise; afterwards the
            # strip is wrapped in an item owned by the executor's drop
            # hook (released exactly once on stage-raise/cancel/drain)
            strip = pool.acquire()
            nb, tail = fill_acquired(strip)
            if nb == 0:
                pool.release(strip)
                if tail is None:
                    break
                yield [None, 0, tail, None, None, None]
            else:
                yield [strip, nb, tail, None, None, None]

    def md5_stage(item):
        strip, nb, tail = item[0], item[1], item[2]
        for bi in range(nb):
            md5_update(strip.data[bi, :block_size])
        if tail:
            md5_update(tail)
        return item

    def encode_inprocess(item):
        strip, nb = item[0], item[1]
        item[3] = erasure.parity_apply_batch_native(
            strip.data[:nb].reshape(nb, k, shard)
        )
        item[5] = None  # frame-write hashes in-process

    # Below this, the pipe round-trip costs more than the batch's own
    # encode+hash: 1-block objects stay in-process.
    min_worker_blocks = max(1, 2 * (1 << 20) // max(1, erasure.block_size))

    def encode(item):
        strip, nb, tail = item[0], item[1], item[2]
        if nb:
            if nb < min_worker_blocks:
                encode_inprocess(item)
            else:
                try:
                    wpool.encode_batch(strip, nb, erasure.codec_id)
                    item[3] = strip.parity
                    item[5] = strip.digests
                except (_workers.WorkerCrashed,
                        _workers.WorkerUnavailable):
                    # The shm data region is untouched by a dead
                    # worker: recompute this batch in-process,
                    # byte-identically.
                    wpool.note_fallback()
                    encode_inprocess(item)
        item[4] = erasure.encode_data(tail) if tail is not None else None
        return item

    def frame_write(item):
        strip, nb, tail, parity, tail_blocks, digests = item
        out = 0
        if nb:
            writer.write_frame_batches(strip.data, parity, nb, k, m,
                                       shard, digests=digests)
            out += nb * block_size
        if strip is not None:
            pool.release(strip)
            item[0] = None
        if tail_blocks is not None:
            writer.write(tail_blocks)
            out += len(tail)
        totals["bytes"] += out
        return out

    # Single-batch streams skip the stage-thread spin-up (nothing to
    # overlap) but STILL ship multi-block batches to a worker — the
    # c5-shaped workload (many concurrent few-MiB PUTs) is exactly N
    # single-batch streams, and keeping their encode+hash on the main
    # interpreter is what kept the aggregate flat. encode() owns the
    # worker-vs-inprocess choice either way.
    # pool-ok: fill_acquired releases on raise; then the strip lives in
    # `first`, released by the inline path's finally drop() or handed
    # to the pipeline whose drop hook owns it
    strip0 = pool.acquire()
    nb0, tail0 = fill_acquired(strip0)
    first = [strip0, nb0, tail0, None, None, None]
    if filler.eof:
        try:
            if nb0 or tail0 is not None:
                if md5_update is not None:
                    md5_stage(first)
                frame_write(encode(first))
            else:
                pool.release(strip0)
                first[0] = None
        finally:
            # lifetime-ok: drop() releases item[0] exactly once and
            # no-ops after the inline path nil'd it above
            drop(first)  # no-op when the inline path released it
        return totals["bytes"]

    def source_from_first():
        yield first
        yield from strips_source()

    stages = []
    if md5_update is not None:
        stages.append(Stage("md5", md5_stage, leaf=True,
                            bytes_of=lambda it: it[1] * block_size))
    stages += [Stage("worker-encode", encode),
               Stage("frame-write", frame_write, bytes_of=int)]
    Pipeline(telemetry, stages, queue_depth=1, pools=[pool],
             drop=drop).run(source_from_first())
    return totals["bytes"]


def _read_full(src, n: int) -> bytes:
    from ..pipeline.buffers import copy_add

    first = src.read(n)
    if len(first) == n or not first:
        return first  # common case (BytesIO, files): zero extra copies
    out = bytearray(first)
    while len(out) < n:
        chunk = src.read(n - len(out))
        if not chunk:
            break
        out += chunk
    # Chunked-source fallback (sockets, wrapped readers): the join is
    # a real extra pass over these bytes — counted, never silent.
    copy_add("put.read_join", len(out))
    return bytes(out)  # copy-ok: put.read_join


class ParallelReader:
    """Read >=k shard chunks per block from n readers, escalating to spare
    readers on failure (ref parallelReader, cmd/erasure-decode.go:30-201).

    Python-threaded variant: it fires dataBlocks reads concurrently, and
    each failure triggers the next untried reader, remembering dead ones
    across blocks. Missing-file / corrupt errors are recorded so the caller
    can kick off heal, exactly like the reference's bitrotHeal /
    missingPartsHeal flags."""

    # Blocks fetched per fan-out: one read_chunks + one verify call per
    # reader covers BATCH_BLOCKS blocks, amortizing the per-block task
    # dispatch and file-read cost (the reference amortizes differently —
    # goroutines are ~free; Python's are not).
    BATCH_BLOCKS = 8

    def __init__(self, readers: list, erasure: Erasure, offset: int, total_length: int):
        self.readers = list(readers)
        self.org_readers = readers
        self.data_blocks = erasure.data_blocks
        self.offset = (offset // erasure.block_size) * erasure.shard_size()
        self.shard_size = erasure.shard_size()
        self.shard_file_size = erasure.shard_file_size(total_length)
        self.errs: list = [None] * len(readers)
        self.reader_to_buf = list(range(len(readers)))
        self.saw_missing = False
        self.saw_corrupt = False
        # blocks handed out with a data shard missing: the caller has to
        # rebuild each before it can join the data
        self.degraded_blocks = 0
        self._queue: list = []  # prefetched per-block buf lists
        self._blocks_wanted = None  # caller hint: don't prefetch past it

    def prefer_readers(self, prefer: list[bool]):
        """Move preferred (typically local) readers to the front
        (ref cmd/erasure-decode.go:63-88)."""
        if len(prefer) != len(self.org_readers):
            return
        readers = list(self.org_readers)
        r2b = list(range(len(readers)))
        nxt = 0
        for i, ok in enumerate(prefer):
            if not ok or readers[i] is None:
                continue
            if i == nxt:
                nxt += 1
                continue
            readers[nxt], readers[i] = readers[i], readers[nxt]
            r2b[nxt], r2b[i] = r2b[i], r2b[nxt]
            nxt += 1
        self.readers = readers
        self.reader_to_buf = r2b

    def set_blocks_wanted(self, n: int):
        """Bound prefetching to the caller's remaining block count so a
        small range-GET never reads batch-extra chunks."""
        self._blocks_wanted = n

    def read(self) -> list:
        """One block's worth: returns newBuf list (len n) with >= dataBlocks
        filled entries, or raises quorum error. Internally fetches
        BATCH_BLOCKS blocks per reader fan-out."""
        if not self._queue:
            self._fetch_batch()
        return self._queue.pop(0)

    def _fetch_batch(self):
        # Per-block chunk lengths for this batch (tail chunk is short).
        n_max = self.BATCH_BLOCKS
        if self._blocks_wanted is not None:
            n_max = max(1, min(n_max, self._blocks_wanted))
        lengths: list[int] = []
        off = self.offset
        for _ in range(n_max):
            shard = min(self.shard_size, self.shard_file_size - off)
            if shard <= 0:
                break
            lengths.append(shard)
            off += shard
        if not lengths:
            self._queue.append([None] * len(self.readers))
            return

        cv = threading.Condition()
        results: dict[int, list] = {}  # buf_idx -> per-block chunks
        state = {"next": 0, "active": 0, "closed": False,
                 "progress": time.monotonic()}
        inflight: set[int] = set()   # reader idx currently mid-read
        abandoned: set[int] = set()  # hedged past; late results dropped
        parked: dict[int, object] = {}  # abandoned idx -> its reader

        def try_next() -> int | None:
            with cv:
                i = state["next"]
                if i >= len(self.readers):
                    return None
                state["next"] += 1
                return i

        def run(i: int):
            while i is not None:
                rr = self.readers[i]
                if rr is None:
                    i = try_next()
                    continue
                buf_idx = self.reader_to_buf[i]
                with cv:
                    # closed-check and inflight-entry are one atomic
                    # step: once the batch is closed, a worker that has
                    # not yet STARTED its read must not touch the reader
                    # — the caller is about to advance the offset, and a
                    # late read against the new offset with this batch's
                    # lengths would interleave two reads on one stream.
                    # Its untouched reader stays in the rotation.
                    if state["closed"]:
                        return
                    inflight.add(i)
                try:
                    chunks = rr.read_chunks(self.offset, lengths)
                except Exception as exc:  # noqa: BLE001 - classified below
                    with cv:
                        inflight.discard(i)
                        if i in abandoned:
                            abandoned.discard(i)
                            parked.pop(i, None)  # failed late: dropped
                            _io_compensator.released()
                            cv.notify_all()
                            return
                    if isinstance(exc, ErrFileNotFound):
                        self.saw_missing = True
                    elif isinstance(exc, ErrFileCorrupt):
                        self.saw_corrupt = True
                    if self.saw_missing or self.saw_corrupt:
                        # Byte-flow ledger: the stream is degraded from
                        # this instant — the shared op-tag holder flips
                        # to get-degraded, reclassifying every
                        # remaining byte in every serving thread.
                        _ioflow.retag_degraded()
                    self.org_readers[buf_idx] = None
                    self.readers[i] = None
                    self.errs[i] = exc
                    i = try_next()
                    continue
                with cv:
                    inflight.discard(i)
                    if i in abandoned:
                        abandoned.discard(i)
                        _io_compensator.released()
                        # The late read still completed THIS batch's
                        # schedule, so the reader's stream position is
                        # exactly the next batch's offset: if no further
                        # batch has advanced past it, the slow-but-alive
                        # reader REJOINS the rotation instead of forcing
                        # reconstruction for the rest of the stream.
                        rr2 = parked.pop(i, None)
                        if (rr2 is not None and self.readers[i] is None
                                and getattr(rr2, "_curr", None)
                                == self.offset):
                            self.readers[i] = rr2
                            self.errs[i] = None
                    else:
                        results[buf_idx] = chunks
                        state["progress"] = time.monotonic()
                    cv.notify_all()
                return

        def worker(i: int):
            try:
                run(i)
            finally:
                with cv:
                    state["active"] -= 1
                    cv.notify_all()

        first = []
        for _ in range(self.data_blocks):
            i = try_next()
            if i is not None:
                first.append(i)
        from ..observability import carry as _obs_carry

        # Reader threads carry the caller's trace (disk-op and
        # worker-verify spans) and byte-flow op tag (shard-read
        # bytes) so both attribute to this request.
        bound_worker = _obs_carry(worker)
        with cv:
            state["active"] = len(first)
        for i in first:
            _io_pool.submit(bound_worker, i)
        hedge_s = ROBUST.hedge_delay_s
        deadline = time.monotonic() + ROBUST.long_op_deadline_s
        last_hedge = 0.0
        state["progress"] = time.monotonic()
        t_span0 = time.monotonic_ns()
        # The wait is on the profiler's clock as well: the span is
        # recorded below, with the bookkeeping after the wait in it.
        with _spans.twin("fanout", "shard-read-wait"), cv:
            while len(results) < self.data_blocks:
                if (state["active"] == 0
                        and state["next"] >= len(self.readers)):
                    break  # everyone finished/failed; nothing to try
                now = time.monotonic()
                if now >= deadline:
                    break
                # STALL-based hedging: fire only when no result has
                # arrived for a full hedge window (a batch that is
                # merely slower than hedge_delay but making steady
                # progress must not pay read amplification).
                fire_at = max(state["progress"], last_hedge) + hedge_s
                if now >= fire_at:
                    # A preferred shard is stalled: dispatch the next
                    # untried (parity) reader instead of blocking on
                    # it (hedged read; the erasure-decoding dual of
                    # proceeding once any k of n shards arrive).
                    last_hedge = now
                    j = try_next()
                    if j is not None:
                        state["active"] += 1
                        record_stat("hedged_reads_total")
                        # Event mark: the hedge decision on this
                        # request's timeline (span dual of the
                        # hedged_reads_total aggregate).
                        _spans.record("fanout", f"hedge #{j}", 0)
                        _io_pool.submit(bound_worker, j)
                    continue
                cv.wait(min(fire_at, deadline) - now)
            # Close the batch: workers that have not started their
            # read exit at the closed-check, readers untouched.
            # Readers still MID-read are abandoned: their stream is
            # parked on THIS batch's offsets, so reusing them next
            # batch would interleave two reads on one stream. Drop
            # them from the rotation — slow is not missing, so no
            # heal hint, and a late result is simply discarded. Each
            # abandoned worker still pins a pool thread until its
            # read returns; compensate the pool ceiling meanwhile.
            state["closed"] = True
            for j in list(inflight):
                abandoned.add(j)
                inflight.discard(j)
                _io_compensator.parked()
                if self.errs[j] is None:
                    self.errs[j] = ErrDiskOpTimeout(
                        f"shard reader {j} abandoned past hedge"
                    )
                # Parked, not destroyed: if its in-flight read
                # completes while the stream position still lines up
                # with the rotation, the reader rejoins (see run()).
                parked[j] = self.readers[j]
                self.readers[j] = None
                _spans.record("fanout", f"straggler-detach #{j}", 0)
        # One span per reader fan-out: results-arrival wait + the
        # hedge/abandon bookkeeping above.
        _spans.record("fanout", "shard-read-wait",
                      time.monotonic_ns() - t_span0)

        if len(results) < self.data_blocks:
            err = reduce_read_quorum_errs(
                self.errs, OBJECT_OP_IGNORED_ERRS, self.data_blocks
            )
            raise err if err else ErrErasureReadQuorum()

        if any(i not in results for i in range(self.data_blocks)):
            self.degraded_blocks += len(lengths)
        for t in range(len(lengths)):
            new_buf: list = [None] * len(self.org_readers)
            for buf_idx, chunks in results.items():
                new_buf[buf_idx] = chunks[t]
            self._queue.append(new_buf)
        self.offset += sum(lengths)
        if self._blocks_wanted is not None:
            self._blocks_wanted -= len(lengths)


def _release_readers(readers: list, path: str) -> None:
    """The end of a read stream. Pooled shm ring slots go back to their
    pool (parked fan-out threads defer their own slot's release), and
    what the bitrot readers verified is published, one increment a
    stream: bitrot_verified_bytes_total{path}."""
    verified = 0
    for r in readers:
        if hasattr(r, "release_buffers"):
            r.release_buffers()
        verified += getattr(r, "verified_bytes", 0)
    if verified:
        registry.note_read("bitrot_verified_bytes_total", verified,
                           path=path)


def decode_stream(erasure: Erasure, writer, readers: list, offset: int,
                  length: int, total_length: int,
                  prefer: list[bool] | None = None,
                  telemetry: str = "get") -> tuple[int, Exception | None]:
    """Read k-of-n shards, reconstruct as needed, write the byte range
    [offset, offset+length) to `writer`.

    Returns (bytes_written, heal_hint) where heal_hint is ErrFileNotFound /
    ErrFileCorrupt if some source failed but the read succeeded — the
    caller queues a heal, like cmd/erasure-object.go:324-338.
    (ref Erasure.Decode, cmd/erasure-decode.go:205-283)

    Past two blocks the loop runs on the staged pipeline
    (pipeline/executor.py): the shard read and bitrot verify of what
    comes next and the rebuild of what was read overlap the client
    write, with bounded queues so a slow client applies backpressure
    instead of buffering the object in memory. The device and the mesh
    engine move whole reader batches through it (`fused`: one rebuild
    dispatch a batch), the host engines blocks (`pipelined`) or, with
    the worker pool armed, batches on one thread (`workers`).
    """
    if offset < 0 or length < 0 or offset + length > total_length:
        raise ErrInvalidArgument("bad range")
    if length == 0:
        return 0, None

    reader = ParallelReader(readers, erasure, offset, total_length)
    if prefer is not None and len(prefer) == len(readers):
        reader.prefer_readers(prefer)

    block_size = erasure.block_size
    start_block = offset // block_size
    end_block = (offset + length) // block_size
    # Per-block (offset, length) geometry, precomputed so the serial and
    # pipelined drivers consume the identical schedule; its length also
    # bounds the reader's prefetch so a small range-GET reads no extra
    # chunks.
    geoms: list[tuple[int, int]] = []
    for block in range(start_block, end_block + 1):
        if start_block == end_block:
            block_offset = offset % block_size
            block_length = length
        elif block == start_block:
            block_offset = offset % block_size
            block_length = block_size - block_offset
        elif block == end_block:
            block_offset = 0
            block_length = (offset + length) % block_size
        else:
            block_offset = 0
            block_length = block_size
        if block_length == 0:
            break
        geoms.append((block_offset, block_length))
    reader.set_blocks_wanted(len(geoms))

    bytes_written = 0
    heal_hint: Exception | None = None

    def note_heal() -> None:
        nonlocal heal_hint
        if reader.saw_missing and heal_hint is None:
            heal_hint = ErrFileNotFound("shard missing during read")
        if reader.saw_corrupt and heal_hint is None:
            heal_hint = ErrFileCorrupt("bitrot during read")

    # <=2 blocks: read-ahead can overlap at most one handoff — not
    # worth the per-request thread spin-up (the small-object/range-GET
    # fast path stays identical to the serial driver).
    # Shard loss is only discovered at read time (a destroyed part file
    # still yields a non-None reader that fails on its first fetch), so
    # there is no up-front healthy/degraded split to route on: every
    # driver takes healthy and degraded blocks as they come.
    engine = registry.select_engine(erasure.shard_size(),
                                    erasure.total_shards,
                                    codec_id=erasure.codec_id)
    wpool = None
    if engine == "native" and registry.supports(erasure.codec_id, "worker"):
        from ..pipeline import workers as _workers

        wpool = _workers.armed()
    with _spans.span("stream") as sp:
        try:
            if engine in ("device", "mesh") and len(geoms) > 2:
                # Same fused driver for both accelerator engines, as in
                # heal_stream; only the codec differs (one chip vs the
                # mesh). It keeps reader batches in flight, so the
                # readers' recycled rings stay off, as for `pipelined`.
                if engine == "mesh":
                    from ..parallel.mesh_engine import for_geometry
                else:
                    from .device_engine import for_geometry

                codec = for_geometry(erasure.data_blocks,
                                     erasure.parity_blocks,
                                     erasure.codec_id)
                sp.relabel("fused")
                bytes_written = _decode_stream_fused(
                    erasure, writer, reader, geoms, note_heal, codec,
                    telemetry
                )
            elif (wpool is not None and len(geoms) > 2
                  and _worker_read_profitable(erasure, readers)):
                # Worker serving path (ISSUE 11): bitrot verification runs
                # in the pool via the readers' shm rings, and degraded
                # blocks batch per failure pattern into worker reconstruct
                # dispatches over pooled shm strips — the main
                # interpreter's GIL stays free for shard reads and client
                # writes, which is what lets N concurrent GETs coexist
                # with the PUT load. Serial batch consumption (write
                # before next fetch) makes the recycled rings safe. The
                # profitability gate keeps small-shard streams on the
                # pipelined branch below: there the verify offload never
                # engages, so serializing would trade the stage-thread
                # read/write overlap for nothing.
                for r in readers:
                    if hasattr(r, "reuse_buffers"):
                        r.reuse_buffers()
                sp.relabel("workers")
                bytes_written = _decode_stream_workers(
                    erasure, writer, reader, geoms, note_heal, wpool
                )
            elif len(geoms) <= 2:
                # Serial consumption drains every batch's views before the
                # next reader fan-out, so the bitrot readers may recycle
                # their read buffers (readinto a private ring, no fresh
                # bytes per fetch). The pipelined branch below keeps
                # several batches in flight and must NOT enable this.
                for r in readers:
                    if hasattr(r, "reuse_buffers"):
                        r.reuse_buffers()
                sp.relabel("serial")
                for block_offset, block_length in geoms:
                    bufs = reader.read()
                    note_heal()
                    erasure.decode_data_blocks(bufs)
                    bytes_written += _write_data_blocks(
                        writer, bufs, erasure.data_blocks, block_offset,
                        block_length
                    )
            else:
                from ..pipeline import Pipeline, Stage

                sp.relabel("pipelined")

                def decode(gb):
                    geom, bufs = gb
                    erasure.decode_data_blocks(bufs)
                    return gb

                pipe = Pipeline(telemetry, [
                    Stage("shard-read", lambda geom: (geom, reader.read())),
                    Stage("decode", decode, bytes_of=lambda gb: gb[0][1]),
                ], queue_depth=2)
                # The client write stays on the CALLER's thread — response
                # framing and socket state must not move across threads.
                for (block_offset, block_length), bufs in pipe.results(geoms):
                    note_heal()
                    bytes_written += _write_data_blocks(
                        writer, bufs, erasure.data_blocks, block_offset,
                        block_length
                    )
        finally:
            _release_readers(readers, "get")

    if reader.degraded_blocks:
        registry.note_read("get_reconstructed_blocks_total",
                           reader.degraded_blocks)
    if bytes_written != length:
        raise ErrLessData(f"wrote {bytes_written}, want {length}")
    return bytes_written, heal_hint


# A block of a reader batch that the fused decode driver rebuilds on the
# host: the ragged tail, whose short shards fit no batch.
_HOST_BLOCK = ("host",)


def _decode_stream_fused(erasure: Erasure, writer, reader, geoms: list,
                         note_heal, codec, telemetry: str) -> int:
    """Fused decode driver for the GET path of the device and the mesh
    engine: the unit of a rebuild is the reader's batch, not the block.
    `codec` is device_engine.DeviceCodec or mesh_engine.MeshCodec; both
    speak reconstruct_async.

    Three threads, as `pipelined` has them: a `shard-read` stage hands
    on whole reader batches (ParallelReader.BATCH_BLOCKS blocks a
    fan-out), a `rebuild` stage gathers the survivors of consecutive
    degraded blocks that share a failure pattern into one [B, k, S]
    array (the one host copy, counted) and dispatches it, and the
    caller's thread waits for the rebuilt rows and writes to the
    client. So the fetch of batch N+1 and the rebuild of batch N
    overlap the client write of batch N-1, for healthy streams too.
    Healthy blocks pass through untouched and open no device span; the
    ragged tail block is rebuilt on the host; a pattern that changes
    inside a batch closes the run. Every block is written from the
    reader's own buffers, the lost rows from the device's output, in
    stream order."""
    from ..pipeline import Pipeline, Stage
    from ..pipeline.buffers import copy_add
    from ..utils.errors import ErrShardSize, ErrTooFewShards

    k = erasure.data_blocks
    shard = erasure.shard_size()

    def pattern(bufs):
        """None for a healthy block, _HOST_BLOCK for the ragged tail,
        else (the k survivors to read, the data shards to rebuild)."""
        present = tuple(
            i for i, b in enumerate(bufs) if b is not None and len(b)
        )
        missing_data = tuple(i for i in range(k) if i not in present)
        if not missing_data:
            return None
        if len(present) < k:
            raise ErrTooFewShards(
                f"{len(present)} shards present, need {k}"
            )
        blen = len(bufs[present[0]])
        for i in present:
            if len(bufs[i]) != blen:
                raise ErrShardSize("present shards differ in size")
        return (present[:k], missing_data) if blen == shard else _HOST_BLOCK

    def fetch(chunk):
        return chunk, [reader.read() for _ in chunk]

    def rebuild(item):
        chunk, blocks = item
        keys = [pattern(bufs) for bufs in blocks]
        runs = []  # (first block, one past the last, targets, future)
        i = 0
        while i < len(blocks):
            key, j = keys[i], i + 1
            if key is _HOST_BLOCK:
                erasure.decode_data_blocks(blocks[i])
            elif key is not None:
                while j < len(blocks) and keys[j] == key:
                    j += 1
                present, targets = key
                src = np.empty((j - i, k, shard), dtype=np.uint8)
                for row, bufs in enumerate(blocks[i:j]):
                    for r_i, si in enumerate(present):
                        src[row, r_i] = np.frombuffer(
                            memoryview(bufs[si]), dtype=np.uint8
                        )
                copy_add("get.fused_gather", src.nbytes)
                fut, _ = codec.reconstruct_async(src, present, targets,
                                                 with_hashes=False)
                runs.append((i, j, targets, fut))
            i = j
        return chunk, blocks, runs

    # An item is a reader batch, so a queue holds one: a batch read
    # ahead and one rebuilt ahead of the client write are the overlap
    # there is to have, and a stream never holds more than five.
    pipe = Pipeline(telemetry, [
        Stage("shard-read", fetch),
        Stage("rebuild", rebuild,
              bytes_of=lambda out: sum(ln for _, ln in out[0])),
    ], queue_depth=1)
    nb = ParallelReader.BATCH_BLOCKS
    bytes_written = 0
    # The client write stays on the CALLER's thread — response framing
    # and socket state must not move across threads.
    for chunk, blocks, runs in pipe.results(
            geoms[at:at + nb] for at in range(0, len(geoms), nb)):
        note_heal()
        for i, j, targets, fut in runs:
            rebuilt = _to_host(fut)  # D2H started at dispatch
            for bi, bufs in enumerate(blocks[i:j]):
                for t_i, t in enumerate(targets):
                    bufs[t] = rebuilt[bi, t_i]
        for (off, ln), bufs in zip(chunk, blocks):
            bytes_written += _write_data_blocks(writer, bufs, k, off, ln)
    return bytes_written


def _worker_read_profitable(erasure: Erasure, readers: list) -> bool:
    """Whether the worker GET driver can beat the pipelined one for
    this stream: the shards must carry the streaming default algorithm
    (legacy-algo objects can never verify in a worker) AND a reader's
    per-batch framed read must clear the verify-offload floor, so
    healthy blocks (the common case) get GIL-free verification in
    exchange for the lost stage-thread overlap. Otherwise the offload
    never engages and the pipelined branch's shard-read ∥ decode ∥
    client-write overlap wins."""
    from .bitrot import BitrotAlgorithm, StreamingBitrotReader

    for r in readers:
        if r is None:
            continue
        if getattr(r, "_algo", None) is not BitrotAlgorithm.HIGHWAYHASH256S:
            return False
        break  # one object, one algorithm
    phys = ParallelReader.BATCH_BLOCKS * (erasure.shard_size() + 32)
    return phys >= StreamingBitrotReader.WORKER_VERIFY_MIN


def _decode_stream_workers(erasure: Erasure, writer, reader, geoms: list,
                           note_heal, wpool) -> int:
    """Worker decode driver for the GET path: consecutive degraded
    blocks sharing one failure pattern gather into a pooled shm strip
    (survivor rows into the data region — the only copy, counted) and
    reconstruct as ONE worker batch (gf reconstruct matrix + native
    apply in a child interpreter; zero payload over the pipe). Healthy
    blocks write straight through in stream order. A worker failure
    mid-batch recomputes THAT batch in-process from the intact shm
    survivors via the same erasure.decode_data_blocks math — byte-
    identical output."""
    from ..pipeline import workers as _workers
    from ..pipeline.buffers import copy_add
    from ..utils.errors import ErrShardSize, ErrTooFewShards

    k = erasure.data_blocks
    m = erasure.parity_blocks
    shard = erasure.shard_size()
    n_shards = erasure.total_shards
    pool = _workers.strip_pool(ParallelReader.BATCH_BLOCKS, k, m, shard)
    bytes_written = 0
    # One in-flight gather batch: [strip, nb, present, targets, geoms].
    state = {"strip": None, "nb": 0, "present": (), "targets": (),
             "geoms": []}

    def flush() -> None:
        nonlocal bytes_written
        strip, nb = state["strip"], state["nb"]
        if strip is None:
            return
        present, targets = state["present"], state["targets"]
        src = strip.recon_src(nb)
        try:
            try:
                wpool.recon_batch(strip, nb, present, targets,
                                  digests=False, op="decode",
                                  codec=erasure.codec_id)
                rebuilt = strip.recon_out(nb, len(targets))
            except (_workers.WorkerCrashed, _workers.WorkerUnavailable):
                # The shm survivors are intact: recompute this batch
                # in-process through the SAME codec path the serial
                # driver uses — byte-identical by construction.
                wpool.note_fallback("decode")
                rebuilt = None
            for bi, (off, ln) in enumerate(state["geoms"]):
                bufs: list = [None] * n_shards
                for row, si in enumerate(present):
                    bufs[si] = src[bi, row]
                if rebuilt is None:
                    erasure.decode_data_blocks(bufs)
                else:
                    for t_i, t in enumerate(targets):
                        bufs[t] = rebuilt[bi, t_i]
                bytes_written += _write_data_blocks(writer, bufs, k, off,
                                                    ln)
        finally:
            state.update(strip=None, nb=0, geoms=[])
            pool.release(strip)

    try:
        for off, ln in geoms:
            bufs = reader.read()
            note_heal()
            present = tuple(
                i for i, b in enumerate(bufs) if b is not None and len(b)
            )
            missing_data = tuple(
                i for i in range(k) if i not in set(present)
            )
            if not missing_data:
                # Healthy block: no reconstruction; drain so client
                # writes stay strictly in stream order.
                flush()
                bytes_written += _write_data_blocks(writer, bufs, k, off,
                                                    ln)
                continue
            if len(present) < k:
                raise ErrTooFewShards(
                    f"{len(present)} shards present, need {k}"
                )
            blen = len(bufs[present[0]])
            for i in present:
                if len(bufs[i]) != blen:
                    raise ErrShardSize("present shards differ in size")
            if blen != shard:
                # Ragged tail block: host reconstruction, in order.
                flush()
                erasure.decode_data_blocks(bufs)
                bytes_written += _write_data_blocks(writer, bufs, k, off,
                                                    ln)
                continue
            key = (present[:k], missing_data)
            if state["strip"] is not None and key != (state["present"],
                                                      state["targets"]):
                flush()  # failure pattern changed mid-stream
            if state["strip"] is None:
                # pool-ok: released by flush()'s finally, or by the
                # driver-level finally below if the stream errors
                # mid-gather
                state["strip"] = pool.acquire()
                state["present"], state["targets"] = key
            # Gather the k survivor rows out of the reader's recycled
            # ring into the shm strip — the batch outlives further
            # fetches, which reuse the ring's buffers (the worker-plane
            # dual of get.fused_gather).
            src = state["strip"].recon_src(ParallelReader.BATCH_BLOCKS)
            row = state["nb"]
            for r_i, si in enumerate(key[0]):
                src[row, r_i] = np.frombuffer(
                    memoryview(bufs[si]), dtype=np.uint8
                )
                copy_add("get.worker_hold", blen)
            state["nb"] += 1
            state["geoms"].append((off, ln))
            if state["nb"] >= ParallelReader.BATCH_BLOCKS:
                flush()
        flush()
    finally:
        if state["strip"] is not None:
            pool.release(state["strip"])
            state["strip"] = None
    return bytes_written


def _write_data_blocks(dst, blocks: list, data_blocks: int,
                       offset: int, length: int) -> int:
    """Concatenate data shards, honoring offset/length within the block
    (ref writeDataBlocks, cmd/erasure-utils.go:41-114)."""
    if length == 0:
        return 0
    total = sum(len(blocks[i]) for i in range(data_blocks))
    if total < length:
        raise ErrLessData(f"block holds {total}, need {length}")
    write = length
    written = 0
    for i in range(data_blocks):
        b = blocks[i]
        if offset >= len(b):
            offset -= len(b)
            continue
        if not isinstance(b, (bytes, bytearray, memoryview)):
            # copy-ok: get.reassemble — no-op view for the contiguous
            # decode outputs; a real copy (non-contiguous row) counts.
            fixed = np.ascontiguousarray(b)
            if fixed is not b:
                from ..pipeline.buffers import copy_add

                copy_add("get.reassemble", fixed.nbytes)
            b = fixed
        chunk = memoryview(b)[offset:]
        offset = 0
        if write < len(chunk):
            chunk = chunk[:write]
        # memoryview straight through — a bytes() copy here is a full
        # extra pass over every GET byte; all sinks (sockets, files,
        # transform writers) accept the buffer protocol.
        dst.write(chunk)
        written += len(chunk)
        write -= len(chunk)
        if write <= 0:
            break
    # Logical (payload-level) bytes served to the client — the
    # denominator of the degraded-GET read-amplification series.
    _ioflow.logical(written)
    return written


def heal_stream(erasure: Erasure, writers: list, readers: list,
                part_size: int, telemetry: str = "heal"):
    """Reconstruct a part onto stale-disk writers: decode every block from
    the surviving readers and write ONLY the missing shards, with write
    quorum 1 (ref Erasure.Heal, cmd/erasure-lowlevel-heal.go:28-48).

    `writers` has one entry per shard position; non-None entries are the
    stale disks to fill.

    Past two blocks the host engines run the loop on the staged
    pipeline: shard reads of block N+1 and GF reconstruction of block N
    overlap the stale-disk writes of block N-1, so heal throughput is
    bounded by the slowest stage rather than their sum."""
    targets = [i for i, w in enumerate(writers) if w is not None]
    if not targets:
        return
    reader = ParallelReader(readers, erasure, 0, part_size)
    total_blocks = (
        (part_size + erasure.block_size - 1) // erasure.block_size
        if part_size > 0 else 0
    )
    reader.set_blocks_wanted(total_blocks)

    def write_targets(shards) -> None:
        from ..pipeline.buffers import copy_add

        for t_i, t in enumerate(targets):
            # copy-ok: heal.shard_copy
            chunk = np.asarray(shards[t_i]).tobytes()
            copy_add("heal.shard_copy", len(chunk))
            writers[t].write(chunk)

    engine = registry.select_engine(erasure.shard_size(),
                                    erasure.total_shards,
                                    codec_id=erasure.codec_id)
    with _spans.span("stream") as sp:
        try:
            if engine in ("device", "mesh") and total_blocks:
                # Same fused reconstruct+digest driver for both accelerator
                # engines; only the codec differs (one chip vs the mesh).
                if engine == "mesh":
                    from ..parallel.mesh_engine import for_geometry
                else:
                    from .device_engine import for_geometry

                codec = for_geometry(erasure.data_blocks,
                                     erasure.parity_blocks,
                                     erasure.codec_id)
                sp.relabel("heal_fused")
                return _heal_stream_fused(erasure, writers, reader, targets,
                                          total_blocks, codec)

            if (engine == "native" and total_blocks > 2
                    and len(targets) <= erasure.parity_blocks):
                from ..pipeline import workers as _workers

                wpool = (_workers.armed()
                         if registry.supports(erasure.codec_id, "worker")
                         else None)
                if wpool is not None:
                    # Worker heal driver (ISSUE 11): per-failure-pattern
                    # batch reconstruct + re-digest in a child interpreter
                    # over pooled shm strips, bitrot verification of the
                    # survivor reads in the pool too — the native-engine
                    # counterpart of the fused device/mesh heal.
                    sp.relabel("heal_workers")
                    return _heal_stream_workers(erasure, writers, reader,
                                                targets, total_blocks, wpool)

            if total_blocks <= 2:
                # Serial heal consumes (reconstructs + copies) each batch
                # before the next fan-out: safe to recycle the readers'
                # buffers.
                for r in readers:
                    if hasattr(r, "reuse_buffers"):
                        r.reuse_buffers()
                sp.relabel("heal_serial")
                for _ in range(total_blocks):
                    bufs = reader.read()
                    write_targets(erasure.reconstruct_targets(bufs, targets))
                return
            from ..pipeline import Pipeline, Stage

            sp.relabel("heal_pipelined")
            pipe = Pipeline(telemetry, [
                Stage("shard-read", lambda _i: reader.read()),
                Stage("reconstruct",
                      lambda bufs: erasure.reconstruct_targets(bufs, targets)),
            ], queue_depth=2)
            for shards in pipe.results(range(total_blocks)):
                write_targets(shards)
        finally:
            _release_readers(readers, "heal")


# Blocks per fused heal-reconstruction dispatch; matches the read-side
# prefetch (ParallelReader.BATCH_BLOCKS) so one device batch consumes
# exactly one reader fan-out.
_DEVICE_HEAL_BATCH = 8


def _heal_stream_fused(erasure: Erasure, writers: list, reader,
                       targets: list[int], total_blocks: int,
                       codec) -> None:
    """Fused heal driver: batches of surviving-shard blocks ship as one
    [B, k, S] fused dispatch that rebuilds the stale shards AND their
    bitrot digests (same single-dispatch + donated-buffer + async-D2H
    treatment as the encode path). `codec` is either the single-chip
    device engine (device_engine.DeviceCodec) or the mesh engine
    (parallel/mesh_engine.MeshCodec) — both speak reconstruct_async.
    The dispatch of batch N overlaps the stale-disk writes of batch N-1;
    a ragged tail block (short shard) falls back to the host
    reconstruction, exactly like the encode drivers' tail path."""
    from ..pipeline.buffers import copy_add

    k = erasure.data_blocks
    shard = erasure.shard_size()
    # Device digests frame the target writers' chunks only when every
    # target speaks the fused-digest protocol (HH256S streaming writers).
    want_digests = all(
        getattr(writers[t], "device_hashable", False) for t in targets
    )
    # Batches are copied out of the reader's buffers at gather time, so
    # the recycled readinto ring is safe even with dispatches in flight.
    for r in reader.readers:
        if hasattr(r, "reuse_buffers"):
            r.reuse_buffers()

    pending = None  # (rebuilt_future, digests_future)

    def flush(p) -> None:
        from ..pipeline.buffers import copy_add

        rebuilt, digs = _to_host(*p)  # D2H already started at dispatch
        for bi in range(rebuilt.shape[0]):
            for t_i, t in enumerate(targets):
                w = writers[t]
                # copy-ok: heal.shard_copy
                chunk = rebuilt[bi, t_i].tobytes()
                copy_add("heal.shard_copy", len(chunk))
                if digs is not None and hasattr(w, "write_with_digest"):
                    # copy-ok: meta (32-byte digest)
                    w.write_with_digest(chunk, digs[bi, t_i].tobytes())
                else:
                    w.write(chunk)

    batch: list = []
    batch_present: tuple = ()

    def dispatch_batch() -> None:
        nonlocal pending, batch
        if not batch:
            return
        src = np.stack(batch)
        out = codec.reconstruct_async(src, batch_present, tuple(targets),
                                      with_hashes=want_digests)
        batch = []
        if pending is not None:
            flush(pending)  # overlap: batch N computes while N-1 writes
        pending = out

    from ..utils.errors import ErrShardSize, ErrTooFewShards

    for _ in range(total_blocks):
        bufs = reader.read()
        present = tuple(
            i for i, b in enumerate(bufs) if b is not None and len(b)
        )
        # Same typed validation as the host reconstruct_targets path: a
        # truncated shard or sub-quorum survivor set must classify as an
        # erasure error, not a raw numpy shape failure.
        if len(present) < k:
            raise ErrTooFewShards(
                f"{len(present)} shards present, need {k}"
            )
        blen = len(bufs[present[0]])
        for i in present:
            if len(bufs[i]) != blen:
                raise ErrShardSize("present shards differ in size")
        if blen != shard:
            # Ragged tail: drain the device ring in order, then host-path
            # the short block.
            dispatch_batch()
            if pending is not None:
                flush(pending)
                pending = None
            shards = erasure.reconstruct_targets(list(bufs), targets)
            for t_i, t in enumerate(targets):
                # copy-ok: heal.shard_copy
                chunk = np.asarray(shards[t_i]).tobytes()
                copy_add("heal.shard_copy", len(chunk))
                writers[t].write(chunk)
            continue
        if batch and present[:k] != batch_present:
            # Survivor set changed mid-stream (a reader died): close the
            # old pattern's batch; the next one compiles/caches its own.
            dispatch_batch()
        batch_present = present[:k]
        batch.append(np.stack([
            np.frombuffer(memoryview(bufs[i]), dtype=np.uint8)
            for i in present[:k]
        ]))
        if len(batch) >= _DEVICE_HEAL_BATCH:
            dispatch_batch()
    dispatch_batch()
    if pending is not None:
        flush(pending)


def _heal_stream_workers(erasure: Erasure, writers: list, reader,
                         targets: list[int], total_blocks: int,
                         wpool) -> None:
    """Worker heal driver: per-failure-pattern batches of survivor
    blocks gather straight into a pooled shm strip and ONE worker task
    rebuilds the stale shards AND their bitrot frame digests
    (_child_recon: the same cached reconstruction matrix + native
    kernels as the in-process path, plus hash_strided_digests over the
    rebuilt region). The parent then frames [digest||chunk] writes
    without hashing a byte. A worker failure recomputes the batch
    in-process via erasure.reconstruct_targets — byte-identical,
    because the frame digest is a pure function of the chunk."""
    from ..pipeline import workers as _workers
    from ..pipeline.buffers import copy_add
    from ..utils.errors import ErrShardSize, ErrTooFewShards

    k = erasure.data_blocks
    m = erasure.parity_blocks
    shard = erasure.shard_size()
    n_shards = erasure.total_shards
    targets_t = tuple(targets)
    # Worker digests frame the target writers' chunks only when every
    # target speaks the fused-digest protocol (HH256S streaming
    # writers) — same gate as the device/mesh heal.
    want_digests = all(
        getattr(writers[t], "device_hashable", False) for t in targets
    )
    # Batches are copied out of the readers' rings at gather time and
    # written before the next fan-out: the recycled rings are safe.
    for r in reader.readers:
        if hasattr(r, "reuse_buffers"):
            r.reuse_buffers()
    pool = _workers.strip_pool(_DEVICE_HEAL_BATCH, k, m, shard)
    state = {"strip": None, "nb": 0, "present": ()}

    def flush() -> None:
        strip, nb = state["strip"], state["nb"]
        if strip is None:
            return
        present = state["present"]
        src = strip.recon_src(nb)
        try:
            digs = None
            try:
                wpool.recon_batch(strip, nb, present, targets_t,
                                  digests=want_digests, op="heal",
                                  codec=erasure.codec_id)
                rebuilt = strip.recon_out(nb, len(targets_t))
                if want_digests:
                    digs = strip.recon_digests(nb, len(targets_t))
            except (_workers.WorkerCrashed, _workers.WorkerUnavailable):
                # Survivors intact in shm: recompute in-process through
                # the same codec path the serial heal uses. write()
                # re-hashes each chunk, producing the identical
                # [digest||chunk] framing the worker would have.
                wpool.note_fallback("heal")
                rebuilt = None
            for bi in range(nb):
                if rebuilt is None:
                    bufs: list = [None] * n_shards
                    for row, si in enumerate(present):
                        bufs[si] = src[bi, row]
                    shards = erasure.reconstruct_targets(bufs, targets)
                    for t_i, t in enumerate(targets):
                        # copy-ok: heal.shard_copy
                        chunk = np.asarray(shards[t_i]).tobytes()
                        copy_add("heal.shard_copy", len(chunk))
                        writers[t].write(chunk)
                    continue
                for t_i, t in enumerate(targets):
                    w = writers[t]
                    # copy-ok: heal.shard_copy
                    chunk = rebuilt[bi, t_i].tobytes()
                    copy_add("heal.shard_copy", len(chunk))
                    if digs is not None and hasattr(w,
                                                    "write_with_digest"):
                        # copy-ok: meta (32-byte digest)
                        w.write_with_digest(chunk, digs[t_i, bi].tobytes())
                    else:
                        w.write(chunk)
        finally:
            state.update(strip=None, nb=0)
            pool.release(strip)

    try:
        for _ in range(total_blocks):
            bufs = reader.read()
            present = tuple(
                i for i, b in enumerate(bufs) if b is not None and len(b)
            )
            # Same typed validation as the host reconstruct_targets path.
            if len(present) < k:
                raise ErrTooFewShards(
                    f"{len(present)} shards present, need {k}"
                )
            blen = len(bufs[present[0]])
            for i in present:
                if len(bufs[i]) != blen:
                    raise ErrShardSize("present shards differ in size")
            if blen != shard:
                # Ragged tail: drain in order, then host-path the short
                # block (write() hashes it — identical framing).
                flush()
                shards = erasure.reconstruct_targets(list(bufs), targets)
                for t_i, t in enumerate(targets):
                    # copy-ok: heal.shard_copy
                    chunk = np.asarray(shards[t_i]).tobytes()
                    copy_add("heal.shard_copy", len(chunk))
                    writers[t].write(chunk)
                continue
            if state["strip"] is not None and present[:k] != state[
                    "present"]:
                flush()  # survivor set changed mid-stream
            if state["strip"] is None:
                # pool-ok: released by flush()'s finally, or by the
                # driver-level finally below on a mid-gather error
                state["strip"] = pool.acquire()
                state["present"] = present[:k]
            src = state["strip"].recon_src(_DEVICE_HEAL_BATCH)
            row = state["nb"]
            for r_i, si in enumerate(state["present"]):
                src[row, r_i] = np.frombuffer(
                    memoryview(bufs[si]), dtype=np.uint8
                )
                copy_add("heal.worker_hold", blen)
            state["nb"] += 1
            if state["nb"] >= _DEVICE_HEAL_BATCH:
                flush()
        flush()
    finally:
        if state["strip"] is not None:
            pool.release(state["strip"])
            state["strip"] = None
