"""Hot-object serving tier (ISSUE 19): single-flight decode coalescing
plus an erasure-aware decoded-block cache.

The problem: millions of clients stampeding a few hot keys each pay a
full shard-read + erasure decode + bitrot verify per GET, even though
every one of them wants the same bytes. This module makes repeat
traffic skip erasure entirely, in three coordinated moves:

- **single-flight coalescing** — the first GET of a (bucket, object,
  version-id, etag) becomes the *leader*: it runs the one decode
  pipeline (under the one read-admission slot). Concurrent GETs of the
  same identity attach as *followers* and slice their byte ranges off
  the leader's decoded blocks; they take NO decode slot (the admission
  governor counts them as coalesced bypasses instead). The follower
  attach window is bounded: a late joiner past the stream head falls
  back to its own read — it never blocks the leader, and the leader
  never waits for a slow follower.

- **decoded-block cache** — post-decode, post-verify payload blocks
  held in memory, keyed (bucket, object, version-id, etag, part,
  block-index), byte quota + watermark GC in the spirit of
  `object/cache.py` DiskCache. A warm hit performs ZERO shard reads —
  provable on the byte-flow ledger, whose dir="read" class covers only
  shard/payload bytes (the per-GET quorum metadata read stays, and
  stays classified "rmeta": coherence comes from FRESH metadata, not
  from hope). A hit for a stale version is structurally impossible:
  the key embeds the version-id and etag read under the object lock on
  THIS request, so an overwrite (new etag/version) or delete (404 at
  the metadata phase) can never alias into old blocks. Write paths
  (put/delete/heal/transition/metadata update) still invalidate
  eagerly so dead versions stop holding quota.

- **range coalescing** — a ranged GET against a hot key expands to a
  block-aligned fetch: the leader decodes whole blocks (the unit the
  erasure geometry already produces), caches them, and slices the
  client's exact range. Adjacent small ranges against the same key
  then coalesce into one decode — the followers/hits slice per-client.
  The one retained copy per decoded byte is counted on the copy budget
  as `get.cache_hold`.

Admission is fed by the PR11 hot-bucket sketch: a key is tier-hot only
when its bucket is tracked in `ioflow.hot_buckets()` AND the key's own
cumulative served bytes (a second space-saving sketch, per key) exceed
MTPU_READTIER_HOT_BYTES. Cold keys take the unmodified legacy path —
`MTPU_READTIER=off` (re-read per GET) is therefore byte-inert.

Note the plane dependency: with the byte-flow ledger disarmed
(MTPU_IOFLOW=0) the bucket sketch is empty, so the tier admits nothing
and GETs flow the legacy path unchanged.
"""

from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict

from ..observability import ioflow as _ioflow
from ..observability import spans as _spans
from ..pipeline.buffers import copy_add
from ..utils.errors import ErrOperationTimedOut
from ..utils.fanout import decode_slot as _decode_slot

# Series contributed to the metrics_v2 descriptor catalog.
READTIER_DESCRIPTORS: list[tuple[str, str, str]] = [
    ("readtier_hits_total", "counter",
     "GETs served entirely from the decoded-block cache (zero shard "
     "reads)"),
    ("readtier_misses_total", "counter",
     "Tier-hot GETs that led a decode pipeline (cache cold or partial)"),
    ("readtier_coalesced_total", "counter",
     "Follower GETs served off another request's in-flight decode"),
    ("readtier_evictions_total", "counter",
     "Decoded blocks evicted by the byte-quota watermark GC or "
     "write-path invalidation"),
    ("readtier_bytes_held", "gauge",
     "Decoded payload bytes currently held by the block cache"),
    ("readtier_leader_crashes_total", "counter",
     "Single-flight leader decodes that died mid-stream (followers "
     "fall back when unstarted, fail clean otherwise)"),
]

# Watermark GC target, in the spirit of object/cache.py DiskCache:
# crossing the quota purges LRU blocks down to this fraction of it.
LOW_WATERMARK = 0.8


def enabled() -> bool:
    """Re-read per GET (the `tier()` accessor) so tests/operators flip
    the tier live — same convention as MTPU_IOFLOW / MTPU_TRACE."""
    return os.environ.get("MTPU_READTIER", "on").lower() not in (
        "0", "off", "false", "no"
    )


class _BlockRef:
    """One decoded payload block of the aligned fetch plan: its cache
    key and its extent in object byte space."""

    __slots__ = ("key", "obj_start", "size")

    def __init__(self, key: tuple, obj_start: int, size: int):
        self.key = key
        self.obj_start = obj_start
        self.size = size


class _FellBehind(Exception):
    """Follower-internal: the needed block left the attach window (or
    the flight ended without producing it)."""


class _Flight:
    """One in-flight leader decode that followers attach to.

    The leader publishes completed blocks into a bounded window (the
    attach window, MTPU_READTIER_WINDOW blocks behind the stream head)
    and never waits on followers; a follower that needs a block older
    than the window falls behind (-> cache, else fallback/clean fail).
    """

    __slots__ = ("seq_of", "window", "head", "floor", "done", "error",
                 "cv", "_w")

    def __init__(self, plan: list[_BlockRef], window: int):
        self.cv = threading.Condition()
        # Immutable after construction: block key -> publish sequence.
        self.seq_of = {ref.key: i for i, ref in enumerate(plan)}
        self.window: dict[int, bytearray] = {}   # guarded-by: cv
        self.head = -1                           # guarded-by: cv
        self.floor = 0                           # guarded-by: cv
        self.done = False                        # guarded-by: cv
        self.error: Exception | None = None      # guarded-by: cv
        self._w = max(1, window)

    def publish(self, seq: int, data) -> None:
        """Leader: block `seq` is decoded+verified; advance the head
        and evict past the attach window. Never blocks."""
        with self.cv:
            self.window[seq] = data
            self.head = seq
            floor = max(self.floor, seq - self._w + 1)
            for s in range(self.floor, floor):
                self.window.pop(s, None)
            self.floor = floor
            self.cv.notify_all()

    def finish(self, error: Exception | None) -> None:
        with self.cv:
            self.done = True
            self.error = error
            self.cv.notify_all()

    def fetch(self, seq: int, timeout_s: float):
        """Follower: wait for block `seq`. Raises _FellBehind when the
        block left the window (or will never come), ErrOperationTimedOut
        when the leader stalls past `timeout_s` (e.g. wedged on its own
        slow client), or the leader's error verbatim when it crashed
        before producing the block."""
        deadline = time.monotonic() + timeout_s
        with self.cv:
            while True:
                if seq <= self.head:
                    data = self.window.get(seq)
                    if data is None:
                        raise _FellBehind()
                    return data
                if self.done:
                    if self.error is not None:
                        raise self.error
                    raise _FellBehind()
                left = deadline - time.monotonic()
                if left <= 0:
                    raise ErrOperationTimedOut(
                        "hot-object tier: shared decode stalled"
                    )
                self.cv.wait(left)


class _BlockSink:
    """Writer handed to the leader's decode_stream: cuts the sequential
    payload stream into whole blocks of the precomputed plan geometry,
    retaining each completed block — the ONE copy out of the recycled
    reader ring buffers, counted as `get.cache_hold` — then publishes
    it (flight window + block cache) and slices the leader's own client
    range as blocks complete, so leader latency matches the legacy
    streaming path block for block."""

    __slots__ = ("_plan", "_i", "_buf", "_fill", "_publish", "_writer",
                 "_lo", "_hi")

    def __init__(self, plan: list[_BlockRef], publish, writer,
                 client_offset: int, client_length: int):
        self._plan = plan
        self._i = 0
        self._buf = bytearray(plan[0].size)
        self._fill = 0
        self._publish = publish     # fn(seq, ref, data)
        self._writer = writer
        self._lo = client_offset
        self._hi = client_offset + client_length

    def write(self, data) -> int:
        view = memoryview(data)
        pos, total = 0, len(view)
        while pos < total:
            ref = self._plan[self._i]
            n = min(total - pos, ref.size - self._fill)
            # The retained-copy site: decoded payload leaves the
            # recycled ring exactly once, into the block being held.
            # copy-ok: get.cache_hold
            self._buf[self._fill:self._fill + n] = view[pos:pos + n]
            copy_add("get.cache_hold", n)
            self._fill += n
            pos += n
            if self._fill == ref.size:
                self._complete(ref)
        return total

    def _complete(self, ref: _BlockRef) -> None:
        block, self._buf, self._fill = self._buf, bytearray(0), 0
        self._publish(self._i, ref, block)
        # Slice the leader's own client range off the completed block.
        lo = max(self._lo, ref.obj_start)
        hi = min(self._hi, ref.obj_start + ref.size)
        if lo < hi:
            self._writer.write(
                memoryview(block)[lo - ref.obj_start:hi - ref.obj_start]
            )
        self._i += 1
        if self._i < len(self._plan):
            self._buf = bytearray(self._plan[self._i].size)


class ReadTier:
    """Process-global tier instance: the per-key hotness sketch, the
    decoded-block cache, and the single-flight registry."""

    def __init__(self):
        self.quota = int(os.environ.get(
            "MTPU_READTIER_QUOTA", str(64 << 20)))
        self.hot_bytes = int(os.environ.get(
            "MTPU_READTIER_HOT_BYTES", str(1 << 20)))
        self.window = int(os.environ.get("MTPU_READTIER_WINDOW", "8"))
        topk = int(os.environ.get("MTPU_READTIER_TOPK", "64"))
        self._mu = threading.Lock()
        # Per-key cumulative served bytes (space-saving, same structure
        # as the ioflow bucket sketch, keyed bucket/object).
        self._sketch = _ioflow.SpaceSaving(topk)     # guarded-by: _mu
        # LRU decoded-block cache: key -> block payload.
        self._blocks: "OrderedDict[tuple, bytearray]" = OrderedDict()  # guarded-by: _mu
        # (bucket, object) -> cache keys, for write-path invalidation.
        self._by_object: dict[tuple, set] = {}       # guarded-by: _mu
        self._bytes_held = 0                         # guarded-by: _mu
        self._flights: dict[tuple, _Flight] = {}     # guarded-by: _mu
        # Counters (mirrored by metrics_v2._collect_readtier).
        self.hits_total = 0                          # guarded-by: _mu
        self.misses_total = 0                        # guarded-by: _mu
        self.coalesced_total = 0                     # guarded-by: _mu
        self.evictions_total = 0                     # guarded-by: _mu
        self.leader_crashes_total = 0                # guarded-by: _mu
        self.follower_fallbacks_total = 0            # guarded-by: _mu

    # -- admission ----------------------------------------------------------

    def _hot(self, bucket: str, object_: str, length: int) -> bool:
        with self._mu:
            key = f"{bucket}/{object_}"
            self._sketch.offer(key, length)
            if self._sketch.counts.get(key, 0) <= self.hot_bytes:
                return False
        # Key-level bytes crossed the threshold: confirm against the
        # PR11 hot-bucket sketch (the tier admits only sketch-hot keys;
        # a disarmed ledger keeps the tier inert).
        for entry in _ioflow.hot_buckets():
            if entry["bucket"] == bucket:
                return True
        return False

    # -- the fetch plan -----------------------------------------------------

    @staticmethod
    def _plan(bucket: str, object_: str, fi, erasure,
              offset: int, length: int) -> list[_BlockRef]:
        """Block-aligned cover of object range [offset, offset+length):
        the erasure block grid restarts at every part boundary (each
        part decodes independently), so the plan walks parts exactly
        like the legacy part loop does."""
        bs = erasure.block_size
        etag = fi.metadata.get("etag", "")
        plan: list[_BlockRef] = []
        part_index, part_offset = fi.to_object_part_index(offset)
        part_start = offset - part_offset
        remaining = length
        for p in range(part_index, len(fi.parts)):
            if remaining <= 0:
                break
            part = fi.parts[p]
            part_length = min(part.size - part_offset, remaining)
            first = part_offset // bs
            last = (part_offset + part_length - 1) // bs
            for j in range(first, last + 1):
                size = min(bs, part.size - j * bs)
                key = (bucket, object_, fi.version_id, etag,
                       part.number, j)
                plan.append(_BlockRef(key, part_start + j * bs, size))
            remaining -= part_length
            part_offset = 0
            part_start += part.size
        return plan

    # -- cache primitives (callers hold _mu) --------------------------------

    def _cache_get_locked(self, key: tuple):  # guarded-by: _mu
        data = self._blocks.get(key)
        if data is not None:
            self._blocks.move_to_end(key)
        return data

    def _cache_put_locked(self, ref: _BlockRef, data) -> None:  # guarded-by: _mu
        if ref.size > self.quota:
            return
        if ref.key in self._blocks:
            return  # concurrent leader already admitted this block
        self._blocks[ref.key] = data
        self._by_object.setdefault(
            (ref.key[0], ref.key[1]), set()).add(ref.key)
        self._bytes_held += ref.size
        if self._bytes_held > self.quota:
            self._gc_locked()

    def _gc_locked(self) -> None:  # guarded-by: _mu
        """Purge LRU blocks down to the low watermark (DiskCache's GC
        shape, minus the filesystem)."""
        target = int(self.quota * LOW_WATERMARK)
        while self._bytes_held > target and self._blocks:
            key, data = self._blocks.popitem(last=False)
            self._drop_index_locked(key, len(data))

    def _drop_index_locked(self, key: tuple, size: int) -> None:  # guarded-by: _mu
        self._bytes_held -= size
        self.evictions_total += 1
        obj = (key[0], key[1])
        keys = self._by_object.get(obj)
        if keys is not None:
            keys.discard(key)
            if not keys:
                del self._by_object[obj]

    # -- public surface -----------------------------------------------------

    def invalidate(self, bucket: str, object_: str) -> None:
        """Write-path hook (put/delete/heal/transition/metadata): drop
        every cached block of the object so dead versions stop holding
        quota. Correctness never depends on this — the cache key pins
        (version-id, etag) read fresh per GET."""
        with self._mu:
            for key in list(self._by_object.get((bucket, object_), ())):
                data = self._blocks.pop(key, None)
                if data is not None:
                    self._drop_index_locked(key, len(data))

    def serve(self, objects, bucket: str, object_: str, fi, fis, erasure,
              writer, offset: int, length: int):
        """Try to serve GET range [offset, offset+length) through the
        tier. Returns ("hit"|"coalesced"|"leader", heal_hint) when the
        range was fully written, or None to decline — the caller runs
        the unmodified legacy read and is guaranteed zero bytes were
        written here."""
        if not self._hot(bucket, object_, length):
            return None
        plan = self._plan(bucket, object_, fi, erasure, offset, length)
        if not plan:
            return None
        role, fl, datas = self._decide(plan)
        # Where the tier itself answers (no decode of this request's
        # own), that is a leaf on the request's span tree; a leader's
        # time is its `stream`.
        if role == "hit":
            with _spans.span("readtier", "hit", mirror=True):
                self._slice(plan, datas, writer, offset, length, "hit")
            return ("hit", None)
        if role == "leader":
            hint = self._lead(objects, bucket, object_, fi, fis, erasure,
                              plan, fl, writer, offset, length)
            return ("leader", hint)
        with _spans.span("readtier", "coalesced", mirror=True) as sp:
            served = self._follow(plan, fl, writer, offset, length)
            if served is None:
                sp.relabel("fallback")
            return served

    def _decide(self, plan: list[_BlockRef]):
        """One atomic admission decision: full cache hit, follower
        attach, or leader registration — so two concurrent misses can
        never both lead the same identity."""
        ident = plan[0].key[:4]
        with self._mu:
            datas = [self._cache_get_locked(ref.key) for ref in plan]
            if all(d is not None for d in datas):
                self.hits_total += 1
                return "hit", None, datas
            fl = self._flights.get(ident)
            if fl is not None and all(ref.key in fl.seq_of
                                      for ref in plan):
                return "follower", fl, None
            fl = _Flight(plan, self.window)
            self._flights[ident] = fl
            self.misses_total += 1
            return "leader", fl, None

    # -- serving paths ------------------------------------------------------

    def _slice(self, plan, datas, writer, offset, length,
               kind: str) -> None:
        """Write the client's exact range off whole decoded blocks, and
        account the served bytes: ledger classification + logical bytes
        (these streams never pass _write_data_blocks, which counts the
        legacy path) + the governor's coalesced-bypass counter (no
        decode slot was consumed)."""
        hi_req = offset + length
        for ref, data in zip(plan, datas):
            lo = max(offset, ref.obj_start)
            hi = min(hi_req, ref.obj_start + ref.size)
            if lo < hi:
                writer.write(
                    memoryview(data)[lo - ref.obj_start:hi - ref.obj_start]
                )
        _ioflow.served(kind, length)
        _ioflow.logical(length)
        from ..pipeline.admission import read_governor

        read_governor().note_coalesced()

    def _lead(self, objects, bucket, object_, fi, fis, erasure, plan, fl,
              writer, offset, length):
        """Run the one decode pipeline for this identity: block-aligned
        expanded range, under the one read-admission slot, publishing
        blocks to the flight window + cache as they complete."""
        ident = plan[0].key[:4]
        aligned_lo = plan[0].obj_start
        aligned_hi = plan[-1].obj_start + plan[-1].size

        def publish(seq, ref, data):
            with self._mu:
                self._cache_put_locked(ref, data)
            fl.publish(seq, data)

        sink = _BlockSink(plan, publish, writer, offset, length)
        err: Exception | None = None
        try:
            with _decode_slot():
                hint = objects._decode_range(
                    bucket, object_, fi, fis, erasure, sink,
                    aligned_lo, aligned_hi - aligned_lo,
                )
            return hint
        except BaseException as exc:
            err = exc if isinstance(exc, Exception) else \
                ErrOperationTimedOut("hot-object tier: leader aborted")
            with self._mu:
                self.leader_crashes_total += 1
            raise
        finally:
            with self._mu:
                if self._flights.get(ident) is fl:
                    del self._flights[ident]
            fl.finish(err)

    def _follow(self, plan, fl, writer, offset, length):
        """Slice this GET's range off the shared decode, block by block
        (cache first — the leader admits blocks as it publishes — then
        the flight window). Zero bytes written yet -> any trouble falls
        back to the caller's own read; mid-stream trouble fails clean
        (the server severs the response, never a short 200)."""
        timeout_s = float(
            os.environ.get("MTPU_DECODE_SLOT_DEADLINE_S", "30"))
        hi_req = offset + length
        written = 0
        for ref in plan:
            with self._mu:
                data = self._cache_get_locked(ref.key)
            if data is None:
                try:
                    data = fl.fetch(fl.seq_of[ref.key], timeout_s)
                except _FellBehind:
                    if written == 0:
                        with self._mu:
                            self.follower_fallbacks_total += 1
                        return None
                    raise ErrOperationTimedOut(
                        "hot-object tier: follower fell behind the "
                        "shared decode stream"
                    ) from None
                except Exception:
                    # Leader crashed (its error re-raised verbatim):
                    # unstarted followers retry on their own read.
                    if written == 0:
                        with self._mu:
                            self.follower_fallbacks_total += 1
                        return None
                    raise
            lo = max(offset, ref.obj_start)
            hi = min(hi_req, ref.obj_start + ref.size)
            if lo < hi:
                writer.write(
                    memoryview(data)[lo - ref.obj_start:hi - ref.obj_start]
                )
                written += hi - lo
        with self._mu:
            self.coalesced_total += 1
        _ioflow.served("coalesced", written)
        _ioflow.logical(written)
        from ..pipeline.admission import read_governor

        read_governor().note_coalesced()
        return ("coalesced", None)

    # -- introspection ------------------------------------------------------

    def snapshot(self) -> dict:
        with self._mu:
            return {
                "quota": self.quota,
                "bytes_held": self._bytes_held,
                "blocks": len(self._blocks),
                "flights": len(self._flights),
                "hits_total": self.hits_total,
                "misses_total": self.misses_total,
                "coalesced_total": self.coalesced_total,
                "evictions_total": self.evictions_total,
                "leader_crashes_total": self.leader_crashes_total,
                "follower_fallbacks_total": self.follower_fallbacks_total,
            }


# ---------------------------------------------------------------------------
# process-global instance

_tier: ReadTier | None = None  # guarded-by: _tier_mu
_tier_mu = threading.Lock()


def tier() -> ReadTier | None:
    """The live tier, or None when MTPU_READTIER is off (checked per
    call: flipping the knob takes effect on the next GET)."""
    if not enabled():
        return None
    global _tier
    # guardedby-ok: double-checked fast path — a stale None read just
    # falls through to the locked check; the reference write is atomic
    t = _tier
    if t is None:
        with _tier_mu:
            if _tier is None:
                _tier = ReadTier()
            t = _tier
    return t


def invalidate(bucket: str, object_: str) -> None:
    """Module-level write-path hook: no-op when the tier never armed
    (writes must not pay tier construction)."""
    # guardedby-ok: racy read of an atomically-rebound reference — a
    # tier constructed concurrently starts empty, nothing to drop
    t = _tier
    if t is not None:
        t.invalidate(bucket, object_)


def snapshot() -> dict | None:
    # guardedby-ok: racy read of an atomically-rebound reference
    t = _tier
    return t.snapshot() if t is not None else None


def reset() -> None:
    """Test hook: drop the tier so the next GET re-reads the knobs
    (never called on a serving path)."""
    global _tier
    with _tier_mu:
        _tier = None
