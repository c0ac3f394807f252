"""Background heal services: the MRF (most-recently-failed) drain loop
and the fresh-disk / erasure-set sweep — behavioral parity with the
reference's cmd/erasure-sets.go mrfOperations and cmd/global-heal.go
(healErasureSet). Admin-driven heal sequences (token start/poll/stop,
IO gating, rate limits — cmd/admin-heal-ops.go) live in healseq.py.
"""

from __future__ import annotations

import threading
import time
from collections import deque

from ..utils.errors import ErrObjectNotFound, ErrVersionNotFound

# Drain-rate window: (monotonic_ts, healed) samples per drain pass.
_RATE_WINDOW_S = 300.0


class MRFHealer:
    """Drain per-set MRF queues (partial writes that met quorum but
    failed on some disks) and re-heal those objects
    (ref cmd/erasure.go:75 mrfOpCh + cmd/erasure-sets.go:96)."""

    def __init__(self, object_layer, metrics=None, logger=None):
        self.ol = object_layer
        self.metrics = metrics
        self.logger = logger
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self.healed_total = 0  # guarded-by: _rate_mu
        # Scoreboard: drain samples over the last _RATE_WINDOW_S feed
        # the mrf_drain_rate gauge (entries healed per second).
        self._drained: deque = deque()  # guarded-by: _rate_mu
        self._rate_mu = threading.Lock()
        self._interval_s = 5.0  # rate-span floor; start() overwrites

    def drain_rate_per_s(self) -> float:
        now = time.monotonic()
        with self._rate_mu:
            while self._drained and now - self._drained[0][0] > _RATE_WINDOW_S:
                self._drained.popleft()
            if not self._drained:
                return 0.0
            # Span floored at the drain interval: a single fresh sample
            # scraped milliseconds after the pass must read as "N per
            # interval", not N divided by the scrape latency (a 100x
            # spike that fires rate alerts).
            span = max(self._interval_s, now - self._drained[0][0])
            total = sum(n for _, n in self._drained)
            return total / span

    def _note_drained(self, healed: int) -> None:
        # drain_once() runs from BOTH the healer loop and the disk
        # monitor's reconnect hook (background/monitor.py), so the
        # total shares the rate window's lock.
        with self._rate_mu:
            self.healed_total += healed
            self._drained.append((time.monotonic(), healed))
            while self._drained and (self._drained[-1][0]
                                     - self._drained[0][0]) > _RATE_WINDOW_S:
                self._drained.popleft()

    def drain_once(self) -> int:
        healed = 0
        for pool in getattr(self.ol, "pools", []):
            for es in pool.sets:
                for bucket, object_, version_id, t0 in \
                        es.drain_mrf(with_times=True):
                    try:
                        # remove_dangling: MRF entries include deletes a
                        # straggler disk missed — the leftover copy is
                        # sub-quorum dangling garbage that must be
                        # purged, not requeued forever as a quorum
                        # failure (ref isObjectDangling purge).
                        es.heal_object(bucket, object_, version_id,
                                       remove_dangling=True)
                        healed += 1
                        if self.metrics is not None:
                            self.metrics.inc("mrf_healed_total")
                            self.metrics.inc("heal_objects_total",
                                             trigger="mrf")
                    except (ErrObjectNotFound, ErrVersionNotFound):
                        # Nothing left to heal anywhere reachable (e.g.
                        # a delete that every live disk applied): drop
                        # the entry — requeueing would spin forever.
                        continue
                    except Exception as exc:  # noqa: BLE001 requeue
                        # Original timestamp preserved: a repeatedly
                        # failing repair keeps AGING on the scoreboard
                        # (mrf_oldest_age_seconds) instead of looking
                        # ~drain-interval fresh forever.
                        es.queue_mrf(bucket, object_, version_id,
                                     enqueued_at=t0)
                        if self.metrics is not None:
                            self.metrics.inc("heal_failures_total")
                        if self.logger is not None:
                            self.logger.log_once_if(
                                exc, f"mrf:{bucket}/{object_}"
                            )
        self._note_drained(healed)
        return healed

    def start(self, interval_s: float = 5.0):
        self._interval_s = max(1e-3, interval_s)

        def loop():
            while not self._stop.wait(self._pace_delay(interval_s)):
                self.drain_once()

        self._thread = threading.Thread(target=loop, daemon=True)
        self._thread.start()
        return self

    @staticmethod
    def _pace_delay(interval_s: float) -> float:
        """Stretch the drain interval while the heal pacer reports
        foreground pressure (ISSUE 17): the per-heal pace slot already
        yields inside a pass, but skipping the NEXT pass entirely is
        cheaper than starting one that will spend its time yielding.
        Bounded at 4x so the backlog always keeps draining."""
        from . import healpace

        p = healpace.installed()
        if p is None or not p.cfg.enabled:
            return interval_s
        try:
            if p.pressured():
                return min(4.0 * interval_s, interval_s + 2.0)
        except Exception:  # noqa: BLE001 - pacing must never kill drain
            pass
        return interval_s

    def stop(self):
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=5)


def heal_erasure_set(object_layer, buckets: list[str] | None = None) -> dict:
    """Full sweep heal of every object (fresh-disk path,
    ref cmd/global-heal.go:154 healErasureSet).

    Runs on the staged pipeline (pipeline/executor.py): the listing
    walk (metacache/disk IO) feeds a bounded queue that the heal stage
    (shard reads + reconstruction + writes) drains, so enumerating the
    next listing page overlaps healing the previous one — on a fresh
    disk with millions of objects the sweep is otherwise serialized on
    alternating list/heal IO. Bounded depth keeps at most one page of
    names in memory; a heal failure is counted, never fatal (parity
    with the reference's per-object error tolerance)."""
    from ..pipeline import Pipeline, Stage

    result = {"buckets": 0, "objects": 0, "failed": 0}
    names = buckets
    if names is None:
        names = [
            b.name for b in object_layer.list_buckets()
            if not b.name.startswith(".")
        ]

    def listing():
        for bucket in names:
            result["buckets"] += 1
            marker = ""
            while True:
                res = object_layer.list_objects(
                    bucket, marker=marker, max_keys=1000
                )
                for oi in res.objects:
                    yield (bucket, oi.name)
                if not res.is_truncated:
                    break
                marker = res.next_marker

    def heal_one(item):
        bucket, name = item
        try:
            object_layer.heal_object(bucket, name)
            result["objects"] += 1
        except Exception:  # noqa: BLE001 count failures
            result["failed"] += 1
        return item

    from ..observability import ioflow

    # The sweep's LISTING IO is heal work too (per-object heal re-tags
    # at the heal_object choke point, which is a no-op here — same op).
    with ioflow.tag("heal"):
        Pipeline("heal-sweep", [Stage("heal", heal_one)],
                 queue_depth=64).run(listing())
    return result
