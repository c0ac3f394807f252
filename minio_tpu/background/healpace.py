"""Adaptive heal pacing (ISSUE 17).

A dead-drive heal storm competes with foreground traffic for the same
spindles: every healed byte costs k read bytes (the ledger prices it at
exactly k per stripe at k+m), and an unpaced MRF drain can push
foreground disk p99 past any SLO while it catches up.  The pacer sits
at the single choke point every heal passes through
(``ErasureObjects.heal_object``) and makes heal I/O *borrow* capacity
instead of taking it:

- heals take one of a small fixed pool of tokens (background-class
  budget, independent of the admission governors' foreground slots);
- before taking a token a heal YIELDS while foreground pressure is
  high — pressure is (a) queue depth on either admission governor or
  (b) span-measured foreground disk p99 over a sliding window of the
  last ``max_wait_s`` seconds (a sample older than the longest a heal
  may wait says nothing about what a heal waiting now would relieve:
  once the foreground goes quiet, so does the pressure);
- a heal never waits longer than ``max_wait_s``: at the deadline it is
  granted anyway (counted separately).  Starvation therefore slows the
  MRF drain but can never deadlock it — the backlog always reaches dry.

The pacer holds no lock while a heal runs (the token is a counter, not
a mutex), so it adds no edge to the lock graph and cannot deadlock
against per-object write locks.

Disarm with ``MTPU_HEAL_PACE=off``: every surface becomes an inert
no-op (the right call on 1-core hosts where the serial heal sweep is
already self-pacing).
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from collections import deque
from dataclasses import dataclass

# Op classes that are themselves background work: their disk latencies
# must not count as "foreground pressure" or the pacer would throttle
# heals in response to its own reads.
_BACKGROUND_OPS = ("heal", "scan", "replication", "untagged")

# Below this many samples the p99 estimate is noise; report 0.0 so a
# freshly booted pacer never throttles on a handful of cold-cache ops.
_MIN_P99_SAMPLES = 20


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


@dataclass(frozen=True)
class PaceConfig:
    enabled: bool = True
    tokens: int = 2               # concurrent heal token pool
    queue_high: int = 2           # admission backlog that counts as pressure
    disk_p99_ms: float = 75.0     # foreground disk p99 that counts as pressure
    max_wait_s: float = 2.0       # deadline-grant bound per heal
    yield_s: float = 0.05         # sleep quantum while yielding to pressure
    window: int = 512             # foreground disk latency ring size

    @classmethod
    def from_env(cls) -> "PaceConfig":
        enabled = os.environ.get("MTPU_HEAL_PACE", "on").lower() not in (
            "0", "off", "false", "no"
        )
        return cls(
            enabled=enabled,
            tokens=max(1, _env_int("MTPU_HEAL_PACE_TOKENS", 2)),
            queue_high=max(1, _env_int("MTPU_HEAL_PACE_QUEUE_HIGH", 2)),
            disk_p99_ms=_env_float("MTPU_HEAL_PACE_DISK_P99_MS", 75.0),
            max_wait_s=_env_float("MTPU_HEAL_PACE_MAX_WAIT_MS", 2000.0)
            / 1000.0,
        )


class HealPacer:
    """Token bucket + pressure gate for background heal I/O."""

    def __init__(self, config: PaceConfig | None = None,
                 pressure_probe=None):
        self.cfg = config or PaceConfig.from_env()
        self._cv = threading.Condition()
        self._inflight = 0            # guarded-by: _cv
        self._grants = 0              # guarded-by: _cv
        self._deadline_grants = 0     # guarded-by: _cv
        self._yields = 0              # guarded-by: _cv
        self._throttle_s = 0.0        # guarded-by: _cv
        self._lat_mu = threading.Lock()
        self._lat = deque(maxlen=self.cfg.window)  # guarded-by: _lat_mu
        # Injectable for tests: () -> bool, True while foreground
        # pressure should keep heals yielding.
        self._probe = pressure_probe or self._default_pressure

    # -- foreground latency feed (from storage.diskcheck) -------------

    def note_foreground_disk(self, seconds: float) -> None:
        with self._lat_mu:
            self._lat.append((time.monotonic(), seconds))

    def disk_p99_s(self) -> float:
        horizon = time.monotonic() - self.cfg.max_wait_s
        with self._lat_mu:
            samples = sorted(s for t, s in self._lat if t >= horizon)
        if len(samples) < _MIN_P99_SAMPLES:
            return 0.0
        idx = min(len(samples) - 1, int(0.99 * (len(samples) - 1) + 0.5))
        return samples[idx]

    # -- pressure ------------------------------------------------------

    def _default_pressure(self) -> bool:
        from ..pipeline import admission

        backlog = (admission.governor().backlog()
                   + admission.read_governor().backlog())
        if backlog >= self.cfg.queue_high:
            return True
        return self.disk_p99_s() * 1000.0 >= self.cfg.disk_p99_ms

    def pressured(self) -> bool:
        if not self.cfg.enabled:
            return False
        return bool(self._probe())

    # -- the slot ------------------------------------------------------

    @contextlib.contextmanager
    def heal_slot(self):
        """Take a background heal token, yielding to foreground
        pressure, but ALWAYS granting within max_wait_s (deadline
        grant) — pacing may slow the MRF drain, never wedge it."""
        if not self.cfg.enabled:
            yield
            return
        t0 = time.monotonic()
        deadline = t0 + self.cfg.max_wait_s
        forced = False
        # Phase 1: back off while foreground is pressured.  No lock is
        # held here — heals sleeping in this loop cannot block anyone.
        while self.pressured():
            if time.monotonic() >= deadline:
                forced = True
                break
            with self._cv:
                self._yields += 1
            time.sleep(self.cfg.yield_s)
        # Phase 2: token acquire with the remaining budget.
        with self._cv:
            while self._inflight >= self.cfg.tokens:
                left = deadline - time.monotonic()
                if left <= 0:
                    forced = True
                    break
                self._cv.wait(left)
            self._inflight += 1
            self._grants += 1
            if forced:
                self._deadline_grants += 1
            self._throttle_s += time.monotonic() - t0
        try:
            yield
        finally:
            with self._cv:
                self._inflight -= 1
                self._cv.notify()

    # -- introspection -------------------------------------------------

    def snapshot(self) -> dict:
        with self._cv:
            return {
                "enabled": self.cfg.enabled,
                "tokens": self.cfg.tokens,
                "inflight": self._inflight,
                "grants_total": self._grants,
                "deadline_grants_total": self._deadline_grants,
                "yields_total": self._yields,
                "throttle_seconds_total": round(self._throttle_s, 6),
                "disk_p99_ms": round(self.disk_p99_s() * 1000.0, 3),
            }


# ---------------------------------------------------------------------------
# process-global instance (mirrors pipeline.admission)

_pacer: HealPacer | None = None  # guarded-by: _pacer_mu
_pacer_mu = threading.Lock()


def pacer() -> HealPacer:
    global _pacer
    # guardedby-ok: double-checked fast path — a stale None read just
    # falls through to the locked check; the reference write is atomic
    p = _pacer
    if p is None:
        with _pacer_mu:
            if _pacer is None:
                _pacer = HealPacer()
            p = _pacer
    return p


def reconfigure(config: PaceConfig | None = None) -> HealPacer:
    """Swap the process pacer (tests; scenario runs). In-flight heals
    hold the old instance's token and release against it — safe while
    heals are running."""
    global _pacer
    with _pacer_mu:
        _pacer = HealPacer(config or PaceConfig.from_env())
        return _pacer


def reset() -> None:
    """Drop the process pacer (scenario/test teardown). The next
    ``pacer()`` call lazily rebuilds from the environment."""
    global _pacer
    with _pacer_mu:
        _pacer = None


def installed() -> HealPacer | None:
    """The live pacer or None — never constructs (metrics collection
    and pressure peeks must not force a pacer into existence)."""
    # guardedby-ok: racy telemetry read of an atomically-bound reference
    return _pacer


def note_disk_op(seconds: float) -> None:
    """Foreground disk latency feed, called from the diskcheck wrap on
    every timed op.  Cheap no-op until a pacer exists and is enabled;
    background-class ops (heal/scan/replication) are filtered so the
    pacer only sees the latency foreground clients experience."""
    # guardedby-ok: racy telemetry read of an atomically-bound reference
    p = _pacer
    if p is None or not p.cfg.enabled:
        return
    from ..observability import ioflow

    if ioflow.current_op() in _BACKGROUND_OPS:
        return
    p.note_foreground_disk(seconds)


# ---------------------------------------------------------------------------
# metrics catalog (collected by observability.metrics_v2)

HEALPACE_DESCRIPTORS = [
    ("heal_pace_tokens", "gauge",
     "Configured background heal token pool size"),
    ("heal_pace_inflight", "gauge",
     "Heal operations currently holding a pace token"),
    ("heal_pace_disk_p99_seconds", "gauge",
     "Sliding-window foreground disk p99 seen by the heal pacer"),
    ("heal_pace_grants_total", "counter",
     "Heal pace tokens granted"),
    ("heal_pace_deadline_grants_total", "counter",
     "Heal pace tokens granted at the max-wait deadline despite "
     "pressure or token exhaustion"),
    ("heal_pace_yields_total", "counter",
     "Heal pacing yield quanta slept due to foreground pressure"),
    ("heal_pace_throttle_seconds_total", "counter",
     "Total seconds heals spent waiting for a pace token"),
]
