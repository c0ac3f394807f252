"""Per-op metrics + disk-id validation + in-band health tracking over
StorageAPI — the analog of the reference's xlStorageDiskIDCheck wrapper
(/root/reference/cmd/xl-storage-disk-id-check.go: every StorageAPI call
is counted + timed per operation, the disk's identity is re-verified so
a swapped/stale disk surfaces as errDiskNotFound, and a diskHealthTracker
latches a hung drive faulty instead of letting it wedge every caller).

The wrapper is a transparent proxy: any StorageAPI implementation (local
or remote) can be wrapped, and callers keep using the same 34-method
surface. Metrics land in the shared registry as
  mtpu_disk_ops_total{op=...,disk=...}
  mtpu_disk_op_errors_total{op=...,disk=...}
  mtpu_disk_op_seconds{op=...}            (histogram)
  mtpu_disk_op_timeouts_total{op=...,disk=...}
  mtpu_disk_guard_inline_total{op=...}
  mtpu_disk_faulty_total{disk=...} / mtpu_disk_readmit_total{disk=...}
mirroring the reference's storageMetric counters
(cmd/xl-storage-disk-id-check.go:33-75).

Health tracking (opt-in via a DiskHealth instance):
- every timed op runs under a per-op wall-clock deadline — a hung NFS
  mount or dying HDD costs the caller at most the deadline, never an
  unbounded stall (ref diskHealthCheck's context deadlines). The op runs
  on a thread of the drive's own executor and the caller waits for it
  with the deadline — unless the caller is itself a QuorumFanout worker
  whose dispatcher waits no longer than that and detaches it
  (utils/fanout.Waited): then the op runs on the worker, and the
  fan-out's detach is what starts the deadline's consequences;
- a bounded per-disk in-flight token budget: once `max_inflight` ops
  are stuck on one disk, further calls fail fast with ErrDiskFaulty
  instead of queueing more threads behind the hang;
- a circuit breaker latching the disk faulty (ErrDiskFaulty) after N
  CONSECUTIVE timeouts, with a background probe that re-admits the
  disk once it answers again (ref errFaultyDisk + the monitor loop).
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as _FutTimeout
from contextlib import contextmanager
from dataclasses import dataclass

from ..utils import parse_duration_s
from ..utils.errors import ErrDiskFaulty, ErrDiskNotFound, ErrDiskOpTimeout
from ..utils.fanout import waited as _waited

# The ops that get counted/timed (the reference enumerates the same set
# as storageMetric constants).
_TIMED_OPS = frozenset({
    "disk_info", "make_vol", "make_vol_bulk", "list_vols", "stat_vol",
    "delete_vol", "list_dir", "walk_dir", "delete_version",
    "delete_versions", "write_metadata", "update_metadata", "read_version",
    "rename_data", "list_versions", "read_file", "append_file",
    "create_file", "read_file_stream", "create_file_writer", "rename_file",
    "check_parts", "check_file", "delete", "verify_file", "stat_info_file",
    "write_all", "read_all",
})

# Ops with inherently longer wall-clock budgets: namespace walks stream
# a whole directory tree, stream opens / file creates may fallocate and
# touch cold metadata (ref the larger deadlines DiskInfo vs WalkDir get
# in xl-storage-disk-id-check.go).
_LONG_OPS = frozenset({
    "walk_dir", "read_file_stream", "create_file_writer", "create_file",
    "verify_file", "list_dir", "list_vols", "delete",
})

# Identity/liveness ops pass through without the disk-id gate (they are
# what the gate itself uses; ref DiskInfo/GetDiskID skip the check too).
_PASSTHROUGH = frozenset({
    "is_online", "is_local", "hostname", "endpoint", "get_disk_id",
    "set_disk_id", "close",
})

_ID_CHECK_INTERVAL_S = 5.0


def _pace_note(elapsed_s: float) -> None:
    """Feed a timed disk-op latency to the heal pacer's foreground
    pressure window (ISSUE 17). Lazy import keeps storage import-light;
    the pacer itself filters background-class ops via the ioflow tag."""
    from ..background import healpace

    healpace.note_disk_op(elapsed_s)

# Byte accounting happens ONLY at the syscall layer of the node that
# owns the disk (storage/local.py, storage/directio.py); the op tag
# crosses the storage-REST wire in a header (distributed/rest.py), so
# remote bytes land once, correctly classified, in the owner's ledger
# — never double-counted at the proxy boundary.


@dataclass
class RobustConfig:
    """Process-wide hung-drive tolerance knobs (config subsystem
    `drive`, config/config.py). One mutable instance (`ROBUST`) is the
    single source the storage wrapper AND the erasure fan-outs read, so
    the deadline a PUT observes and the deadline one disk op gets can't
    drift apart."""

    enabled: bool = True
    op_deadline_s: float = 30.0
    long_op_deadline_s: float = 120.0
    hedge_delay_s: float = 0.15
    straggler_grace_s: float = 2.0
    breaker_threshold: int = 3
    probe_interval_s: float = 5.0
    max_inflight: int = 16


ROBUST = RobustConfig()


def configure_robustness(kvs) -> RobustConfig:
    """Apply the `drive` config subsystem KVS onto the live ROBUST
    instance (env > stored > default resolution already happened in
    Config.get)."""
    ROBUST.enabled = kvs.get("enable", "on") != "off"
    for attr, key, default in (
        ("op_deadline_s", "op_deadline", 30.0),
        ("long_op_deadline_s", "long_op_deadline", 120.0),
        ("hedge_delay_s", "hedge_delay", 0.15),
        ("straggler_grace_s", "straggler_grace", 2.0),
        ("probe_interval_s", "probe_interval", 5.0),
    ):
        setattr(ROBUST, attr,
                parse_duration_s(kvs.get(key, ""), default=default))
    try:
        ROBUST.breaker_threshold = max(1, int(kvs.get("breaker_threshold",
                                                      "3")))
    except ValueError:
        ROBUST.breaker_threshold = 3
    try:
        ROBUST.max_inflight = max(1, int(kvs.get("max_inflight", "16")))
    except ValueError:
        ROBUST.max_inflight = 16
    return ROBUST


@contextmanager
def robust_overrides(**kw):
    """Temporarily override ROBUST fields (tests, admin what-if)."""
    old = {k: getattr(ROBUST, k) for k in kw}
    for k, v in kw.items():
        setattr(ROBUST, k, v)
    try:
        yield ROBUST
    finally:
        for k, v in old.items():
            setattr(ROBUST, k, v)


class DiskHealth:
    """Per-disk health state: in-flight token budget + consecutive-
    timeout circuit breaker (ref diskHealthTracker,
    cmd/xl-storage-disk-id-check.go). Pure state — the deadline
    enforcement and the re-admission probe live in MetricsDisk, which
    holds the disk handle."""

    def __init__(self, endpoint: str = "", config: RobustConfig | None = None):
        self.endpoint = endpoint
        self.cfg = config or ROBUST
        self._lock = threading.Lock()
        self._tokens_cv = threading.Condition(self._lock)
        # _tokens_cv shares _lock's mutex: either name satisfies the
        # guard (Condition(lock) aliasing).
        self._inflight = 0          # guarded-by: _tokens_cv|_lock
        self._consec_timeouts = 0   # guarded-by: _lock
        self._faulty = False        # guarded-by: _lock
        # Totals for gauges/admin (monotonic; registry counters are
        # inc'd at event time by the wrapper).
        self.timeouts_total = 0
        self.latched_total = 0
        self.readmitted_total = 0
        self.rejected_total = 0
        self.last_latch_monotonic = 0.0

    # --- token budget ---

    def acquire(self, timeout_s: float = 0.0) -> bool:
        """Take one in-flight token, WAITING up to timeout_s for one to
        free — healthy burst load (fan-out pools are wider than the
        budget) must queue briefly, not fail. Only when no token frees
        for the whole window (everything in flight is stuck) does this
        reject, and that rejection is itself evidence of a wedged disk."""
        deadline = time.monotonic() + timeout_s
        with self._tokens_cv:
            while self._inflight >= self.cfg.max_inflight:
                left = deadline - time.monotonic()
                if left <= 0:
                    self.rejected_total += 1
                    return False
                self._tokens_cv.wait(left)
            self._inflight += 1
            return True

    def release(self) -> None:
        with self._tokens_cv:
            self._inflight -= 1
            self._tokens_cv.notify()

    @property
    def inflight(self) -> int:
        # guardedby-ok: racy telemetry read — an int snapshot for
        # gauges and caps; staleness costs one extra queue round
        return self._inflight

    # --- breaker ---

    def is_faulty(self) -> bool:
        # guardedby-ok: racy fast-path read — a stale False does one
        # guarded op (deadline still bounds it), a stale True fails
        # fast one op late; both converge next op
        return self._faulty

    def record_ok(self) -> None:
        with self._lock:
            self._consec_timeouts = 0

    def record_timeout(self) -> bool:
        """Count one deadline miss; returns True when this miss LATCHES
        the breaker (caller starts the re-admission probe)."""
        with self._lock:
            self.timeouts_total += 1
            self._consec_timeouts += 1
            if (not self._faulty
                    and self._consec_timeouts >= self.cfg.breaker_threshold):
                self._faulty = True
                self.latched_total += 1
                self.last_latch_monotonic = time.monotonic()
                return True
            return False

    def readmit(self) -> None:
        with self._lock:
            self._faulty = False
            self._consec_timeouts = 0
            self.readmitted_total += 1

    def state(self) -> dict:
        return {
            # guardedby-ok: racy telemetry snapshot for admin/state
            # endpoints — consistency across fields is not promised
            "state": "faulty" if self._faulty else "ok",
            # guardedby-ok: racy telemetry snapshot (see above)
            "inflight": self._inflight,
            "timeouts": self.timeouts_total,
            "latched": self.latched_total,
            "readmitted": self.readmitted_total,
            "rejected": self.rejected_total,
            # guardedby-ok: racy telemetry snapshot (see above)
            "consecutiveTimeouts": self._consec_timeouts,
        }


class _Watch:
    """One guarded op running on a QuorumFanout worker. Nobody waits on
    a future for it; the fan-out's detach (detached) and the call's
    return (ended) race instead, and exactly one of them accounts for
    the op. A detach is no timeout yet — a fan-out leaves a straggler
    one grace after quorum — so it arms a timer for what is left of the
    op's own deadline, and an op still running then is written off as
    the hop's waiter would have written it off."""

    __slots__ = ("_disk", "_op", "_deadline_s", "_due", "_mu", "_over",
                 "_timer")

    def __init__(self, disk: "MetricsDisk", op: str, deadline_s: float):
        self._disk = disk
        self._op = op
        self._deadline_s = deadline_s
        self._due = time.monotonic() + deadline_s
        self._mu = threading.Lock()
        self._over = False   # guarded-by: _mu
        self._timer = None   # guarded-by: _mu

    def detached(self) -> None:
        """The fan-out has walked away. Runs on its dispatcher's thread
        (or this op's own, see Waited.watch); a second call is a no-op."""
        from ..observability import carry as _obs_carry

        with self._mu:
            if self._over or self._timer is not None:
                return
            left = self._due - time.monotonic()
            if left > 0:
                # The caller's byte-flow tag rides along: the pacer
                # filters background ops by it.
                self._timer = threading.Timer(left, _obs_carry(self._expire))
                self._timer.name = "mtpu-dh-watch"
                self._timer.daemon = True
                self._timer.start()
                return
        self._expire()

    def _expire(self) -> None:
        with self._mu:
            if self._over:
                return
            self._over = True
        self._disk._note_timeout(self._op, self._deadline_s)

    def ended(self) -> bool:
        """The call returned. True when it made its deadline, and the
        caller accounts for it; False when it was written off."""
        with self._mu:
            in_time = not self._over
            self._over = True
            timer = self._timer
        if timer is not None:
            timer.cancel()
        return in_time


class MetricsDisk:
    """Transparent StorageAPI proxy adding per-op metrics, periodic
    disk-id re-validation (ref checkDiskStale,
    cmd/xl-storage-disk-id-check.go:404-419) and — when `health` is
    given — per-op deadlines + the faulty-disk circuit breaker."""

    def __init__(self, disk, metrics=None, expected_disk_id: str = "",
                 health: DiskHealth | None = None):
        self._disk = disk
        self._metrics = metrics
        self._expected_id = expected_disk_id
        self._last_check = 0.0
        self._stale = False
        self._health = health
        if health is not None and not health.endpoint:
            try:
                health.endpoint = disk.endpoint()
            except Exception:  # noqa: BLE001 - cosmetic only
                pass
        self._deadline_pool: ThreadPoolExecutor | None = None  # guarded-by: _probe_lock
        self._probe_lock = threading.Lock()
        self._probe_running = False         # guarded-by: _probe_lock
        self._probe_attempt_live = False    # guarded-by: _probe_lock

    # --- identity passthrough ---

    def __getattr__(self, name: str):
        attr = getattr(self._disk, name)
        if name in _PASSTHROUGH or name not in _TIMED_OPS:
            return attr
        wrapped = self._wrap(name, attr)
        # Cache so subsequent lookups skip __getattr__.
        self.__dict__[name] = wrapped
        return wrapped

    def health_info(self) -> dict | None:
        """Health tracker snapshot for admin drive info / metrics-v2
        scrape; None when health tracking is not attached."""
        if self._health is None:
            return None
        return self._health.state()

    @property
    def health(self) -> DiskHealth | None:
        return self._health

    def _wrap(self, op: str, fn):
        from ..observability import spans as _spans

        def call(*args, **kwargs):
            self._check_id()
            h = self._health
            if h is not None and h.cfg.enabled:
                if _spans.current() is None:
                    return self._call_guarded(op, fn, args, kwargs)
                # Per-disk op latency on the request's span timeline —
                # the leaf level of the attribution tree (which DISK a
                # stalled fan-out was actually waiting on); on the
                # profiler's clock under the op alone, so that sixteen
                # drives sum to one row.
                t0s = time.monotonic_ns()
                try:
                    with _spans.twin("disk", op):
                        return self._call_guarded(op, fn, args, kwargs)
                finally:
                    _spans.record(
                        "disk", f"{op}:{self._disk.endpoint()}",
                        time.monotonic_ns() - t0s,
                    )
            # No health tracker, or one switched off: the direct call.
            t0 = time.perf_counter()
            try:
                with _spans.twin("disk", op):
                    out = fn(*args, **kwargs)
            except Exception:
                if self._metrics is not None:
                    self._metrics.inc(
                        "disk_op_errors_total", op=op,
                        disk=self._disk.endpoint(),
                    )
                raise
            finally:
                if self._metrics is not None:
                    self._metrics.inc(
                        "disk_ops_total", op=op, disk=self._disk.endpoint()
                    )
                    self._metrics.observe(
                        "disk_op_seconds", time.perf_counter() - t0, op=op
                    )
                if _spans.current() is not None:
                    _spans.record(
                        "disk", f"{op}:{self._disk.endpoint()}",
                        int((time.perf_counter() - t0) * 1e9),
                    )
                _pace_note(time.perf_counter() - t0)
            return out

        call.__name__ = op
        return call

    # --- deadline + breaker enforcement ---

    def _deadline_for(self, op: str) -> float:
        cfg = self._health.cfg
        return (cfg.long_op_deadline_s if op in _LONG_OPS
                else cfg.op_deadline_s)

    def _pool(self) -> ThreadPoolExecutor:
        # Lazily created per disk; sized to the token budget, so the
        # pool can never queue behind stuck ops (acquire() bounds
        # submissions). One hung disk pins at most max_inflight threads
        # HERE instead of draining the caller's pool — except under a
        # QuorumFanout (_run_on_worker), where the op stays on the
        # fan-out's worker: a hung commit pins that one worker, not it
        # and a thread here, and the fan-out's StragglerCompensator
        # makes the worker up. Creation is double-checked under a lock:
        # two racing first ops must not each build an executor and leak
        # the loser's worker thread.
        # guardedby-ok: double-checked fast path — a stale None read
        # falls through to the locked re-check below
        pool = self._deadline_pool
        if pool is None:
            with self._probe_lock:
                pool = self._deadline_pool
                if pool is None:
                    pool = ThreadPoolExecutor(
                        max_workers=self._health.cfg.max_inflight,
                        thread_name_prefix=(
                            f"mtpu-dh-{self._disk.endpoint()[:16]}"
                        ),
                    )
                    self._deadline_pool = pool
        return pool

    def _call_guarded(self, op: str, fn, args, kwargs):
        h = self._health
        ep = self._disk.endpoint()
        if h.is_faulty():
            # Latched: fail fast until the background probe re-admits
            # (ref errFaultyDisk short-circuit).
            raise ErrDiskFaulty(f"{ep}: circuit open, awaiting probe")
        deadline_s = self._deadline_for(op)
        t0 = time.perf_counter()
        if not h.acquire(timeout_s=deadline_s):
            # No token freed for the WHOLE deadline — everything in
            # flight is stuck. Counted apart from deadline misses: one
            # hung op under load produces MANY rejections, and
            # conflating them would make the timeout rate read orders
            # of magnitude too high.
            if self._metrics is not None:
                self._metrics.inc("disk_inflight_rejected_total",
                                  op=op, disk=ep)
            raise ErrDiskFaulty(
                f"{ep}: {h.cfg.max_inflight} ops in flight for {deadline_s}s"
            )

        mark = _waited()
        if mark is not None and mark.deadline_s <= deadline_s:
            return self._run_on_worker(mark, op, ep, fn, args, kwargs,
                                       deadline_s, t0)

        def run():
            try:
                return fn(*args, **kwargs)
            finally:
                # Token released when the op ACTUALLY finishes, even if
                # the caller abandoned it at the deadline — that is the
                # budget's whole point.
                h.release()

        # Execution gets the FULL deadline from submission — the token
        # wait is bounded separately above. Charging queue time against
        # the execution budget would latch a healthy disk under a
        # burst: late acquirers would time out on ops the disk is
        # executing perfectly normally and feed the breaker.
        fut = self._pool().submit(run)
        try:
            out = fut.result(timeout=deadline_s)
        except _FutTimeout:
            self._note_timeout(op, deadline_s)
            raise ErrDiskOpTimeout(
                f"{op} on {ep} exceeded {deadline_s}s deadline"
            ) from None
        except Exception:
            self._note_done(op, ep, t0, failed=True)
            raise
        self._note_done(op, ep, t0, failed=False)
        return out

    def _run_on_worker(self, mark, op: str, ep: str, fn, args, kwargs,
                       deadline_s: float, t0: float):
        """The guarded op of a QuorumFanout worker, token held: run it
        here. The fan-out waits no longer than this op's deadline and
        then detaches this thread, so a second thread that waits the
        same time guards nothing more. What the hop's timeout arm did
        follows the detach instead (_Watch), and the token still goes
        back only when the call returns."""
        if self._metrics is not None:
            self._metrics.inc("disk_guard_inline_total", op=op)
        watch = _Watch(self, op, deadline_s)
        mark.watch(watch.detached)
        failed = True
        try:
            out = fn(*args, **kwargs)
            failed = False
            return out
        finally:
            mark.unwatch()
            self._health.release()
            if watch.ended():
                self._note_done(op, ep, t0, failed)

    def _note_done(self, op: str, ep: str, t0: float, failed: bool) -> None:
        """An op that returned inside its deadline. Even a FAST failure
        (missing file, bad volume) proves the disk responsive: reset
        the consecutive-timeout streak."""
        self._health.record_ok()
        elapsed = time.perf_counter() - t0
        if self._metrics is not None:
            if failed:
                self._metrics.inc("disk_op_errors_total", op=op, disk=ep)
            self._metrics.inc("disk_ops_total", op=op, disk=ep)
            self._metrics.observe("disk_op_seconds", elapsed, op=op)
        if not failed:
            _pace_note(elapsed)

    def _note_timeout(self, op: str, deadline_s: float) -> None:
        """An op given up at its deadline: feed the breaker, and start
        the probe when this miss latches it."""
        latched = self._health.record_timeout()
        if self._metrics is not None:
            ep = self._disk.endpoint()
            self._metrics.inc("disk_op_timeouts_total", op=op, disk=ep)
            self._metrics.inc("disk_op_errors_total", op=op, disk=ep)
            self._metrics.inc("disk_ops_total", op=op, disk=ep)
            if latched:
                self._metrics.inc("disk_faulty_total", disk=ep)
        if latched:
            self._start_probe()
        # An abandoned op cost its caller the FULL deadline — that
        # is the latency the pacer's pressure window must see.
        _pace_note(deadline_s)

    # --- re-admission probe (ref the monitor's reconnect loop, scoped
    # --- to the breaker: latched -> probed -> re-admitted) ---

    def _start_probe(self):
        with self._probe_lock:
            if self._probe_running:
                return
            self._probe_running = True
        threading.Thread(
            target=self._probe_loop, daemon=True,
            name=f"mtpu-dh-probe-{self._disk.endpoint()[:16]}",
        ).start()

    def _probe_loop(self):
        h = self._health
        try:
            while h.is_faulty():
                time.sleep(h.cfg.probe_interval_s)
                if self._probe_once():
                    h.readmit()
                    if self._metrics is not None:
                        self._metrics.inc(
                            "disk_readmit_total", disk=self._disk.endpoint()
                        )
                    return
        finally:
            with self._probe_lock:
                self._probe_running = False
            # Re-latched between readmit and exit? Restart the probe.
            if h.is_faulty():
                self._start_probe()

    def _probe_once(self) -> bool:
        """One deadline-bounded liveness attempt against the RAW disk.
        At most one attempt thread is in flight: a hung probe must not
        stack a new thread every interval (it is reused — when it
        finally returns, the next probe round reads its verdict)."""
        with self._probe_lock:
            if self._probe_attempt_live:
                return False
            self._probe_attempt_live = True
        done = threading.Event()
        verdict = {"ok": False}

        def attempt():
            try:
                self._disk.disk_info()
                verdict["ok"] = True
            except Exception:  # noqa: BLE001 - still sick
                verdict["ok"] = False
            finally:
                with self._probe_lock:
                    self._probe_attempt_live = False
                done.set()

        threading.Thread(target=attempt, daemon=True,
                         name="mtpu-dh-probe-try").start()
        done.wait(timeout=self._health.cfg.op_deadline_s)
        return verdict["ok"]

    def _check_id(self):
        """Re-verify the wrapped disk still carries the expected id. A
        replaced/reformatted disk changes id → all ops fail DiskNotFound
        until the heal/format machinery re-admits it (ref errDiskStale)."""
        if not self._expected_id:
            return
        now = time.monotonic()
        if self._stale:
            # Latched: every op fails while the id mismatches (ref
            # errDiskStale semantics) — but re-probe once per interval so
            # reinstalling the CORRECT disk self-heals without a process
            # restart.
            if now - self._last_check >= _ID_CHECK_INTERVAL_S:
                self._last_check = now
                if self._disk.get_disk_id() == self._expected_id:
                    self._stale = False
                    return
            raise ErrDiskNotFound(
                f"stale disk: expected id {self._expected_id}"
            )
        if now - self._last_check < _ID_CHECK_INTERVAL_S:
            return
        self._last_check = now
        actual = self._disk.get_disk_id()
        if actual and actual != self._expected_id:
            self._stale = True
            raise ErrDiskNotFound(
                f"disk id changed: have {actual}, want {self._expected_id}"
            )

    def unwrap(self):
        return self._disk

    def __repr__(self):  # pragma: no cover - debug aid
        return f"MetricsDisk({self._disk!r})"
