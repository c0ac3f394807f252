"""Shared fan-out machinery for shard IO: local and remote per-disk work
both go through a thread pool. One module owns the quorum-wait and
straggler-detach protocol and the admission slots, so the writer path
(erasure/streaming.py), the reader path, and the object-layer fanouts
(object/erasure_objects.py, object/metadata.py) can't drift apart."""

from __future__ import annotations

import os
import threading
import time

# Late straggler outcomes discarded after detach: the slot already
# carries its timeout and MRF repairs the shard, but the DROP itself
# must be countable — a drive that persistently finishes-then-fails
# just past the grace window looks healthy in the error columns unless
# its discarded failures are tallied somewhere. Module counters for
# tests; mirrored onto the metrics endpoint when a registry is
# installed (server boot calls set_metrics, same pattern as
# erasure/streaming.py).
LATE_DROPS = {"errors": 0, "results": 0}  # guarded-by: _late_mu
_late_mu = threading.Lock()
_metrics = None


def set_metrics(registry) -> None:
    global _metrics
    _metrics = registry


def _note_late_drop(err) -> None:
    key = "errors" if err is not None else "results"
    with _late_mu:
        LATE_DROPS[key] += 1
    if _metrics is not None:
        _metrics.inc(f"fanout_late_dropped_{key}_total")

# Admission control for the CPU-bound encode+hash+write section of PUT
# and multipart part uploads: at most cpu_count streams run it
# concurrently; excess uploads queue FAIRLY (round-robin across
# clients, per-client in-flight caps), deep queues reject immediately,
# and a queue wait past the deadline returns 503 like the reference's
# maxClients throttle (cmd/handler-api.go:36-78). The policy lives in
# pipeline/admission.AdmissionGovernor; this wrapper exists so every
# encode entry point (PUT, multipart) keeps one call shape.
ENCODE_SLOT_DEADLINE_S = float(
    os.environ.get("MTPU_ENCODE_SLOT_DEADLINE_S", "30")
)


def encode_slot():
    """Bounded fair admission: a slow uploader holding a slot must not
    wedge every other PUT forever — waiters time out to a retriable
    503 (ErrOperationTimedOut), a full queue rejects immediately, and
    one hot client cannot starve the rest (the governor's round-robin
    grant order)."""
    from ..pipeline.admission import governor

    return governor().slot()


def decode_slot():
    """The read-side twin (ISSUE 11): every erasure GET's decode+verify
    section passes the READ governor — its own slot pool (2 per core by
    default), so GET clients get the same per-client caps, round-robin
    fairness, and queue-depth 503s as PUT clients, and neither plane
    can starve the other."""
    from ..pipeline.admission import read_governor

    return read_governor().slot()


def heal_slot():
    """The background-class twin (ISSUE 17): every object heal's
    read+re-encode section takes a token from the heal pacer's small
    background budget — yielding while foreground queue depth or disk
    p99 is high, but always granted within the pace deadline so a
    saturated foreground can slow the MRF drain, never wedge it."""
    from ..background.healpace import pacer

    return pacer().heal_slot()


class Waited:
    """What a QuorumFanout worker's thread carries while attempt(i)
    runs: "my caller waits `deadline_s` for me and then detaches me".
    Code under attempt(i) that would otherwise put a thread of its own
    between itself and a hang (the drive guard, storage/diskcheck.py)
    reads it with waited() and runs on this thread instead; what it
    must learn of the detach it registers with watch()."""

    __slots__ = ("deadline_s", "_detached", "_on_detach")

    def __init__(self, deadline_s: float):
        self.deadline_s = deadline_s
        self._detached = False
        self._on_detach = None

    def watch(self, on_detach) -> None:
        """Worker side: call on_detach() when the fan-out walks away —
        now, if it already has (an attempt goes on after its detach).
        on_detach may be called twice in that race; it must not mind."""
        self._on_detach = on_detach
        if self._detached:
            on_detach()

    def unwatch(self) -> None:
        self._on_detach = None

    def detach(self) -> None:
        """Fan-out side, once, after its wait gave this worker up."""
        self._detached = True
        on_detach = self._on_detach
        if on_detach is not None:
            on_detach()


_waited = threading.local()


def waited() -> Waited | None:
    """The calling thread's mark: set for the length of one attempt(i)
    of a QuorumFanout, None on every other thread."""
    return getattr(_waited, "mark", None)


class StragglerCompensator:
    """Keeps a fan-out ThreadPoolExecutor's HEALTHY capacity constant
    while detached stragglers occupy workers, possibly forever (a write
    wedged below any deadline — e.g. an NFS stall — blocks its pool
    thread until the kernel gives up). Each parked straggler raises the
    pool's worker ceiling by one so new fan-outs still get their full
    concurrency; when the straggler finally returns the ceiling drops
    back. Growth is capped so a pathological storm cannot spawn
    unbounded threads — past the cap, stragglers start eating into
    shared capacity again (and the health breaker has long since
    latched the drive responsible)."""

    def __init__(self, pool, max_extra: int = 256):
        # Relies on ThreadPoolExecutor._max_workers being consulted on
        # every submit (_adjust_thread_count); degrade to a no-op if a
        # future CPython renames it.
        self._pool = pool if hasattr(pool, "_max_workers") else None
        self._max_extra = max_extra
        self._extra = 0     # guarded-by: _mu
        self._applied = 0   # guarded-by: _mu
        self._mu = threading.Lock()

    def _apply(self):  # guarded-by: _mu
        want = min(self._extra, self._max_extra)
        delta = want - self._applied
        if delta and self._pool is not None:
            self._pool._max_workers += delta
        self._applied = want

    def parked(self):
        with self._mu:
            self._extra += 1
            self._apply()

    def released(self):
        with self._mu:
            self._extra -= 1
            self._apply()


def quorum_wait(cv, pending, count_ok, quorum, deadline_s, grace_s):
    """The quorum-wait protocol shared by every erasure fan-out
    (shard writes, commit renames, deletes): block on `cv` until
    count_ok() reaches `quorum` plus one straggler grace, the fan-out
    becomes quorum-IMPOSSIBLE (fail now — but only after one grace, so
    tasks ms from settling still report true outcomes for cleanup
    paths like undoRename), every task finished, or deadline_s
    elapses. count_ok runs under cv. Whatever is left in `pending`
    afterwards is the caller's to detach. Records one request span
    (kind "fanout"/"quorum-wait") so a PUT stalled on a straggling
    disk attributes the stall to the fan-out, not the handler; on the
    profiler's clock too, since the wait holds no span of its thread."""
    from ..observability import spans as _spans

    with _spans.span("fanout", "quorum-wait", mirror=True):
        _quorum_wait(cv, pending, count_ok, quorum, deadline_s, grace_s)


def _quorum_wait(cv, pending, count_ok, quorum, deadline_s, grace_s):
    deadline = time.monotonic() + deadline_s
    grace_end = None
    fail_end = None
    with cv:
        while pending:
            now = time.monotonic()
            ok = count_ok()
            if ok >= quorum:
                if grace_end is None:
                    grace_end = now + grace_s
                if now >= grace_end:
                    break
                cv.wait(grace_end - now)
            elif ok + len(pending) < quorum:
                if fail_end is None:
                    fail_end = now + grace_s
                if now >= fail_end:
                    break
                cv.wait(fail_end - now)
            elif now >= deadline:
                break
            else:
                cv.wait(deadline - now)


class QuorumFanout:
    """The detach state machine around quorum_wait, shared by the shard
    -write fan-out (ParallelWriter) and the commit/delete fan-outs
    (_quorum_fanout): dispatch attempt(i) for every index in `pending`
    to the pool, wait for quorum + grace, then detach
    whatever is still in flight — stamping its outcome via on_detach,
    pairing each parked straggler with one compensator release when its
    worker finally frees, and discarding late results. One protocol,
    one set of races to reason about.

    `cv`/`detached`/`straggling` may be shared across dispatches (the
    writer fan-out detaches persistently across blocks) or fresh per
    call (one-shot commit fan-outs)."""

    def __init__(self, pool, compensator, cv=None,
                 detached=None, straggling=None):
        self.pool = pool
        self.comp = compensator
        self.cv = cv if cv is not None else threading.Condition()
        self.detached = detached if detached is not None else set()
        self.straggling = straggling if straggling is not None else set()

    def _release(self, i):
        if i in self.straggling:
            self.straggling.discard(i)
            self.comp.released()

    def dispatch(self, attempt, pending, quorum,
                 deadline_s, grace_s, *, count_ok, record,
                 on_detach, skip=None, on_stragglers=None):
        from ..observability import carry as _obs_carry
        from ..observability import spans as _spans

        cv = self.cv
        detached = self.detached
        marks: dict[int, Waited] = {}  # written and read under cv

        def run(i):
            with cv:
                # Detached (or skippable) while still QUEUED: never
                # start work whose result is already discarded — a
                # rename that has not begun must not land minutes after
                # the caller's locks were released.
                if i in detached or (skip is not None and skip(i)):
                    pending.discard(i)
                    self._release(i)
                    cv.notify_all()
                    return
                mark = marks[i] = Waited(deadline_s)
            err = None
            _waited.mark = mark
            try:
                attempt(i)
            except Exception as exc:  # noqa: BLE001 - collected for quorum
                err = exc
            finally:
                # Pool threads serve callers that do not wait this way.
                _waited.mark = None
            with cv:
                if i in detached:
                    # Straggler finished after detach: result discarded
                    # (its slot already carries the timeout; MRF/heal
                    # repairs whatever it missed); worker freed. The
                    # discard is counted — a drive that keeps failing
                    # just past the grace window must not be invisible.
                    _note_late_drop(err)
                    self._release(i)
                    cv.notify_all()
                    return
                pending.discard(i)
                record(i, err)
                cv.notify_all()

        # Pool workers run attempt(i) on foreign threads: carry the
        # caller's trace and byte-flow op tag so their disk-op spans
        # and ledger bytes attribute to this request.
        bound_run = _obs_carry(run)
        for i in sorted(pending):
            self.pool.submit(bound_run, i)

        quorum_wait(cv, pending, count_ok, quorum, deadline_s, grace_s)
        given_up = []
        with cv:
            if pending and on_stragglers is not None:
                on_stragglers(len(pending))
            for i in list(pending):
                detached.add(i)
                self.straggling.add(i)
                self.comp.parked()
                on_detach(i)
                pending.discard(i)
                if i in marks:
                    given_up.append(marks[i])
                # Zero-duration event mark: the detach decision itself
                # is a fact worth seeing on a slow request's timeline.
                _spans.record("fanout", f"straggler-detach #{i}", 0)
        # Whatever a started straggler is inside learns, outside cv,
        # that nobody waits for it any more.
        for mark in given_up:
            mark.detach()
