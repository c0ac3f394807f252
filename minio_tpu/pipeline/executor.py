"""Pipeline executor: runs a chain of stages on one thread each,
connected by bounded queues.

Semantics:
- **Backpressure** — every inter-stage queue is bounded (`queue_depth`);
  a slow stage stalls its upstream instead of buffering the stream.
- **Ordering** — one worker per stage + FIFO queues: items leave the
  pipeline in source order (GET writes to a client socket, PUT commits
  strips sequentially — reordering would corrupt both).
- **First-error cancellation** — the first raising stage wins; a cancel
  flag turns every queue wait into a prompt abort, workers exit, and
  run()/results() re-raise the original error after all threads have
  been joined (deterministic draining: no worker outlives the call).
- **Telemetry** — per-stage items/bytes/busy/starve/stall, flushed
  once per run into pipeline.metrics.

The executor deliberately offers ONE topology: a linear chain. Shard
fan-out (one write per disk) stays inside a stage via the existing IO
pool — modeling per-disk branches as pipeline stages would serialize
them.
"""

from __future__ import annotations

import queue as _queue
import threading
import time

from ..observability import spans as _spans
from . import metrics as _pmetrics
from .stage import CANCELLED, END_OF_STREAM, SKIP, Stage, StageStats

# Dequeue waits shorter than this record no span: an idle-poll tick is
# queue mechanics, not latency attribution, and would bury the real
# spans in noise. Execute spans always record (they ARE the work).
_SPAN_WAIT_MIN_NS = 500_000  # 0.5 ms

# Poll interval for cancel-aware queue waits: queue.Queue has no native
# wait-with-abort, so blocked workers re-check the cancel flag at this
# cadence. Item handoff itself is immediate — the poll only bounds how
# long a CANCELLED pipeline keeps its threads.
_POLL_S = 0.05


class PipelineCancelled(Exception):
    """The pipeline was cancelled (externally or by consumer abandon)
    before the stream completed."""


class Pipeline:
    """A linear chain of stages executed with stage overlap.

    name        -- telemetry label ("put", "get", "heal", ...).
    stages      -- list[Stage], executed in order.
    queue_depth -- bound of every inter-stage queue (the in-flight
                   window; with the buffer pool this is what limits
                   memory, not stream length).
    pools       -- BufferPools whose stats to flush with each run.
    drop        -- optional item -> None cleanup invoked for every
                   payload item the pipeline abandons on error or
                   cancellation (stranded in a queue, or produced but
                   never enqueued). Drivers that thread pooled buffers
                   through their items use this to return them, so an
                   aborted stream leaves the pool at its steady-state
                   high-water mark instead of leaking one buffer per
                   abort. An item is dropped AT MOST once, and never
                   after the stage that owns its release consumed it.
    """

    def __init__(self, name: str, stages: list[Stage],
                 queue_depth: int = 2, pools: list | None = None,
                 drop=None):
        if not stages:
            raise ValueError("pipeline needs at least one stage")
        self.name = name
        self.stages = stages
        self.queue_depth = max(1, queue_depth)
        self.pools = pools or []
        self._drop = drop
        self._cancel = threading.Event()
        self._err_mu = threading.Lock()
        self._error: BaseException | None = None

    def _drop_item(self, item) -> None:
        if self._drop is None or item is END_OF_STREAM or item is CANCELLED:
            return
        try:
            self._drop(item)
        except Exception:  # noqa: BLE001  # except-ok: drop-hook cleanup is best effort; first error already propagating
            pass

    # ------------------------------------------------------------------
    # cancel-aware queue ops

    def _put(self, q: _queue.Queue, item) -> bool:
        while not self._cancel.is_set():
            try:
                q.put(item, timeout=_POLL_S)
                return True
            except _queue.Full:
                continue
        return False

    def _get(self, q: _queue.Queue):
        while not self._cancel.is_set():
            try:
                return q.get(timeout=_POLL_S)
            except _queue.Empty:
                continue
        return CANCELLED

    def _fail(self, exc: BaseException, stage: Stage | None = None) -> None:
        with self._err_mu:
            if self._error is None:
                self._error = exc
        if stage is not None:
            stage.stats.errors += 1
        self._cancel.set()

    def cancel(self) -> None:
        """External abort: workers drain promptly; run()/results()
        raise PipelineCancelled unless a stage error came first."""
        self._cancel.set()

    # ------------------------------------------------------------------
    # workers

    def _feed(self, source, out_q: _queue.Queue) -> None:
        try:
            for item in source:
                if not self._put(out_q, item):
                    self._drop_item(item)
                    return
        except BaseException as exc:  # noqa: BLE001 - first error wins
            self._fail(exc)
            return
        self._put(out_q, END_OF_STREAM)

    def _work(self, stage: Stage, in_q: _queue.Queue,
              out_q: _queue.Queue) -> None:
        stats = stage.stats
        # One label per (pipeline, stage); spans no-op when the run is
        # not under a request trace (the carrier installed nothing).
        span_label = f"{self.name}/{stage.name}"
        traced = _spans.current() is not None
        while True:
            t0 = time.perf_counter()
            with _spans.twin("stage-wait", span_label):
                item = self._get(in_q)
            wait = time.perf_counter() - t0
            stats.wait_s += wait
            if item is CANCELLED:
                return
            if item is END_OF_STREAM:
                self._put(out_q, END_OF_STREAM)
                return
            if traced and wait * 1e9 >= _SPAN_WAIT_MIN_NS:
                # Dequeue starvation: this stage sat waiting for its
                # upstream — the handoff half of enqueue/dequeue
                # attribution (the enqueue half is the upstream
                # stage's stall span below).
                _spans.record("stage-wait", span_label, int(wait * 1e9))
            try:
                t0 = time.perf_counter()
                if stage.leaf:
                    with _spans.twin("stage", span_label):
                        out = stage.fn(item)
                else:
                    out = stage.fn(item)
                busy = time.perf_counter() - t0
                stats.busy_s += busy
                if traced:
                    _spans.record("stage", span_label, int(busy * 1e9))
            except BaseException as exc:  # noqa: BLE001 - first error wins
                # Contract with `drop`: a stage releases an item's pooled
                # buffer only on full success, so the failed item still
                # carries it — return it here, exactly once.
                self._drop_item(item)
                self._fail(exc, stage)
                return
            if out is SKIP:
                continue
            stats.items += 1
            if stage.bytes_of is not None:
                try:
                    stats.bytes += int(stage.bytes_of(out))
                except Exception:  # noqa: BLE001  # except-ok: telemetry best effort, never fails the stage
                    pass
            t0 = time.perf_counter()
            ok = self._put(out_q, out)
            stall = time.perf_counter() - t0
            stats.stall_s += stall
            if traced and stall * 1e9 >= _SPAN_WAIT_MIN_NS:
                # Enqueue backpressure: downstream is the bottleneck.
                _spans.record("stage-stall", span_label,
                              int(stall * 1e9))
            if not ok:
                self._drop_item(out)
                return

    # ------------------------------------------------------------------
    # driving

    def results(self, source):
        """Run the pipeline over `source`, yielding the final stage's
        outputs in order from the CALLER's thread. Joins every worker
        before returning/raising — even when the consumer abandons the
        generator mid-stream."""
        # Fresh per run: stats AND the cancel/error state, so a caller
        # may reuse one Pipeline for sequential runs.
        for st in self.stages:
            st.stats = StageStats()
        self._cancel = threading.Event()
        with self._err_mu:
            self._error = None
        queues = [
            _queue.Queue(maxsize=self.queue_depth)
            for _ in range(len(self.stages) + 1)
        ]
        # Carry the caller's request-scoped observability context (span
        # trace + byte-flow op tag) into the stage threads so anything
        # the stage functions call — worker dispatches, fan-outs, disk
        # ops — attributes to the request being served.
        from ..observability import carry as _bound

        threads = [
            threading.Thread(
                target=_bound(self._feed),
                args=(source, queues[0]),
                name=f"mtpu-pipe-{self.name}-src", daemon=True,
            )
        ]
        for i, st in enumerate(self.stages):
            threads.append(threading.Thread(
                target=_bound(self._work),
                args=(st, queues[i], queues[i + 1]),
                name=f"mtpu-pipe-{self.name}-{st.name}", daemon=True,
            ))
        for t in threads:
            t.start()
        out_q = queues[-1]
        cancelled_mid = False
        try:
            while True:
                item = self._get(out_q)
                if item is CANCELLED:
                    cancelled_mid = True
                    break
                if item is END_OF_STREAM:
                    break
                yield item
        except GeneratorExit:
            # Consumer bailed (e.g. a range-GET client hung up): cancel
            # so upstream producers unblock, then fall through to the
            # deterministic join below.
            self._cancel.set()
            raise
        finally:
            self._cancel_wait_flush(threads, queues)
        if self._error is not None:
            raise self._error
        if cancelled_mid:
            raise PipelineCancelled(self.name)

    def run(self, source) -> int:
        """Drive to completion discarding final-stage outputs; returns
        the number of items the last stage produced. Raises the first
        stage/source error."""
        n = 0
        for _ in self.results(source):
            n += 1
        return n

    def _cancel_wait_flush(self, threads, queues=()) -> None:
        # After the caller saw EOS (or error), everything upstream is
        # done or cancelled; setting cancel lets any straggler blocked
        # on a full queue exit, making the join bounded.
        self._cancel.set()
        for t in threads:
            t.join()
        # Workers are parked: anything still queued was abandoned by the
        # cancellation and never reached its releasing stage — return
        # those items' pooled buffers before reporting pool stats.
        if self._drop is not None:
            for q in queues:
                while True:
                    try:
                        self._drop_item(q.get_nowait())
                    except _queue.Empty:
                        break
        _pmetrics.record_run(self.name, self.stages)
        for p in self.pools:
            _pmetrics.record_pool(p)

    # ------------------------------------------------------------------

    def stage_stats(self) -> dict:
        """Last run's per-stage stats (also mirrored to the registry)."""
        return {st.name: st.stats.as_dict() for st in self.stages}
