"""GIL-free request-plane worker pool: native GF batch encode,
survivor-block reconstruct (GET decode / heal), and hh256 frame
verification in child PROCESSES, fed through shared-memory segments —
the fan-in half of the concurrency plane, covering BOTH sides of the
request plane since ISSUE 11 (PR7 covered PUT encode only).

Why processes: the native encode/hash calls already release the GIL,
but with N concurrent PUT streams the Python orchestration around them
(fill loops, writer fan-out, journal commits) contends on the main
interpreter's GIL and the aggregate flatlines (c5 stuck ~0.23 GB/s for
three rounds while every single-object number improved). Moving the
per-batch compute off the main interpreter frees its GIL for
orchestration and scales encode across cores for real. Subinterpreters
would be the lighter vehicle, but per-interpreter GILs need 3.12+;
`multiprocessing` with the spawn context works on the floor we have.

Zero extra copies: the strip buffer a PUT stream fills (ONE readinto
per block, exactly like the in-process driver) IS a shared-memory
segment. The worker maps the same segment by name, computes parity
into the segment's parity region (gf_native.apply_matrix_batch(out=))
and the frame digests into its digest region (hash_strided_digests
(out=)), and replies with a 2-tuple — no payload byte ever crosses the
pipe. The parent then writev's shards straight out of the segment.
`copy_counters` therefore stays at the PR3/PR6 floor (one source-read
copy per input byte, nothing else) — asserted in tests.

The read-side ops keep the same invariant: a GET's survivor blocks
are gathered into the SAME strip segments the encode drivers use (the
data region holds the k survivor rows, the parity region receives the
rebuilt shards, the digest region the re-framed bitrot digests for
heal), and bitrot verification reads happen into pooled flat shm ring
segments (ShmRing) so the whole framed batch is visible to the child
— the pipe carries only names, offsets and a bad-chunk index.

Fallback ladder (armed() is the single gate; DEFAULT-ON since
ISSUE 11 — MTPU_WORKER_POOL=0 opts out):
- single-core hosts, MTPU_WORKER_POOL=0, no native engine, or spawn
  failure → the in-process drivers, untouched (the worker_armed gauge
  records WHY: env/cores/native/spawn/crashes);
- a worker crash mid-batch (WorkerCrashed) → the caller recomputes
  THAT batch in-process from the still-intact shm data — byte-
  identical output, stream uninterrupted — and the pool respawns the
  worker in background;
- too many crashes → the pool disarms itself for the process lifetime.

Shutdown discipline: workers are daemon processes AND an atexit hook
drains them (quit message, join, terminate stragglers) and unlinks
every shared-memory segment, so neither orphan processes nor
/dev/shm litter outlive the parent. The strip pools register in
pipeline.buffers._shared like every other recycled pool, so the chaos
soak's `in_use == 0` sweep covers them.
"""

from __future__ import annotations

import atexit
import os
import queue as _queue
import threading
import weakref

import numpy as np

DIGEST_SIZE = 32

WORKER_DESCRIPTORS: list[tuple[str, str, str]] = [
    ("worker_pool_workers", "gauge",
     "Request-plane worker processes currently alive"),
    ("worker_pool_busy", "gauge",
     "Request-plane worker processes currently executing a task"),
    ("worker_tasks_total", "counter",
     "Tasks (encode/decode/verify/heal batches) run by the worker pool"),
    ("worker_fallbacks_total", "counter",
     "Tasks recomputed in-process after a worker failure"),
    ("worker_crashes_total", "counter",
     "Worker processes lost mid-task"),
    # Read-side op series (ISSUE 11): the encode op stays the aggregate
    # minus these three, so dashboards keep their PR7 shape.
    ("worker_decode_tasks_total", "counter",
     "Degraded-GET reconstruct batches run by the worker pool"),
    ("worker_decode_fallbacks_total", "counter",
     "Degraded-GET batches recomputed in-process after a worker failure"),
    ("worker_verify_tasks_total", "counter",
     "Bitrot frame-verification calls run by the worker pool"),
    ("worker_verify_fallbacks_total", "counter",
     "Bitrot verifications recomputed in-process (worker busy/failed)"),
    ("worker_heal_tasks_total", "counter",
     "Heal reconstruct+redigest batches run by the worker pool"),
    ("worker_heal_fallbacks_total", "counter",
     "Heal batches recomputed in-process after a worker failure"),
    ("worker_armed", "gauge",
     "1 when the worker pool is armed, else 0"),
    ("worker_armed_reason", "gauge",
     "One-hot arm-state reason: exactly one of reason=armed|env|cores|"
     "native|spawn|crashes is 1"),
]

# Per-op registry series (the aggregate worker_tasks_total /
# worker_fallbacks_total always tick as well).
_OP_SERIES = {
    "decode": ("worker_decode_tasks_total", "worker_decode_fallbacks_total"),
    "verify": ("worker_verify_tasks_total", "worker_verify_fallbacks_total"),
    "heal": ("worker_heal_tasks_total", "worker_heal_fallbacks_total"),
}

_metrics = None  # guarded-by: _metrics_mu
_metrics_mu = threading.Lock()


def set_metrics(registry) -> None:
    global _metrics
    with _metrics_mu:
        _metrics = registry


def _reg():
    with _metrics_mu:
        return _metrics


class WorkerCrashed(RuntimeError):
    """The worker process died (or wedged past the deadline) mid-task;
    the task's shm inputs are intact — recompute in-process."""


class WorkerUnavailable(RuntimeError):
    """No worker could take the task (pool disarmed, all busy past the
    wait bound, or the worker declined it); recompute in-process."""


# ---------------------------------------------------------------------------
# shared-memory strip segments

# Every live segment (for atexit unlink): name -> weakref so pooled
# segments die with their pool, not with this registry.
_segments: "weakref.WeakValueDictionary[str, ShmStrip]" = (
    weakref.WeakValueDictionary()
)  # guarded-by: _segments_mu
_segments_mu = threading.Lock()


class ShmStrip:
    """One shared-memory strip segment, laid out as
    data [B, k*S] | parity [B, m, S] | digests [k+m, B, 32].

    The data region is the block-major strip buffer the encode drivers
    fill (same geometry as the in-process pools); parity and digests
    are the worker's output regions. Views are numpy arrays over the
    one mapping — nothing here copies."""

    def __init__(self, batch: int, k: int, m: int, shard: int):
        from multiprocessing import shared_memory

        self.batch, self.k, self.m, self.shard = batch, k, m, shard
        data_n = batch * k * shard
        par_n = batch * m * shard
        dig_n = (k + m) * batch * DIGEST_SIZE
        self._shm = shared_memory.SharedMemory(
            create=True, size=data_n + par_n + dig_n
        )
        self.name = self._shm.name
        buf = self._shm.buf
        self.data = np.frombuffer(buf, dtype=np.uint8, count=data_n)\
            .reshape(batch, k * shard)
        self.parity = np.frombuffer(buf, dtype=np.uint8, count=par_n,
                                    offset=data_n).reshape(batch, m, shard)
        self.digests = np.frombuffer(
            buf, dtype=np.uint8, count=dig_n, offset=data_n + par_n
        ).reshape(k + m, batch, DIGEST_SIZE)
        with _segments_mu:
            _segments[self.name] = self

    # -- read-plane views (ISSUE 11) ---------------------------------------
    # A decode/heal batch reuses the SAME segment layout: the data
    # region holds the k survivor rows per block, the parity region
    # (viewed flat, so any target count T <= m stays contiguous for
    # apply_matrix_batch(out=)) receives the rebuilt shards, and the
    # digest region the re-framed bitrot digests. Parent and child
    # derive these views identically from the region bases.

    def recon_src(self, nb: int) -> np.ndarray:
        """Survivor blocks as [nb, k, S] over the data region."""
        return self.data[:nb].reshape(nb, self.k, self.shard)

    def recon_out(self, nb: int, t: int) -> np.ndarray:
        """Rebuilt shards as a CONTIGUOUS [nb, t, S] view at the parity
        region's base (t <= m; a [:nb, :t] slice would be strided)."""
        flat = self.parity.reshape(-1)
        return flat[: nb * t * self.shard].reshape(nb, t, self.shard)

    def recon_digests(self, nb: int, t: int) -> np.ndarray:
        """Per-target frame digests [t, nb, 32] at the digest region's
        base (heal re-digest output)."""
        flat = self.digests.reshape(-1)
        return flat[: t * nb * DIGEST_SIZE].reshape(t, nb, DIGEST_SIZE)

    def close(self) -> None:
        """Drop the numpy views, unmap, and unlink the segment. Safe to
        call twice (pool drop + atexit sweep)."""
        shm, self._shm = self._shm, None
        if shm is None:
            return
        # The views pin the mapping; they must go first or close()
        # raises BufferError.
        self.data = self.parity = self.digests = None
        try:
            shm.close()
        except BufferError:  # a stale external view still pins it
            return
        try:
            shm.unlink()
        except FileNotFoundError:
            pass

    def __del__(self):  # pragma: no cover - GC timing
        try:
            self.close()
        except Exception:  # noqa: BLE001  # except-ok: GC-time teardown; close() is idempotent and atexit sweeps
            pass


class ShmRing:
    """One flat shared-memory read buffer: a StreamingBitrotReader ring
    slot whose framed [digest||chunk]* batch read lands where a verify
    worker can see it. `view` is the single numpy mapping — readinto
    fills it, the child hashes it, nothing copies."""

    def __init__(self, size: int):
        from multiprocessing import shared_memory

        self.size = size
        self._shm = shared_memory.SharedMemory(create=True, size=size)
        self.name = self._shm.name
        self.view = np.frombuffer(self._shm.buf, dtype=np.uint8, count=size)
        with _segments_mu:
            _segments[self.name] = self

    def close(self) -> None:
        shm, self._shm = self._shm, None
        if shm is None:
            return
        self.view = None
        try:
            shm.close()
        except BufferError:  # a stale external view still pins it
            return
        try:
            shm.unlink()
        except FileNotFoundError:
            pass

    def __del__(self):  # pragma: no cover - GC timing
        try:
            self.close()
        except Exception:  # noqa: BLE001  # except-ok: GC-time teardown; close() is idempotent and atexit sweeps
            pass


def strip_pool(batch: int, k: int, m: int, shard: int):
    """Process-shared recycled pool of ShmStrip segments for one
    geometry — the shm counterpart of the in-process strip pools, and
    registered in the same `buffers._shared` registry so leak sweeps
    (chaos soak `in_use == 0`) cover it."""
    from .buffers import shared_pool

    return shared_pool(
        ("shm-strips", batch, k, m, shard),
        lambda: ShmStrip(batch, k, m, shard),
        capacity=8, name="shm-strips",
    )


def ring_capacity(phys: int) -> int:
    """Size class for a verify ring request: next power of two >= 256
    KiB, so the handful of per-geometry batch sizes collapse onto a few
    shared pools instead of one pool per exact length."""
    cap = 256 * 1024
    while cap < phys:
        cap *= 2
    return cap


def ring_pool(size: int):
    """Process-shared recycled pool of flat ShmRing read buffers for one
    size class — registered in `buffers._shared` like the strip pools so
    the chaos soak's `in_use == 0` sweep covers them too."""
    from .buffers import shared_pool

    return shared_pool(
        ("shm-rings", size),
        lambda: ShmRing(size),
        capacity=16, name="shm-rings",
    )


def _sweep_segments() -> None:
    with _segments_mu:
        strips = list(_segments.values())
    for s in strips:
        try:
            s.close()
        except Exception:  # noqa: BLE001  # except-ok: atexit sweep; a segment that will not close is the OS's now
            pass


# ---------------------------------------------------------------------------
# worker child

def _attach_segment(name: str, batch: int, k: int, m: int, shard: int):
    """Map the parent's segment by name for ONE task. Deliberately
    uncached: the attach is microseconds against a multi-ms batch, and
    a cache keyed by name would (a) pin every churned segment's memory
    for the worker's lifetime and (b) compute into a STALE mapping if
    the OS ever reuses a freed psm_ name. The child's resource tracker
    must NOT adopt the segment — on 3.10 a tracked non-owner unlinks
    it when the child exits (bpo-38119), yanking it from under the
    parent."""
    from multiprocessing import resource_tracker, shared_memory

    shm = shared_memory.SharedMemory(name=name)
    try:
        resource_tracker.unregister(shm._name, "shared_memory")
    except Exception:  # noqa: BLE001  # except-ok: resource_tracker internals moved; worst case the child tracker unlinks early and the task crash-falls-back
        pass
    data_n = batch * k * shard
    par_n = batch * m * shard
    dig_n = (k + m) * batch * DIGEST_SIZE
    buf = shm.buf
    return (
        shm,
        np.frombuffer(buf, dtype=np.uint8, count=data_n)
        .reshape(batch, k * shard),
        np.frombuffer(buf, dtype=np.uint8, count=par_n, offset=data_n)
        .reshape(batch, m, shard),
        np.frombuffer(buf, dtype=np.uint8, count=dig_n,
                      offset=data_n + par_n)
        .reshape(k + m, batch, DIGEST_SIZE),
    )


def _child_encode(mats: dict, name: str, batch: int, nb: int,
                  k: int, m: int, shard: int,
                  codec: str | None = None) -> None:
    """One batch: GF parity into the segment's parity region, frame
    digests for all k+m shards into its digest region. Must stay
    byte-identical to the in-process path: same parity matrix
    derivation (erasure/registry entry for the codec id), same native
    kernels — the codec only changes the byte matrix, never the
    kernel, which is what keeps this shm path codec-agnostic."""
    from ..erasure.bitrot import hash_strided_digests
    from ..ops import gf_native

    shm, data, parity, digests = _attach_segment(name, batch, k, m, shard)
    try:
        mat = mats.get((codec, k, m))
        if mat is None:
            from ..erasure import registry

            entry = registry.get(codec or registry.DEFAULT_CODEC)
            mat = entry.parity_matrix(k, m)
            mats[(codec, k, m)] = mat
        gf_native.apply_matrix_batch(
            mat, data[:nb].reshape(nb, k, shard), out=parity[:nb]
        )
        row = k * shard
        for j in range(k):
            if hash_strided_digests(data, j * shard, row, nb, shard,
                                    out=digests[j]) is None:
                raise RuntimeError(
                    "native strided hash unavailable in worker"
                )
        for pj in range(m):
            hash_strided_digests(parity, pj * shard, m * shard, nb, shard,
                                 out=digests[k + pj])
    finally:
        # Views pin the mapping: drop them before close. A lingering
        # pin only delays the unmap to process exit — never fail a
        # task that already computed correctly.
        data = parity = digests = None  # noqa: F841
        try:
            shm.close()
        except BufferError:  # pragma: no cover - stray external view
            pass


def _child_recon(name: str, batch: int, nb: int, k: int, m: int,
                 shard: int, present: tuple, targets: tuple,
                 with_digests: bool, codec: str | None = None) -> None:
    """One decode/heal batch: rebuild `targets` shards from the k
    survivor rows in the segment's data region into the (flat-viewed)
    parity region, plus their frame digests for heal. Byte-identical to
    the in-process path by construction: the SAME cached reconstruction
    matrix (the codec's registry entry, lru-backed) applied by the SAME
    native kernel (gf_native.apply_matrix_batch)."""
    from ..erasure import registry
    from ..erasure.bitrot import hash_strided_digests
    from ..ops import gf_native

    shm, data, parity, digests = _attach_segment(name, batch, k, m, shard)
    out = dig = None
    try:
        t = len(targets)
        entry = registry.get(codec or registry.DEFAULT_CODEC)
        mat = entry.reconstruct_matrix(k, m, list(present), list(targets))
        out = parity.reshape(-1)[: nb * t * shard].reshape(nb, t, shard)
        gf_native.apply_matrix_batch(
            mat, data[:nb].reshape(nb, k, shard), out=out
        )
        if with_digests:
            dig = digests.reshape(-1)[: t * nb * DIGEST_SIZE]\
                .reshape(t, nb, DIGEST_SIZE)
            for t_i in range(t):
                if hash_strided_digests(out, t_i * shard, t * shard, nb,
                                        shard, out=dig[t_i]) is None:
                    raise RuntimeError(
                        "native strided hash unavailable in worker"
                    )
    finally:
        # EVERY view must go before close or the child's mapping leaks
        # one attach per task (close raises BufferError and __del__
        # cannot unmap either).
        data = parity = digests = out = dig = None  # noqa: F841
        try:
            shm.close()
        except BufferError:  # pragma: no cover - stray external view
            pass


def _child_verify(name: str, size: int, phys: int, chunk: int) -> int:
    """Verify every [digest||chunk] frame of the first `phys` bytes of
    a flat ring segment; returns the first bad chunk index or -1. The
    reply is ONE int — no payload crosses the pipe here either."""
    import ctypes

    from multiprocessing import resource_tracker, shared_memory

    from .. import native
    from ..ops import highwayhash

    lib = native.load()
    if lib is None:
        raise RuntimeError("native hh256 engine unavailable in worker")
    shm = shared_memory.SharedMemory(name=name)
    try:
        resource_tracker.unregister(shm._name, "shared_memory")
    except Exception:  # noqa: BLE001  # except-ok: resource_tracker internals moved; worst case the child tracker unlinks early and the task crash-falls-back
        pass
    try:
        arr = np.frombuffer(shm.buf, dtype=np.uint8, count=size)
        bad = lib.hh256_verify_frames(
            highwayhash.MAGIC_KEY,
            arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            phys, chunk,
        )
        return int(bad)
    finally:
        arr = None  # noqa: F841 - view pins the mapping
        try:
            shm.close()
        except BufferError:  # pragma: no cover - stray external view
            pass


def _worker_cli() -> None:  # pragma: no cover - child process
    """Child loop: unpickle task from stdin -> compute into shm ->
    pickle reply to stdout. Plain subprocess transport (not
    multiprocessing spawn): spawn re-executes the parent's __main__,
    which breaks under pytest/stdin drivers, while stdin EOF here is a
    natural orphan guard — the child exits the moment its parent dies.
    Imports stay jax-free (numpy + the native lib) — checked below,
    since the pool is armed at boot beside a server that may own the
    chip, and a child that imported jax could reach for it; one native
    thread per worker so W workers never oversubscribe the cores the
    parent still needs."""
    import pickle
    import sys

    os.environ.setdefault("MTPU_NATIVE_THREADS", "1")
    # Everything the ops below import, up front, so the check covers it.
    from .. import native  # noqa: F401
    from ..erasure import bitrot, registry  # noqa: F401
    from ..ops import gf_native, highwayhash  # noqa: F401

    if "jax" in sys.modules:
        raise RuntimeError("worker child imported jax")
    inp = sys.stdin.buffer
    out = sys.stdout.buffer
    mats: dict = {}
    try:
        while True:
            try:
                msg = pickle.load(inp)
            except (EOFError, OSError):
                return
            kind = msg[0]
            if kind == "quit":
                return
            if kind == "ping":
                pickle.dump(("ok", None), out)
                out.flush()
                continue
            if kind == "crash":  # test hook: die mid-task
                os._exit(42)
            try:
                # The child measures its own execute-ns and ships it in
                # the reply tuple (ISSUE 12): the parent stitches a
                # cross-process child span under its dispatch span, so
                # queue-wait vs compute separate in slow-request trees.
                # One int — no payload or pickle shape growth.
                import time as _time

                t0 = _time.monotonic_ns()
                if kind == "enc":
                    _child_encode(mats, *msg[1:])
                    result = None
                elif kind == "rec":
                    _child_recon(*msg[1:])
                    result = None
                elif kind == "vfy":
                    result = _child_verify(*msg[1:])
                else:
                    raise ValueError(f"unknown worker op {kind!r}")
                exec_ns = _time.monotonic_ns() - t0
            except Exception as exc:  # noqa: BLE001 - reported to parent
                reply = ("err", f"{type(exc).__name__}: {exc}")
            else:
                reply = ("ok", result, exec_ns)
            pickle.dump(reply, out)
            out.flush()
    except KeyboardInterrupt:
        return


# ---------------------------------------------------------------------------
# parent-side pool

class _Worker:
    """One child process + its stdin/stdout pickle channel."""

    __slots__ = ("proc",)

    def __init__(self, proc):
        self.proc = proc

    @property
    def pid(self) -> int:
        return self.proc.pid

    def send(self, msg: tuple) -> None:
        import pickle

        pickle.dump(msg, self.proc.stdin)
        self.proc.stdin.flush()

    def recv(self, timeout_s: float):
        """Reply or None on timeout; raises EOFError/OSError when the
        child died."""
        import pickle
        import select

        ready, _, _ = select.select([self.proc.stdout], [], [], timeout_s)
        if not ready:
            return None
        return pickle.load(self.proc.stdout)

    def close(self) -> None:
        for f in (self.proc.stdin, self.proc.stdout):
            try:
                if f is not None:
                    f.close()
            except OSError:
                pass


# Stage threads the parent keeps for itself per active stream (source
# fill + writev fan-out): the default-on auto-size leaves them their
# cores instead of oversubscribing every core with a worker.
_RESERVED_STAGE_THREADS = 2


def default_workers() -> int:
    env = os.environ.get("MTPU_WORKER_POOL_SIZE", "")
    if env.isdigit() and int(env) > 0:
        return int(env)
    return max(2, (os.cpu_count() or 2) - _RESERVED_STAGE_THREADS)


class WorkerPool:
    """Fixed-size pool of encode worker processes with an idle queue.
    Dispatch is request/response per batch — the caller's pipeline
    stage blocks on the reply (the pipe recv releases the GIL), while
    the stream's fill and writev stages keep running on their own
    threads. Crashed workers are retired, counted, and respawned in
    background; past `max_respawns` the pool disarms for good."""

    def __init__(self, n: int | None = None,
                 deadline_s: float | None = None):
        self.n = n or default_workers()
        self.deadline_s = deadline_s if deadline_s is not None else float(
            os.environ.get("MTPU_WORKER_DEADLINE_S", "30")
        )
        self.max_respawns = 3 * self.n
        self._idle: _queue.Queue = _queue.Queue()
        self._workers: list[_Worker] = []   # guarded-by: _mu
        self._mu = threading.Lock()
        self._dead = False                  # guarded-by: _mu
        self._respawns = 0                  # guarded-by: _mu
        self._busy = 0                      # guarded-by: _mu
        # Counters (mirrored onto the registry when installed).
        # Aggregates keep their PR7 names; the per-op dicts split them
        # by request-plane op (encode/decode/verify/heal).
        self.tasks_total = 0                # guarded-by: _mu
        self.fallbacks_total = 0            # guarded-by: _mu
        self.crashes_total = 0              # guarded-by: _mu
        self.tasks_by_op: dict[str, int] = {}       # guarded-by: _mu
        self.fallbacks_by_op: dict[str, int] = {}   # guarded-by: _mu

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        for _ in range(self.n):
            self._spawn()
        self._gauge()

    def _spawn(self) -> None:
        import subprocess
        import sys

        env = dict(os.environ)
        # The child must import THIS package, whatever the parent's
        # entry point was (pytest, bench, the server binary).
        pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env["PYTHONPATH"] = pkg_root + os.pathsep + env.get(
            "PYTHONPATH", "")
        env.setdefault("MTPU_NATIVE_THREADS", "1")
        proc = subprocess.Popen(
            [sys.executable, "-c",
             "from minio_tpu.pipeline.workers import _worker_cli; "
             "_worker_cli()"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
        )
        w = _Worker(proc)
        with self._mu:
            self._workers.append(w)
        self._idle.put(w)

    def shutdown(self, timeout_s: float = 5.0) -> None:
        """Quit every worker, join, terminate stragglers. Leaves the
        pool disarmed; shm segments are owned by the strip pools (and
        the atexit sweep), not by this object."""
        with self._mu:
            self._dead = True
            workers, self._workers = self._workers, []
        import subprocess

        for w in workers:
            try:
                w.send(("quit",))
            except (BrokenPipeError, OSError):
                pass
        for w in workers:
            try:
                w.proc.wait(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                w.proc.terminate()
                try:
                    w.proc.wait(timeout=2.0)
                except subprocess.TimeoutExpired:
                    w.proc.kill()
                    w.proc.wait()
            w.close()
        # Drain idle refs so nothing resurrects a closed pipe.
        while True:
            try:
                self._idle.get_nowait()
            except _queue.Empty:
                break
        self._gauge()

    def alive(self) -> bool:
        with self._mu:
            return not self._dead and bool(self._workers)

    def live_pids(self) -> list[int]:
        with self._mu:
            return [w.pid for w in self._workers
                    if w.proc.poll() is None]

    # -- dispatch ----------------------------------------------------------

    def encode_batch(self, strip: ShmStrip, nb: int,
                     codec: str | None = None,
                     _test_crash: bool = False) -> None:
        """Run one batch's GF encode + strided digests in a worker.
        On return, strip.parity[:nb] and strip.digests[:, :nb] hold
        the results. `codec` is the registry codec id determining the
        parity matrix (None = dense default). Raises WorkerCrashed /
        WorkerUnavailable; the shm data region is untouched either way,
        so callers recompute in-process from the same bytes."""
        self._dispatch(
            "encode",
            ("enc", strip.name, strip.batch, nb,
             strip.k, strip.m, strip.shard, codec),
            _test_crash=_test_crash,
        )

    def recon_batch(self, strip: ShmStrip, nb: int, present: tuple,
                    targets: tuple, digests: bool, op: str = "decode",
                    codec: str | None = None,
                    _test_crash: bool = False) -> None:
        """Rebuild `targets` shards from the k survivor rows in
        strip.recon_src(nb) (rows in `present` order). On return,
        strip.recon_out(nb, len(targets)) holds the rebuilt shards and
        — when `digests` — strip.recon_digests(nb, len(targets)) their
        frame digests. `op` labels the telemetry: "decode" (degraded
        GET) or "heal"; `codec` the registry codec id (None = dense)."""
        self._dispatch(
            op,
            ("rec", strip.name, strip.batch, nb, strip.k, strip.m,
             strip.shard, tuple(present), tuple(targets), bool(digests),
             codec),
            _test_crash=_test_crash,
        )

    # A verify task is far cheaper than an encode/reconstruct batch, so
    # a busy pool should divert it in-process (the native verify call
    # releases the GIL anyway) rather than stall the read fan-out.
    VERIFY_WAIT_S = 0.05

    def verify_frames(self, ring: ShmRing, phys: int, chunk: int,
                      _test_crash: bool = False) -> int:
        """Verify the [digest||chunk]* frames in ring.view[:phys] in a
        worker; returns the first bad chunk index or -1 (the caller
        raises ErrFileCorrupt exactly like the in-process path)."""
        bad = self._dispatch(
            "verify", ("vfy", ring.name, ring.size, phys, chunk),
            wait_s=self.VERIFY_WAIT_S, _test_crash=_test_crash,
        )
        return int(bad)

    def _dispatch(self, op: str, msg: tuple, wait_s: float | None = None,
                  _test_crash: bool = False):
        """One request/response task on an idle worker. Raises
        WorkerCrashed / WorkerUnavailable; every shm input region is
        untouched on failure, so callers recompute in-process from the
        same bytes. Under a request trace the whole dispatch records as
        a "worker" span (idle-wait + pipe round-trip) with the child's
        self-measured execute-ns stitched in as a "worker-exec" child
        span — the cross-process half of the latency tree."""
        from ..observability import spans as _spans

        with _spans.span("worker", op):
            return self._dispatch_traced(op, msg, wait_s, _test_crash)

    def _dispatch_traced(self, op: str, msg: tuple,
                         wait_s: float | None = None,
                         _test_crash: bool = False):
        if not self.alive():
            raise WorkerUnavailable("worker pool not running")
        try:
            # Workers ≈ cores and admission bounds concurrent streams
            # to the same order, so a short wait means a worker frees
            # within one batch time; past it, in-process is faster.
            w = self._idle.get(
                timeout=self.deadline_s if wait_s is None else wait_s
            )
        except _queue.Empty:
            raise WorkerUnavailable(
                f"no idle worker for {op} within the wait bound"
            ) from None
        with self._mu:
            self._busy += 1
        self._gauge()
        healthy = False
        try:
            if _test_crash:
                w.send(("crash",))
            else:
                w.send(msg)
            reply = w.recv(self.deadline_s)
            if reply is None:
                raise WorkerCrashed(
                    f"worker pid {w.pid} silent past {self.deadline_s}s"
                )
            status, payload = reply[0], reply[1]
            # Child execute-ns (absent from err/ping replies and from
            # older two-tuple shapes a test may fake).
            exec_ns = reply[2] if len(reply) > 2 else 0
        except Exception as exc:  # noqa: BLE001 - ANY channel fault
            # EOF/pipe errors, a reply garbled by stray stdout output,
            # a truncated pickle from a dying child — every channel
            # fault classifies as a crash so the caller's in-process
            # fallback runs and the worker is retired, never leaked.
            self._retire(w)
            raise exc if isinstance(exc, WorkerCrashed) else WorkerCrashed(
                f"worker pid {w.pid} channel fault: "
                f"{type(exc).__name__}: {exc}"
            ) from None
        else:
            healthy = True
        finally:
            with self._mu:
                self._busy -= 1
            if healthy:
                self._idle.put(w)
            self._gauge()
        if status != "ok":
            # The worker itself is fine; THIS task cannot run there
            # (e.g. native lib failed to build in the child).
            raise WorkerUnavailable(payload or "worker declined the task")
        if exec_ns:
            from ..observability import spans as _spans

            # Parented under the enclosing "worker" dispatch span.
            _spans.record("worker-exec", f"{op} pid {w.pid}", int(exec_ns))
        with self._mu:
            self.tasks_total += 1
            self.tasks_by_op[op] = self.tasks_by_op.get(op, 0) + 1
        reg = _reg()
        if reg is not None:
            reg.inc("worker_tasks_total")
            series = _OP_SERIES.get(op)
            if series is not None:
                reg.inc(series[0])
        return payload

    def _retire(self, w: _Worker) -> None:
        """Drop a crashed worker and respawn a replacement off the
        caller's critical path; disarm the pool past the respawn cap
        (something is systematically killing workers)."""
        with self._mu:
            self.crashes_total += 1
        reg = _reg()
        if reg is not None:
            reg.inc("worker_crashes_total")
        import subprocess

        try:
            w.proc.terminate()
            w.proc.wait(timeout=2.0)
        except subprocess.TimeoutExpired:
            # A child wedged in a native call ignores SIGTERM; it MUST
            # die before the caller's fallback recomputes and the shm
            # strip recycles — a surviving child would scribble its
            # stale task into a segment another stream now owns.
            try:
                w.proc.kill()
                w.proc.wait(timeout=2.0)
            except Exception:  # noqa: BLE001  # except-ok: unkillable (D-state) child; crashes_total already counted this retirement
                pass
        except Exception:  # noqa: BLE001  # except-ok: child already dead; crashes_total already counted this retirement
            pass
        w.close()
        with self._mu:
            if w in self._workers:
                self._workers.remove(w)
            self._respawns += 1
            if self._respawns > self.max_respawns:
                self._dead = True
                return
            if self._dead:
                return
        threading.Thread(target=self._respawn_safe, daemon=True,
                         name="mtpu-worker-respawn").start()

    def _respawn_safe(self) -> None:
        try:
            self._spawn()
        except Exception:  # noqa: BLE001  # except-ok: spawn failed — disarms the pool; armed() reports reason=crashes via the one-hot gauge
            with self._mu:
                self._dead = True
        self._gauge()

    def note_fallback(self, op: str = "encode") -> None:
        with self._mu:
            self.fallbacks_total += 1
            self.fallbacks_by_op[op] = self.fallbacks_by_op.get(op, 0) + 1
        reg = _reg()
        if reg is not None:
            reg.inc("worker_fallbacks_total")
            series = _OP_SERIES.get(op)
            if series is not None:
                reg.inc(series[1])

    # -- telemetry ---------------------------------------------------------

    def _gauge(self) -> None:
        reg = _reg()
        if reg is None:
            return
        with self._mu:
            n, busy = len(self._workers), self._busy
        reg.set_gauge("worker_pool_workers", n)
        reg.set_gauge("worker_pool_busy", busy)

    def snapshot(self) -> dict:
        with self._mu:
            return {
                "workers": len(self._workers),
                "busy": self._busy,
                "dead": self._dead,
                "respawns": self._respawns,
                "tasks_total": self.tasks_total,
                "fallbacks_total": self.fallbacks_total,
                "crashes_total": self.crashes_total,
                "tasks_by_op": dict(self.tasks_by_op),
                "fallbacks_by_op": dict(self.fallbacks_by_op),
            }


# ---------------------------------------------------------------------------
# process-global arming

_pool: WorkerPool | None = None  # guarded-by: _pool_mu
_pool_mu = threading.Lock()
_atexit_registered = False
# Why the pool is (not) armed, for the worker_armed gauge and the
# bench/admin snapshots: "armed" | "env" | "cores" | "native" |
# "spawn" | "crashes" | "unarmed" (never consulted yet).
_arm_reason = "unarmed"
# Set when a full pool spawn failed: with the plane default-on,
# re-attempting an n-process spawn on EVERY stream of a host that
# cannot spawn (sandbox, rlimit) would tax exactly the requests the
# pool exists to speed up. The latch is a COOLDOWN, not permanent —
# a transient failure (fd exhaustion during a deploy) self-heals on
# the next arm attempt after the retry window; shutdown() also clears
# it so an explicit re-arm always gets a real attempt.
_spawn_failed_at: float | None = None  # guarded-by: _pool_mu
_SPAWN_RETRY_S = 60.0


_ARM_REASONS = ("armed", "env", "cores", "native", "spawn", "crashes")


def _note_arm(reason: str) -> None:
    global _arm_reason
    if reason == _arm_reason:
        return  # armed() runs per stream/reader: write only transitions
    _arm_reason = reason
    reg = _reg()
    if reg is not None:
        # One unlabeled 1/0 gauge for alerting plus a ONE-HOT labeled
        # reason series — writing only the current reason's label would
        # leave the previous state's series exported at its old value
        # (the registry keys gauges per label set), so every reason is
        # written every transition.
        reg.set_gauge("worker_armed", 1.0 if reason == "armed" else 0.0)
        for r in _ARM_REASONS:
            reg.set_gauge("worker_armed_reason",
                          1.0 if r == reason else 0.0, reason=r)


def arm_reason() -> str:
    return _arm_reason


_unsupported: str | None = None  # latched probe result ("" = capable)


def _supported() -> str | None:
    """None when a pool can run here; else the reason it never will.
    The probe is immutable for the process lifetime (core count and
    native-lib presence don't change), so it latches — armed() is on
    every stream's path and must not re-probe per call."""
    global _unsupported
    if _unsupported is not None:
        return _unsupported or None
    if (os.cpu_count() or 1) < 2:
        why = "cores"  # single core: processes only add context switches
    else:
        from .. import native
        from ..ops import gf_native

        # hh256 strided/verify kernels need the lib too.
        why = "" if (gf_native.available()
                     and native.load() is not None) else "native"
    _unsupported = why
    return why or None


def ensure_pool(n: int | None = None) -> WorkerPool | None:
    """Start (or return) the process-wide pool; None when unsupported
    or permanently disarmed. Safe to call from any thread."""
    global _pool, _atexit_registered
    with _pool_mu:
        if _pool is not None:
            if _pool.alive():
                return _pool
            _note_arm("crashes")
            return None
        why_not = _supported()
        if why_not is not None:
            _note_arm(why_not)
            return None
        global _spawn_failed_at
        if _spawn_failed_at is not None:
            import time

            if time.monotonic() - _spawn_failed_at < _SPAWN_RETRY_S:
                return None
            _spawn_failed_at = None
        pool = WorkerPool(n)
        try:
            pool.start()
        except Exception:  # noqa: BLE001 - no spawn here (e.g. sandbox)
            pool.shutdown(timeout_s=0.5)
            import time

            _spawn_failed_at = time.monotonic()
            _note_arm("spawn")
            return None
        _pool = pool
        _note_arm("armed")
        if not _atexit_registered:
            atexit.register(shutdown)
            _atexit_registered = True
        return pool


def get_pool() -> WorkerPool | None:
    with _pool_mu:
        return _pool if _pool is not None and _pool.alive() else None


def armed() -> WorkerPool | None:
    """The gate every request-plane driver consults per stream —
    DEFAULT-ON since ISSUE 11: a live pool unless MTPU_WORKER_POOL is
    explicitly off (0/off/false/no). The env knob is read per call so
    tests/operators can flip it without a restart — and an already-
    running pool does NOT capture streams once the knob is turned off
    (a bench section arming the pool must not silently change every
    later stream in the process). Single-core and no-native hosts
    never arm regardless of the knob."""
    env = os.environ.get("MTPU_WORKER_POOL", "").lower()
    if env in ("0", "off", "false", "no"):
        _note_arm("env")
        return None
    if _unsupported:
        return None  # latched: this host never arms (reason recorded)
    pool = get_pool()
    return pool if pool is not None else ensure_pool()


def _purge_strip_pools() -> None:
    """Drop the shm strip/ring pools from the shared-pool registry:
    their freelisted segments are about to be unlinked, and handing a
    dead segment to the next armed stream would crash it. A later arm
    builds fresh pools."""
    from . import buffers

    with buffers._shared_mu:
        for key in [k for k in buffers._shared
                    if isinstance(k, tuple) and k
                    and k[0] in ("shm-strips", "shm-rings")]:
            buffers._shared.pop(key, None)


def shutdown() -> None:
    """Stop the pool, drop the strip pools, and unlink every live shm
    segment (atexit; also called by tests asserting clean teardown).
    Clears the spawn cooldown so an explicit re-arm gets a real
    attempt."""
    global _pool, _spawn_failed_at
    with _pool_mu:
        pool, _pool = _pool, None
        _spawn_failed_at = None
    if pool is not None:
        pool.shutdown()
    _purge_strip_pools()
    _sweep_segments()
