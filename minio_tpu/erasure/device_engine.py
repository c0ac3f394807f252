"""Fused single-dispatch device codec for the erasure hot path.

Once the GF kernel is fast, throughput is decided by data movement and
invocation overhead (the lesson of the XOR-coding optimization
literature, arXiv:2108.02692): a per-batch orchestration of serial
h2d -> compute -> d2h with fresh device allocations every batch throws
away most of even the transfer ceiling.

This module is structured so each [B, k, S] batch costs:

- ONE dispatch: GF parity matmul (ops/rs.py einsum path) and the
  HighwayHash-256 bitrot digests of all k+m shards
  (ops/highwayhash_jax.py) trace into a single jitted computation.
  ``STATS["dispatches"]`` counts invocations and ``STATS["traces"]``
  counts (re)traces so tests can pin dispatches-per-batch == 1 and
  steady-state recompiles == 0.
- DONATED input buffers: the staged H2D batch (HostFeed, below) is
  donated to XLA (``donate_argnums``), so the runtime recycles the
  8 MiB device allocation into the outputs instead of growing the
  arena every batch. The host copy lives on in the pooled strip
  buffer — the data shards are written from host memory, so the
  donated device bytes are never needed again.
- ASYNC D2H: only parity and digests return to host; their
  ``copy_to_host_async`` starts immediately after dispatch, so the
  transfer of batch N overlaps the compute of batch N+1 and the
  shard-write fan-out of batch N-1 (the 3-deep ring the streaming
  drivers run on pipeline/executor.Pipeline).
- Geometry-keyed caches: codecs and compiled functions are cached by
  (k, m) and by what the function reads; device-resident bit-matrices
  and reconstruction matrices by (k, m[, survivors, targets]). A
  failure pattern is a matrix, an argument of the one compiled
  reconstruct function, so steady-state PUT/GET/heal never re-derives
  a matrix, and a new pattern of a known batch shape never re-traces.

The same fused/overlapped treatment covers the read side:
``reconstruct_async`` rebuilds target shards (for heal, AND their
bitrot digests) in one dispatch per batch of blocks (consumed by
erasure/streaming._heal_stream_fused and _decode_stream_fused).

Everything here runs identically on CPU (JAX_PLATFORMS=cpu), which is
how tier-1 exercises the fused path bit-exactly against the host
oracles without a TPU attached.
"""

from __future__ import annotations

import functools
import threading
import warnings

import numpy as np

from ..observability import spans as _spans

# Module counters — the dispatch/trace regression guard read by
# test_bench_smoke and reported by bench.py's device section.
#   dispatches      one per fused call actually sent to the device
#   traces          one per XLA (re)trace of a fused function; flat
#                   counts across same-geometry batches prove the
#                   compiled-function caches hit
#   donated_batches input buffers OFFERED to XLA for reuse (the runtime
#                   may decline for a layout — on device backends that
#                   surfaces as jax's "donated buffers were not usable"
#                   warning, which is left visible there on purpose)
#   async_d2h       outputs whose host copy started at dispatch time
STATS = {"dispatches": 0, "traces": 0, "donated_batches": 0,
         "async_d2h": 0}
_stats_lock = threading.Lock()

_quieted_cpu_warning = False


def _quiet_cpu_donation_warning() -> None:
    """On the CPU backend (tier-1 runs) XLA routinely declines donation
    and warns per compile — pure noise there, since CPU is never the
    deployment target of this engine. Device backends keep the warning:
    it is the only signal that arena reuse did NOT happen."""
    global _quieted_cpu_warning
    if _quieted_cpu_warning:
        return
    _quieted_cpu_warning = True
    import jax

    if jax.default_backend() == "cpu":
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )


def _stat(name: str, n: int = 1) -> None:
    with _stats_lock:
        STATS[name] += n


def stats_snapshot() -> dict:
    with _stats_lock:
        return dict(STATS)


def reset_stats() -> None:
    with _stats_lock:
        for k in STATS:
            STATS[k] = 0


def _is_device_array(x) -> bool:
    return not isinstance(x, np.ndarray) and hasattr(x, "block_until_ready")


def to_host(*handles):
    """Materialise device results on the host, one ndarray per handle
    (None stays None). The D2H started at dispatch, so the time in here
    is the host blocked until the device has finished: the `device-wait`
    span, the one place the streaming drivers wait for the chip."""
    # a host engine's ndarrays come through here too: nothing to wait for
    waits = any(_is_device_array(h) for h in handles)
    with _spans.span("device-wait") if waits else _spans.NULL:
        out = tuple(None if h is None else np.asarray(h) for h in handles)
    return out[0] if len(out) == 1 else out


def _d2h_async(arr) -> None:
    """Start the host copy of a device output without blocking; a later
    np.asarray finds the bytes already (or nearly) landed."""
    if arr is None:
        return
    arr.copy_to_host_async()
    _stat("async_d2h")


class HostFeed:
    """Pipelined host→device staging stage for the device encode engine.

    An encode loop that does H2D, dispatch and D2H from ONE host
    thread leaves the link idle while the host packs or flushes. Run
    as a stage of pipeline/executor.Pipeline, this callable moves the
    H2D copy onto its own worker: the transfer of batch N+1 overlaps
    the MXU compute of batch N and the host write fan-out of batch
    N-1 — double buffering falls out of the executor's bounded queues
    (queue_depth=1 keeps exactly one staged batch ahead).

    The transfer is COMPLETED inside the stage (block_until_ready):
    returning a lazy handle would make the dispatch stage pay the wait
    and re-serialize the feed. Per-stage items/bytes/timing telemetry
    comes from the executor's StageStats, not from this class.

    `sharding` stages onto a sharded layout (the mesh engine's
    dp-groups) instead of the default device; `accept` gates which
    batches stage at all — a declined batch passes through on the host
    and the downstream codec stages it itself (the mesh engine declines
    ragged batches whose row count doesn't divide dp, since those need
    padding the feed must not own).
    """

    def __init__(self, name: str = "h2d", sharding=None, accept=None):
        self.name = name
        self._sharding = sharding
        self._accept = accept

    def __call__(self, batch):
        import jax

        if self._accept is not None and not self._accept(batch):
            return batch
        with _spans.span("device-h2d",
                         "mesh" if self._sharding is not None else "device"):
            if self._sharding is not None:
                dev = jax.device_put(batch, self._sharding)
            else:
                dev = jax.device_put(batch)
            dev.block_until_ready()
        return dev


class DeviceCodec:
    """Fused encode/reconstruct dispatcher for one (k, m) geometry.

    Obtain via :func:`for_geometry` — the cache is what makes repeated
    PUT/heal calls hit the same compiled functions and device-resident
    matrices.
    """

    def __init__(self, data_blocks: int, parity_blocks: int,
                 codec: str | None = None):
        from ..ops import gf
        from . import registry

        self.k = data_blocks
        self.m = parity_blocks
        self.codec_id = codec or registry.DEFAULT_CODEC
        self._entry = registry.get(self.codec_id)
        self._parity_bits_np = gf.bit_matrix_for(
            self._entry.parity_matrix(data_blocks, parity_blocks)
        )
        self._lock = threading.Lock()
        self._dev_mats: dict = {}  # key -> device-resident bit-matrix
        self._fns: dict = {}       # key -> jitted fused fn

    # --- cached device operands / compiled functions ---

    def _dev_mat(self, key, np_bits):
        with self._lock:
            mat = self._dev_mats.get(key)
        if mat is not None:
            return mat
        import jax

        mat = jax.device_put(np_bits)
        with self._lock:
            self._dev_mats.setdefault(key, mat)
            return self._dev_mats[key]

    def _get_fn(self, key, make_impl):
        """ONE compiled-function cache protocol for every fused entry
        point (encode and reconstruct must never drift apart): build the
        impl, jit it with the input batch donated, publish under the
        lock. Donating `blocks` lets XLA recycle the staged input
        batch's device memory for the outputs; the caller never reads
        the device copy again (data shards are written from host
        memory)."""
        with self._lock:
            fn = self._fns.get(key)
        if fn is not None:
            return fn
        import jax

        _quiet_cpu_donation_warning()
        fn = jax.jit(make_impl(), donate_argnums=(1,))
        with self._lock:
            self._fns.setdefault(key, fn)
            return self._fns[key]

    def _note_trace(self) -> None:
        from . import registry

        _stat("traces")
        registry.note_trace(self.codec_id, "device")

    def _fused_fn(self, key, with_hashes: bool):
        def make():
            import jax.numpy as jnp

            from ..ops.highwayhash_jax import hash256_batch_jax
            from ..ops.rs import apply_gf_matrix

            def impl(bitmat, blocks):
                self._note_trace()  # runs at trace time only
                out = apply_gf_matrix(bitmat, blocks)
                if not with_hashes:
                    return out
                all_shards = jnp.concatenate([blocks, out], axis=1)
                return out, hash256_batch_jax(all_shards)

            return impl

        return self._get_fn(key, make)

    def _stage(self, blocks):
        """blocks -> device array we own (safe to donate)."""
        if _is_device_array(blocks):
            return blocks
        import jax

        # Identity for the pooled strip buffers (contiguous uint8); a
        # real host-side fixup copy is counted before the H2D.
        from ..pipeline.buffers import ascontig_counted

        with _spans.span("device-h2d", "device"):
            return jax.device_put(ascontig_counted(blocks,
                                                   "put.device_stage"))

    # --- encode ---

    def encode_async(self, blocks, with_hashes: bool):
        """One fused dispatch: blocks [B, k, S] (host ndarray or staged
        device array) -> (parity [B, m, S], digests [B, k+m, 32] | None),
        both device arrays with their D2H already in flight. The input
        batch buffer is donated."""
        dev = self._stage(blocks)
        fn = self._fused_fn(("enc", with_hashes), with_hashes)
        bitmat = self._dev_mat("parity", self._parity_bits_np)
        _stat("dispatches")
        _stat("donated_batches")
        # trace + lower + cache lookup + enqueue: where a re-trace lands
        with _spans.span("device-call", "enc"):
            out = fn(bitmat, dev)
        parity, digests = out if with_hashes else (out, None)
        _d2h_async(parity)
        _d2h_async(digests)
        return parity, digests

    # --- reconstruct (heal / degraded read) ---

    def _recon_bits(self, present: tuple, targets: tuple) -> np.ndarray:
        from ..ops import gf

        return gf.bit_matrix_for(
            self._entry.reconstruct_matrix(self.k, self.m, list(present),
                                           list(targets))
        )

    def reconstruct_async(self, src, present, targets,
                          with_hashes: bool = False):
        """One fused dispatch rebuilding `targets` shards from the first
        k `present` shards: src [B, k, S] (rows ordered as present[:k])
        -> (rebuilt [B, T, S], digests [B, T, 32] | None), D2H in
        flight, input donated. The compiled function is keyed by what
        `impl` reads, `with_hashes`; the batch's shape and the matrix's
        rows (the target count) are jax.jit's own key. The failure
        pattern is an argument, the device-resident matrix cached per
        (present, targets): a pattern never seen before costs one small
        device_put and no trace."""
        present = tuple(present[: self.k])
        targets = tuple(targets)

        def make():
            from ..ops.highwayhash_jax import hash256_batch_jax
            from ..ops.rs import apply_gf_matrix

            def impl(bitmat, blocks):
                self._note_trace()
                out = apply_gf_matrix(bitmat, blocks)
                if not with_hashes:
                    return out
                return out, hash256_batch_jax(out)

            return impl

        from . import registry

        fn = self._get_fn(("rec", with_hashes), make)
        bitmat = self._dev_mat(("rec", present, targets),
                               self._recon_bits(present, targets))
        dev = self._stage(src)
        registry.note_dispatch(self.codec_id, "device", "reconstruct")
        _stat("dispatches")
        _stat("donated_batches")
        with _spans.span("device-call", "rec"):
            out = fn(bitmat, dev)
        rebuilt, digests = out if with_hashes else (out, None)
        _d2h_async(rebuilt)
        _d2h_async(digests)
        return rebuilt, digests


@functools.lru_cache(maxsize=64)
def for_geometry(data_blocks: int, parity_blocks: int,
                 codec: str | None = None) -> DeviceCodec:
    """The (geometry, codec)-keyed codec cache: every PUT/heal of the
    same erasure set shares one codec — one set of compiled functions,
    one device-resident parity matrix."""
    return DeviceCodec(data_blocks, parity_blocks, codec)
