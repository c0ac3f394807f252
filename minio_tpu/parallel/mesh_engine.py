"""Mesh serving engine: the multi-chip erasure plane as a production
PUT/GET/heal path.

The lane-sharded GF encode / reconstruct / device bitrot digests of
`parallel/sharded.ShardedErasure` behind EXACTLY the async-codec seams
the fused device engine serves (`erasure/device_engine.DeviceCodec`),
so the streaming drivers in `erasure/streaming.py` — HostFeed-staged,
double-buffered, quorum-fan-out on the write side — run on a mesh
without a line of driver duplication. Its proofs: byte-exactness on
virtual CPU devices (`tests/test_mesh_engine.py`), `chip_smoke.py
--chips 4`, and the benchmark's cell `n16mesh4-put10m` on a four-chip
host (dp=1 x lane=4; PERF.md):

- ``encode_async(blocks, with_hashes)`` — ONE pjit dispatch per
  [B, k, S] batch computes the lane-sharded stripe's parity AND the
  HighwayHash-256 bitrot digests of all k+m shards. The parity matmul
  partitions over the 'lane' axis (each mesh column owns its stripe
  rows — the "disk" analog of SURVEY §5.7), digests are lane-local,
  and only parity + digests cross back to the host, D2H in flight at
  return. The staged input batch is donated to XLA.
- ``reconstruct_async(src, present, targets, with_hashes)`` — fused
  rebuild of `targets` shards from the first k `present` shards, one
  compiled program per batch shape and target count (the failure
  pattern is its matrix argument), shard bytes split over 'lane'
  inside the program so reconstruction uses the whole mesh even at
  dp=1, gathered back for the stale-disk writers and the GET's client
  write (erasure/streaming._decode_stream_fused).

Batch padding: the dp axis shards the batch dim, so a ragged last
batch (B % dp != 0) is zero-padded on the host and the outputs lazily
sliced back — steady-state full batches (B = 8) divide every
power-of-two dp and never pay it.

Telemetry (parallel/metrics.py) guards the dispatch invariant the same
way device_engine.STATS does: mesh_dispatches_total must equal
mesh_batches_total and mesh_retraces_total must stay flat across
same-shape batches. Everything runs identically on a virtual CPU mesh
(XLA_FLAGS=--xla_force_host_platform_device_count=8), which is how CI
proves the serving path without a TPU.
"""

from __future__ import annotations

import functools
import threading

import numpy as np

from ..erasure.device_engine import (
    HostFeed,
    _d2h_async,
    _is_device_array,
    _quiet_cpu_donation_warning,
)
from ..observability import spans as _spans
from . import metrics as mesh_metrics
from . import placement


class MeshCodec:
    """Fused mesh dispatcher for one (k, m) geometry on one mesh shape.

    Obtain via :func:`for_geometry` — the cache keys on (k, m, dp,
    lanes) so every PUT/GET/heal of one erasure set reuses the same
    compiled programs and device-resident matrices across requests.
    """

    def __init__(self, data_blocks: int, parity_blocks: int, mesh,
                 codec: str | None = None):
        import math

        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..erasure import registry
        from ..ops import gf

        self.k = data_blocks
        self.m = parity_blocks
        self.n = data_blocks + parity_blocks
        self.codec_id = codec or registry.DEFAULT_CODEC
        self._entry = registry.get(self.codec_id)
        self.mesh = mesh
        self.dp = mesh.shape["dp"]
        self.lanes = mesh.shape["lane"]
        # ONE compiled batch shape serves everything: rows pad to the
        # smallest multiple of dp that fits the steady-state batch.
        # For dp dividing _BATCH_PAD (every power-of-two mesh) that is
        # exactly _BATCH_PAD — zero waste and the H2D feed stages
        # steady-state batches directly. For dp=3 on 12 devices it is
        # 9 (one padded row), where lcm(dp, 8)=24 would triple every
        # dispatch's compute and collective bytes.
        self._pad_rows = self.dp * math.ceil(self._BATCH_PAD / self.dp)
        if self.n % self.lanes != 0:
            raise ValueError(
                f"k+m={self.n} must divide over lane dim {self.lanes}"
            )
        self._parity_bits_np = gf.bit_matrix_for(
            self._entry.parity_matrix(data_blocks, parity_blocks)
        )
        self.data_spec = NamedSharding(mesh, P("dp", None, None))
        self.stripe_spec = NamedSharding(mesh, P("dp", "lane", None))
        self.lane_digest_spec = NamedSharding(mesh, P("dp", "lane", None))
        self.replicated = NamedSharding(mesh, P())
        self._lock = threading.Lock()
        self._dev_mats: dict = {}
        self._fns: dict = {}
        self._gauged: set = set()
        mesh_metrics.record_shape(self.dp, self.lanes, self.n)

    # --- cached device operands / compiled functions (one protocol for
    # encode and reconstruct, mirroring DeviceCodec._get_fn) ---

    def _dev_mat(self, key, np_bits):
        with self._lock:
            mat = self._dev_mats.get(key)
        if mat is not None:
            return mat
        import jax

        mat = jax.device_put(np_bits, self.replicated)
        with self._lock:
            self._dev_mats.setdefault(key, mat)
            return self._dev_mats[key]

    def _get_fn(self, key, make_impl, out_shardings):
        with self._lock:
            fn = self._fns.get(key)
        if fn is not None:
            return fn
        import jax

        _quiet_cpu_donation_warning()
        fn = jax.jit(
            make_impl(),
            in_shardings=(self.replicated, self.data_spec),
            out_shardings=out_shardings,
            donate_argnums=(1,),
        )
        with self._lock:
            self._fns.setdefault(key, fn)
            return self._fns[key]

    # --- staging ---

    # The streaming drivers form steady-state batches of 8 blocks
    # (ParallelReader.BATCH_BLOCKS / _DEVICE_HEAL_BATCH); host-staged
    # batches zero-pad UP to _pad_rows (the dp-aligned cover of this),
    # so a tail of any size reuses one compiled program instead of
    # paying a fresh multi-second XLA compile per distinct tail length
    # (degraded range-GETs would otherwise hit up to 7 tail shapes per
    # failure pattern).
    _BATCH_PAD = 8

    def _stage(self, blocks):
        """blocks -> (device array we own, actual batch rows). Host
        batches are zero-padded to a multiple of both dp and the
        steady-state batch size; the caller slices outputs back to the
        actual row count."""
        if _is_device_array(blocks):
            return blocks, blocks.shape[0]
        import jax

        # Identity for contiguous uint8 input; a real host-side fixup
        # copy is counted before the H2D.
        from ..pipeline.buffers import ascontig_counted

        b = ascontig_counted(blocks, "put.device_stage")
        n = b.shape[0]
        pad = (-n) % self._pad_rows
        mesh_metrics.record("mesh_padded_blocks_total", pad)
        if pad:
            b = np.concatenate(
                [b, np.zeros((pad,) + b.shape[1:], dtype=np.uint8)]
            )
        with _spans.span("device-h2d", "mesh"):
            return jax.device_put(b, self.data_spec), n

    def host_feed(self):
        """The pipelined driver's H2D stage for this mesh: dp-shards the
        staged batch per dp-group (double buffering comes from the
        executor's bounded queues, exactly like the device engine's
        HostFeed). Ragged batches stay on the host — encode_async pads
        and stages those itself."""
        feed = getattr(self, "_feed", None)
        if feed is None:
            # Already-padded batches only: anything else staged here
            # would reach encode_async as a device array, skip _stage's
            # zero-pad, and compile a fresh program per tail shape.
            # (When dp doesn't divide the steady-state batch, every
            # batch needs a host-side pad, so the H2D overlap stage
            # stays out of the loop on those shapes.)
            full = self._pad_rows
            feed = HostFeed(
                "h2d-mesh", sharding=self.data_spec,
                accept=lambda b: b.shape[0] % full == 0,
            )
            self._feed = feed
        return feed

    # --- encode (PUT path) ---

    def encode_async(self, blocks, with_hashes: bool):
        """One fused mesh dispatch: blocks [B, k, S] (host ndarray or
        dp-sharded staged array) -> (parity [B, m, S], digests
        [B, k+m, 32] | None), D2H in flight, input donated."""
        dev, n_rows = self._stage(blocks)
        s = dev.shape[-1]
        key = ("enc", with_hashes, dev.shape)

        def make():
            import jax
            import jax.numpy as jnp

            from ..ops.highwayhash_jax import hash256_batch_jax
            from ..ops.rs import apply_gf_matrix

            k = self.k

            def impl(bitmat, data):
                mesh_metrics.record("mesh_retraces_total")  # trace-time
                parity = apply_gf_matrix(bitmat, data)
                stripe = jnp.concatenate([data, parity], axis=1)
                # The lane scatter: each mesh column owns its k+m/lanes
                # stripe rows — parity rows compute lane-local against
                # the dp-replicated data, digests hash lane-local.
                stripe = jax.lax.with_sharding_constraint(
                    stripe, self.stripe_spec
                )
                if not with_hashes:
                    return stripe[:, k:, :]
                digests = jax.lax.with_sharding_constraint(
                    hash256_batch_jax(stripe), self.lane_digest_spec
                )
                return stripe[:, k:, :], digests

            return impl

        out_shard = (
            (self.data_spec, self.data_spec) if with_hashes
            else self.data_spec
        )
        fn = self._get_fn(key, make, out_shard)
        bitmat = self._dev_mat("parity", self._parity_bits_np)
        b_padded = dev.shape[0]
        self._record_batch(
            blocks=n_rows,
            collective=b_padded * self.m * s
            + (b_padded * self.n * 32 if with_hashes else 0),
            stripe_bytes=b_padded * s,
        )
        with _spans.span("device-call", "enc"):
            out = self._dispatch(fn, bitmat, dev)
        parity, digests = out if with_hashes else (out, None)
        if n_rows != b_padded:
            parity = parity[:n_rows]
            digests = digests[:n_rows] if digests is not None else None
        _d2h_async(parity)
        _d2h_async(digests)
        return parity, digests

    # --- reconstruct (degraded GET / heal) ---

    def _recon_bits(self, present: tuple, targets: tuple) -> np.ndarray:
        from ..erasure import registry

        if self.codec_id == registry.DEFAULT_CODEC:
            # Dense keeps the shared lru of the SPMD proving ground.
            from .sharded import _recon_bits_np

            return _recon_bits_np(self.k, self.m, tuple(present),
                                  tuple(targets))
        from ..ops import gf

        return gf.bit_matrix_for(
            self._entry.reconstruct_matrix(self.k, self.m, list(present),
                                           list(targets))
        )

    def reconstruct_async(self, src, present, targets,
                          with_hashes: bool = False):
        """One fused mesh dispatch rebuilding `targets` shards from the
        first k `present` shards: src [B, k, S] rows ordered as
        present[:k] -> (rebuilt [B, T, S], digests [B, T, 32] | None).
        Compiled + matrix-cached per failure pattern; shard bytes are
        split over the lane axis inside the program (padded to the lane
        dim when S doesn't divide), so a dp=1 mesh still reconstructs
        on every device, then all-gathers the rebuilt shards."""
        from ..erasure import registry

        present = tuple(present[: self.k])
        targets = tuple(targets)
        dev, n_rows = self._stage(src)
        s = dev.shape[-1]
        key = ("rec", with_hashes, dev.shape)

        def make():
            import jax
            import jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..ops.highwayhash_jax import hash256_batch_jax
            from ..ops.rs import apply_gf_matrix

            lanes = self.lanes
            s_pad = (-s) % lanes
            byte_spec = NamedSharding(self.mesh, P("dp", None, "lane"))

            def impl(bitmat, blocks):
                mesh_metrics.record("mesh_retraces_total")  # trace-time
                if s_pad:
                    blocks = jnp.pad(blocks,
                                     ((0, 0), (0, 0), (0, s_pad)))
                # Byte-axis lane split: GF matmul is elementwise over
                # S, so every lane rebuilds its slice of the target
                # shards — the all-gather happens on the way out.
                blocks = jax.lax.with_sharding_constraint(
                    blocks, byte_spec
                )
                out = apply_gf_matrix(bitmat, blocks)
                out = jax.lax.with_sharding_constraint(out, byte_spec)
                if s_pad:
                    out = out[:, :, :s]
                if not with_hashes:
                    return out
                return out, hash256_batch_jax(out)

            return impl

        out_shard = (
            (self.data_spec, self.data_spec) if with_hashes
            else self.data_spec
        )
        fn = self._get_fn(key, make, out_shard)
        bitmat = self._dev_mat(("rec", present, targets),
                               self._recon_bits(present, targets))
        b_padded = dev.shape[0]
        registry.note_dispatch(self.codec_id, "mesh", "reconstruct")
        self._record_batch(
            blocks=n_rows,
            collective=b_padded * len(targets) * s
            + (b_padded * len(targets) * 32 if with_hashes else 0),
            stripe_bytes=0,
        )
        with _spans.span("device-call", "rec"):
            out = self._dispatch(fn, bitmat, dev)
        rebuilt, digests = out if with_hashes else (out, None)
        if n_rows != b_padded:
            rebuilt = rebuilt[:n_rows]
            digests = digests[:n_rows] if digests is not None else None
        _d2h_async(rebuilt)
        _d2h_async(digests)
        return rebuilt, digests

    # --- telemetry ---

    def _dispatch(self, fn, *args):
        """THE collective-call chokepoint: every invocation of a
        compiled mesh program must come through here so
        mesh_dispatches_total counts actual pjit calls — batches are
        counted separately at batch entry (_record_batch), which is
        what keeps the dispatches-per-batch == 1.0 guards falsifiable
        if a future change splits one batch into several collectives."""
        mesh_metrics.record("mesh_dispatches_total")
        out = fn(*args)
        if fn not in self._gauged:
            # a compiled function's output sharding never changes
            self._gauged.add(fn)
            first = out[0] if isinstance(out, tuple) else out
            mesh_metrics.record_output_devices(
                len(first.sharding.device_set))
        return out

    def _record_batch(self, blocks: int, collective: int,
                      stripe_bytes: int) -> None:
        mesh_metrics.record("mesh_batches_total")
        mesh_metrics.record("mesh_blocks_total", blocks)
        mesh_metrics.record("mesh_collective_bytes_total", collective)
        if stripe_bytes:
            rows_per_lane = self.n // self.lanes
            for lane in range(self.lanes):
                mesh_metrics.record_lane_bytes(
                    lane, stripe_bytes * rows_per_lane
                )


@functools.lru_cache(maxsize=32)
def _codec_for(data_blocks: int, parity_blocks: int, dp: int,
               lanes: int, codec: str | None = None) -> MeshCodec:
    mesh = placement.get_mesh(data_blocks + parity_blocks)
    if mesh is None or mesh.shape["dp"] != dp or mesh.shape["lane"] != lanes:
        # Shape env changed between selection and codec build (tests
        # flipping MTPU_MESH_SHAPE): build the requested shape directly.
        from .sharded import make_mesh

        mesh = make_mesh(dp * lanes, lanes=lanes)
    return MeshCodec(data_blocks, parity_blocks, mesh, codec)


def for_geometry(data_blocks: int, parity_blocks: int,
                 codec: str | None = None) -> MeshCodec:
    """The (geometry, codec)-keyed mesh codec cache. Raises RuntimeError
    when no mesh shape fits — callers reach here only after the registry
    selector validated the fit, so this is a programming-error guard,
    not a runtime fallback path."""
    shape = placement.select_shape(data_blocks + parity_blocks)
    if shape is None:
        raise RuntimeError(
            f"no mesh shape fits k+m={data_blocks + parity_blocks} on "
            f"{placement.device_count(initialize=True)} device(s)"
        )
    return _codec_for(data_blocks, parity_blocks, *shape, codec)
