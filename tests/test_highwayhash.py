"""HighwayHash-256 conformance: golden self-test chain from the reference
(/root/reference/cmd/bitrot.go:207-238), magic-key derivation (remainder
path), numpy<->JAX agreement, and batch semantics."""

import numpy as np
import pytest

from minio_tpu.ops.highwayhash import (
    MAGIC_KEY,
    HighwayHash256,
    hash256,
    hash256_batch,
)
from minio_tpu.ops.highwayhash_jax import hash256_batch_jax

GOLDEN_CHAIN = "39c0407ed3f01b18d22c85db4aeff11e060ca5f43131b0126731ca197cd42313"


def test_bitrot_selftest_chain():
    # hash.Size()*hash.BlockSize() = 32*32 iterations of hash-and-append.
    h = HighwayHash256(MAGIC_KEY)
    msg = bytearray()
    sum_ = b""
    for _ in range(32):
        h.reset()
        h.update(bytes(msg))
        sum_ = h.digest()
        msg += sum_
    assert sum_.hex() == GOLDEN_CHAIN


def test_magic_key_derivation():
    # cmd/bitrot.go:33 — the key is HH-256 of the first 100 decimals of pi
    # (utf-8) under a zero key; 100 % 32 == 4 exercises UpdateRemainder.
    pi100 = (
        "1415926535897932384626433832795028841971693993751058209749445923"
        "078164062862089986280348253421170679"
    )
    assert hash256(pi100.encode(), key=bytes(32)) == MAGIC_KEY


def test_streaming_matches_oneshot():
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, size=1000, dtype=np.uint8).tobytes()
    h = HighwayHash256()
    for off in range(0, 1000, 77):  # uneven write sizes
        h.update(data[off : off + 77])
    assert h.digest() == hash256(data)


# Every remainder 0..31 with no whole packet and with one, the two shard
# lengths the benchmark's cells run (87,382 = 2,730 packets + 22 bytes at
# 12+4, 524,288 = 16,384 packets at 2+2), each at a small batch; then the
# batch shapes the cells run, and one unbatched [L].
_LENGTHS = [((3,), n) for n in range(64)] + [((2,), 87382), ((1, 2), 524288)]
_BATCHES = [((8, 16), 1000), ((2, 16), 1000), ((1, 4), 2048), ((8, 2), 1000),
            ((2, 2), 1000), ((), 333), ((3,), 100), ((3,), 1024),
            ((3,), 4096 + 21)]


@pytest.mark.parametrize(
    "batch,length", _LENGTHS + _BATCHES,
    ids=lambda v: "x".join(map(str, v)) or "unbatched"
    if isinstance(v, tuple) else str(v))
def test_jax_matches_numpy(batch, length):
    rng = np.random.default_rng(length + len(batch))
    data = rng.integers(0, 256, size=batch + (length,), dtype=np.uint8)
    want = hash256_batch(data)
    got = np.asarray(hash256_batch_jax(data))
    np.testing.assert_array_equal(want, got)


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of nested jaxprs (scan and loop
    bodies, inner jits) after the equation that holds them."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                yield from _eqns(inner)


def test_packet_step_is_elementwise_and_the_program_small():
    """What PR 30's gain rests on, where tier-1 sees it: at the 12+4 cells'
    shape the scan's body (one packet) holds arithmetic alone, nothing the
    chip's compiler cannot fuse across, and the whole program is a quarter
    of the 5,172 equations it was (429 in the body, ten unrolled
    finalisation rounds outside). The one exception: the step reads its
    eight words off the leading axis of the packet's [8, B, n] slab, eight
    static picks that cost the chip an address each (one stacked input
    measured faster there than eight arrays, each sliced per loop trip)."""
    import collections

    import jax
    import jax.numpy as jnp

    from minio_tpu.ops.highwayhash_jax import _build_hash_fn

    length = 87382
    jaxpr = jax.make_jaxpr(_build_hash_fn(length, MAGIC_KEY))(
        jax.ShapeDtypeStruct((8, 16, length), jnp.uint8)).jaxpr
    eqns = list(_eqns(jaxpr))
    scans = [e for e in eqns if e.primitive.name == "scan"
             and e.params["length"] == length // 32]
    assert len(scans) == 1
    body = collections.Counter(
        e.primitive.name for e in _eqns(scans[0].params["jaxpr"].jaxpr))
    assert (body.pop("slice"), body.pop("squeeze")) == (8, 8)
    assert not set(body) & {
        "gather", "concatenate", "reshape", "transpose", "dynamic_slice",
        "convert_element_type", "broadcast_in_dim", "iota",
    }, sorted(body)
    assert len(eqns) < 1500, len(eqns)


def test_batch_consistent_with_single():
    rng = np.random.default_rng(5)
    chunks = rng.integers(0, 256, size=(4, 131072), dtype=np.uint8)
    batch = hash256_batch(chunks)
    for i in range(4):
        assert batch[i].tobytes() == hash256(chunks[i].tobytes())
