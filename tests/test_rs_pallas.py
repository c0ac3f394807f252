"""Bit-exactness of the fused Pallas GF(2^8) kernel (interpret mode on
CPU; the same kernel compiles natively on TPU) against the numpy oracle
and the einsum formulation — conformance per the reference's
erasureSelfTest contract (/root/reference/cmd/erasure-coding.go:157)."""

import numpy as np
import pytest

from minio_tpu.ops import gf
from minio_tpu.ops.gf import gf_matmul_shards_ref
from minio_tpu.ops.rs import apply_gf_matrix
from minio_tpu.ops.rs_pallas import apply_gf_matrix_pallas


@pytest.mark.parametrize("k,m", [(2, 2), (4, 2), (8, 4), (12, 4), (8, 8),
                                 (14, 2), (16, 16)])
def test_pallas_matches_oracle(k, m):
    rng = np.random.default_rng(k * 100 + m)
    s = 333  # deliberately unaligned to tile/lane sizes
    mat = gf.parity_matrix(k, m)
    bm = gf.bit_matrix(mat)
    shards = rng.integers(0, 256, size=(2, k, s), dtype=np.uint8)
    got = np.asarray(
        apply_gf_matrix_pallas(bm, shards, tile=128, interpret=True)
    )
    want = np.stack([gf_matmul_shards_ref(mat, shards[i]) for i in range(2)])
    assert np.array_equal(got, want)


def test_pallas_matches_einsum_and_handles_lead_dims():
    rng = np.random.default_rng(7)
    k, m, s = 12, 4, 260
    bm = gf.bit_matrix(gf.parity_matrix(k, m))
    shards = rng.integers(0, 256, size=(2, 3, k, s), dtype=np.uint8)
    got = np.asarray(
        apply_gf_matrix_pallas(bm, shards, tile=256, interpret=True)
    )
    want = np.asarray(apply_gf_matrix(bm, shards))
    assert got.shape == want.shape == (2, 3, m, s)
    assert np.array_equal(got, want)


def test_pallas_reconstruct_matrix():
    """Decode path: reconstruct missing data shards via the kernel."""
    rng = np.random.default_rng(3)
    k, m, s = 12, 4, 500
    full = gf.rs_matrix(k, m)
    data = rng.integers(0, 256, size=(k, s), dtype=np.uint8)
    allshards = gf_matmul_shards_ref(full, data)  # [k+m, s]
    # Lose 4 shards: data 0, 5 and parity 12, 15; reconstruct data 0, 5.
    present = [i for i in range(k + m) if i not in (0, 5, 12, 15)]
    rec = gf.reconstruct_matrix(k, m, present, [0, 5])
    sub = allshards[present[:k]]
    got = np.asarray(
        apply_gf_matrix_pallas(gf.bit_matrix(rec), sub[None],
                               tile=256, interpret=True)
    )[0]
    assert np.array_equal(got[0], data[0])
    assert np.array_equal(got[1], data[5])
