"""Bit-exactness of the device GF(2^8) kernel (ops/rs.apply_gf_matrix: the
int8 einsum every device and mesh dispatch runs) against the numpy oracle,
at shard lengths no tile or lane size divides, under leading batch dims
and on a reconstruction matrix — conformance per the reference's
erasureSelfTest contract (/root/reference/cmd/erasure-coding.go:157).
tests/test_codec_golden.py holds the aligned, unbatched 12+4 case."""

import numpy as np
import pytest

from minio_tpu.ops import gf
from minio_tpu.ops.gf import gf_matmul_shards_ref
from minio_tpu.ops.rs import apply_gf_matrix


@pytest.mark.parametrize("k,m", [(2, 2), (4, 2), (8, 4), (12, 4), (8, 8),
                                 (14, 2), (16, 16)])
def test_einsum_matches_oracle(k, m):
    rng = np.random.default_rng(k * 100 + m)
    s = 333  # deliberately unaligned to tile/lane sizes
    mat = gf.parity_matrix(k, m)
    shards = rng.integers(0, 256, size=(2, k, s), dtype=np.uint8)
    got = np.asarray(apply_gf_matrix(gf.bit_matrix(mat), shards))
    want = np.stack([gf_matmul_shards_ref(mat, shards[i]) for i in range(2)])
    assert got.dtype == np.uint8
    assert np.array_equal(got, want)


def test_einsum_handles_lead_dims():
    rng = np.random.default_rng(7)
    k, m, s = 12, 4, 260
    mat = gf.parity_matrix(k, m)
    shards = rng.integers(0, 256, size=(2, 3, k, s), dtype=np.uint8)
    got = np.asarray(apply_gf_matrix(gf.bit_matrix(mat), shards))
    assert got.shape == (2, 3, m, s)
    for i in range(2):
        for j in range(3):
            assert np.array_equal(got[i, j],
                                  gf_matmul_shards_ref(mat, shards[i, j]))


def test_einsum_reconstruct_matrix():
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
    got = np.asarray(apply_gf_matrix(gf.bit_matrix(rec), sub[None]))[0]
    assert np.array_equal(got[0], data[0])
    assert np.array_equal(got[1], data[5])
