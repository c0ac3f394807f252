"""The plain reference of a read: from any k of an object's k+m shard
files, the object.

Independent of the program, as `reference.py` is: it imports nothing of
`minio_tpu`, of JAX or of the C library. It takes the shard files as the
drives hold them (`HighwayHash-256(chunk) || chunk` for every erasure
block), verifies every frame with the reference's own hash, drops a
shard that has a frame that fails, and recovers the data by Gaussian
elimination over GF(2^8) with the reference's own tables: the k rows of
the coding matrix that belong to the first k good shards are inverted,
and the inverse is applied to their chunks. The shards that were not
there are computed again from the data, so a test can hold what the
program rebuilt against them byte for byte.

The guarantee this stands behind is `degraded_read` in
`benchmark/configs/node12-ec8p4-dev1.json`; the tests that hold the served
path to it are `tests/test_ec8p4_degraded_get.py` and
`tests/benchmark_gate/test_read_cells.py`.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from . import reference
from .reference import DIGEST


class TooFewShards(Exception):
    """Fewer than k shard files passed their digests: no answer."""


@dataclass
class Decoded:
    body: bytes
    # shard index (1-based, as xl.meta counts) -> chunks [blocks, S] of
    # every shard the caller did not hand in or that was dropped
    rebuilt: dict[int, np.ndarray] = field(default_factory=dict)
    dropped: list[int] = field(default_factory=list)   # a frame failed
    verified_bytes: int = 0                  # chunk bytes that passed


def split_frames(raw: bytes, block_size: int, k: int,
                 size: int) -> tuple[np.ndarray, np.ndarray]:
    """A shard file of an object of `size` bytes, a whole number of
    blocks long -> (digests [blocks, 32], chunks [blocks, S])."""
    if size % block_size:
        raise ValueError("the reference takes objects a whole number of "
                         "blocks long")
    s = reference.shard_size(block_size, k)
    frames = np.frombuffer(raw, dtype=np.uint8)
    if frames.size != (size // block_size) * (DIGEST + s):
        raise ValueError(f"a shard file of {frames.size} B is not "
                         f"{size // block_size} frames of {DIGEST + s} B")
    frames = frames.reshape(-1, DIGEST + s)
    return frames[:, :DIGEST], frames[:, DIGEST:]


def coding_matrix(codec: str, k: int, m: int) -> np.ndarray:
    """[k+m, k]: the identity over the codec's parity rows."""
    return np.concatenate([np.eye(k, dtype=np.uint8),
                           reference.parity_matrix(codec, k, m)])


def solve(rows: np.ndarray) -> np.ndarray:
    """Inverse of a [k, k] matrix over GF(2^8): the reference's own
    Gauss-Jordan elimination on [rows | I]."""
    try:
        return reference._mat_inv(rows)
    except ValueError as exc:
        raise TooFewShards("the surviving rows are not independent") \
            from exc


def decode(files: dict[int, bytes], k: int, m: int, block_size: int,
           size: int, codec: str = "dense-gf8") -> Decoded:
    """files: shard index (1-based) -> the shard file's bytes, for the
    shards that are there. Raises TooFewShards where fewer than k of
    them pass their digests."""
    out = Decoded(b"")
    good: dict[int, np.ndarray] = {}
    for idx in sorted(files):
        digests, chunks = split_frames(files[idx], block_size, k, size)
        if np.array_equal(reference.highwayhash256(chunks), digests):
            good[idx] = chunks
            out.verified_bytes += chunks.size
        else:
            out.dropped.append(idx)
    if len(good) < k:
        raise TooFewShards(f"{len(good)} of {k + m} shards are there and "
                           f"pass their digests, {k} are needed")
    full = coding_matrix(codec, k, m)
    first = sorted(good)[:k]
    inverse = solve(full[[i - 1 for i in first]])
    have = np.stack([good[i] for i in first], axis=1)      # [blocks, k, S]
    data = reference.apply_matrix(inverse, have)           # [blocks, k, S]
    out.body = data.reshape(data.shape[0], -1)[:, :block_size].tobytes()
    lost = [i for i in range(1, k + m + 1) if i not in good]
    if lost:
        again = reference.apply_matrix(full[[i - 1 for i in lost]], data)
        out.rebuilt = {i: again[:, n] for n, i in enumerate(lost)}
    return out
