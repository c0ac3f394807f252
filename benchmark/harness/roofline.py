"""The least work the erasure code needs, from shapes alone, and the least
time a chip needs for it. Counted from the client's side (user bytes of
the operations it completed), so it reads the same whatever engine or
kernel does the work.

Reed-Solomon over GF(2^8) as a GF(2) bit-matrix product (ROADMAP S3): m
output shards from k input shards is an (8m x 8k) by (8k x S) product of
0/1 values per block, 2 * 8m * 8k int8 operations per column of k bytes.
12+4 encode: 2*32*96/12 = 512 int8 operations and (12+4)/12 = 1.33 bytes
of HBM traffic (each shard read once, each parity shard written once) per
input byte. The bitrot hash's own arithmetic is left out: a hash that is
fused with the code reads no byte twice, so it adds no traffic, and its
integer work is not the matrix unit's.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def columns(user_bytes: float, k: int, block_size: int) -> float:
    """Byte columns of k input shards that hold `user_bytes`: every block
    is cut into k shards of ceil(block/k) bytes, the last one padded."""
    return user_bytes / block_size * -(-block_size // k)


def coding_work(user_bytes: float, k: int, outputs: int,
                block_size: int) -> dict:
    """Work to make `outputs` shards (parity on a PUT, the lost shards on
    a heal or a degraded GET) from k shards that hold `user_bytes`."""
    cols = columns(user_bytes, k, block_size)
    return {"int8_ops": cols * 2 * (8 * outputs) * (8 * k),
            "hbm_bytes": cols * (k + outputs)}


def peaks_for(device_kind: str, path: str | None = None) -> dict:
    with open(path or os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"peaks.json has no device {device_kind!r}: an "
                       "unknown device is an error, not a default")
    return table[device_kind]


def least_seconds(work: dict, peaks: dict) -> tuple[float, str]:
    """-> (seconds, which bound it was)."""
    by_ops = work["int8_ops"] / peaks["int8_ops_per_s"]
    by_bytes = work["hbm_bytes"] / peaks["hbm_bytes_per_s"]
    return (by_ops, "int8") if by_ops >= by_bytes else (by_bytes, "hbm")
