"""Forced-multicore child for the hot-object tier's end-to-end ledger
proof (tests/test_readtier.py): a REAL S3 server with the worker pool
armed serves a 6 MiB hot key, and the byte-flow ledger shows that

- 8 concurrent signed GETs of the key with a COLD block cache cost
  exactly ONE decode's dir="read" shard bytes (single-flight), and
- a warm GET costs ZERO dir="read" bytes (decoded-block cache hit).

cpu_count is pinned to 4 for the worker-pool probe alone
(pipeline/workers.py disarms with reason `cores` under two, and sizes
the pool from the count): the worker processes, shm segments, and the
threaded server are real; only the core count is faked."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.pop("MTPU_WORKER_POOL", None)
os.environ["MTPU_READTIER"] = "on"
os.cpu_count = lambda: 4  # read by workers.armed() and the governors


def main(tmp: str) -> None:
    import http.client
    import threading
    import urllib.parse

    import numpy as np

    from minio_tpu.api import S3Server
    from minio_tpu.api.sign import sign_v4_request
    from minio_tpu.bucket import BucketMetadataSys
    from minio_tpu.iam import IAMSys
    from minio_tpu.object import readtier
    from minio_tpu.object.pools import ErasureServerPools
    from minio_tpu.object.sets import ErasureSets
    from minio_tpu.observability import ioflow
    from minio_tpu.pipeline import workers
    from minio_tpu.pipeline.admission import read_governor
    from minio_tpu.storage.local import LocalStorage

    access, secret = "tpuadmin", "tpuadmin-secret-key"
    disks = [
        LocalStorage(os.path.join(tmp, f"d{i}"), endpoint=f"d{i}")
        for i in range(4)
    ]
    sets = ErasureSets(
        disks, 4, deployment_id="c41f2a9e-66d0-4b53-9d2a-0f4f0a7e3b11",
        pool_index=0,
    )
    sets.init_format()
    ol = ErasureServerPools([sets])
    srv = S3Server(ol, IAMSys(access, secret),
                   BucketMetadataSys(ol)).start()

    pool = workers.armed()
    assert pool is not None, f"pool failed to arm: {workers.arm_reason()}"

    def request(method, path, body=b""):
        headers = sign_v4_request(
            secret, access, method, srv.endpoint, path, [], {}, body,
        )
        conn = http.client.HTTPConnection(srv.endpoint, timeout=180)
        conn.request(method, urllib.parse.quote(path), body=body,
                     headers=headers)
        resp = conn.getresponse()
        data = resp.read()
        conn.close()
        return resp.status, data

    def shard_reads() -> int:
        # dir="read" covers shard/payload bytes only; the per-GET
        # quorum metadata reads stay classified "rmeta".
        return sum(n for (_, _, dr), n in
                   ioflow.snapshot()["bytes"].items() if dr == "read")

    st, _ = request("PUT", "/bkt")
    assert st == 200, f"make_bucket: {st}"

    # 6 MiB: six 1 MiB erasure blocks; the PUT's tagged writes seed the
    # ledger's hot-bucket sketch, and the first GET's 6 MiB offer
    # crosses MTPU_READTIER_HOT_BYTES — so GET 1 is already a leader.
    payload = np.random.default_rng(11).integers(
        0, 256, 6 << 20, np.uint8
    ).tobytes()
    st, _ = request("PUT", "/bkt/hot", body=payload)
    assert st == 200, f"put_object: {st}"

    readtier.reset()  # fresh tier: knobs re-read, sketch cold

    r0 = shard_reads()
    st, got = request("GET", "/bkt/hot")
    assert st == 200 and got == payload, f"leader GET: {st}"
    single_decode_read = shard_reads() - r0
    snap = readtier.snapshot()
    assert snap["misses_total"] == 1, snap

    r1 = shard_reads()
    st, got = request("GET", "/bkt/hot")
    assert st == 200 and got == payload, f"warm GET: {st}"
    warm_read_delta = shard_reads() - r1
    assert readtier.snapshot()["hits_total"] == 1

    # Cold cache, hot sketch: the 8-way stampede must coalesce.
    readtier.invalidate("bkt", "hot")
    base = readtier.snapshot()
    gov0 = read_governor().snapshot()["coalesced_bypass_total"]
    r2 = shard_reads()
    barrier = threading.Barrier(8)
    statuses: list = [None] * 8
    bodies_ok: list = [False] * 8

    def client(i: int) -> None:
        barrier.wait(30)
        st_i, got_i = request("GET", "/bkt/hot")
        statuses[i] = st_i
        bodies_ok[i] = got_i == payload

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    k8_read_delta = shard_reads() - r2
    # A follower increments coalesced_total (then the governor) AFTER
    # writing its last block to the socket — the client can finish its
    # Content-Length read a beat before the server thread runs those
    # two lines. Bytes are settled (delta above); poll the counters
    # until every GET is accounted for before snapshotting.
    import time

    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        tier = readtier.snapshot()
        done = (tier["misses_total"] - base["misses_total"]) \
            + (tier["hits_total"] - base["hits_total"]) \
            + (tier["coalesced_total"] - base["coalesced_total"]) \
            + (tier["follower_fallbacks_total"]
               - base["follower_fallbacks_total"])
        gov_delta = (read_governor().snapshot()["coalesced_bypass_total"]
                     - gov0)
        served_delta = (tier["hits_total"] - base["hits_total"]) \
            + (tier["coalesced_total"] - base["coalesced_total"])
        if done >= 8 and gov_delta >= served_delta:
            break
        time.sleep(0.02)
    tier = readtier.snapshot()

    out = {
        "arm_reason": workers.arm_reason(),
        "single_decode_read": single_decode_read,
        "warm_read_delta": warm_read_delta,
        "k8_read_delta": k8_read_delta,
        "k8_statuses": statuses,
        "bodies_identical": all(bodies_ok),
        "k8_leaders": tier["misses_total"] - base["misses_total"],
        "k8_served": (tier["hits_total"] - base["hits_total"])
        + (tier["coalesced_total"] - base["coalesced_total"]),
        "governor_coalesced_delta":
            read_governor().snapshot()["coalesced_bypass_total"] - gov0,
        "tier": tier,
        "served": {k: v for k, v in
                   ioflow.snapshot()["served"].items()},
    }
    srv.stop()
    # Drop lingering numpy views over shm segments (response buffers
    # freed by GC timing) so the unlink sweep is quiet.
    import gc

    gc.collect()
    workers.shutdown()
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1])
