"""Forced-multicore child for the end-to-end span-tree proof
(tests/test_spans.py): a REAL S3 server with the worker pool armed
serves a signed PUT and a degraded GET (both data shards destroyed)
under MTPU_TRACE_SLOW_MS=0, then emits the captured span trees, the
admin slow-requests payload, and the metrics exposition as JSON.

cpu_count is pinned to 4 for the worker-pool probe alone
(pipeline/workers.py disarms with reason `cores` under two, and sizes
the pool from the count): the worker processes and shm segments are
real; only the core count is faked (byte paths are identical either
way)."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["MTPU_TRACE_SLOW_MS"] = "0"
os.environ.pop("MTPU_WORKER_POOL", None)
os.cpu_count = lambda: 4  # read by workers.armed() and the governors


def main(tmp: str) -> None:
    import http.client
    import urllib.parse

    import numpy as np

    from minio_tpu.api import S3Server
    from minio_tpu.api.sign import sign_v4_request
    from minio_tpu.bucket import BucketMetadataSys
    from minio_tpu.iam import IAMSys
    from minio_tpu.object.pools import ErasureServerPools
    from minio_tpu.object.sets import ErasureSets
    from minio_tpu.observability import pubsub as _pubsub
    from minio_tpu.observability import spans
    from minio_tpu.observability.metrics import Metrics
    from minio_tpu.observability.trace import TraceHub
    from minio_tpu.pipeline import admission as _admission
    from minio_tpu.pipeline import workers
    from minio_tpu.storage.local import LocalStorage

    reg = Metrics()
    hub = TraceHub()
    spans.set_metrics(reg)
    spans.set_trace_hub(hub)
    _admission.set_metrics(reg)
    _pubsub.set_metrics(reg)
    workers.set_metrics(reg)

    access, secret = "tpuadmin", "tpuadmin-secret-key"
    disks = [
        LocalStorage(os.path.join(tmp, f"d{i}"), endpoint=f"d{i}")
        for i in range(4)
    ]
    sets = ErasureSets(
        disks, 4, deployment_id="bb1b6f3a-4b87-4a0c-8164-4f4a51824ed9",
        pool_index=0,
    )
    sets.init_format()
    ol = ErasureServerPools([sets])
    srv = S3Server(ol, IAMSys(access, secret), BucketMetadataSys(ol),
                   metrics=reg, trace=hub).start()

    pool = workers.armed()
    assert pool is not None, f"pool failed to arm: {workers.arm_reason()}"

    def request(method, path, body=b"", query=None):
        headers = sign_v4_request(
            secret, access, method, srv.endpoint, path, query or [],
            {}, body,
        )
        conn = http.client.HTTPConnection(srv.endpoint, timeout=180)
        qs = urllib.parse.urlencode(query or [])
        conn.request(method, urllib.parse.quote(path)
                     + (f"?{qs}" if qs else ""),
                     body=body, headers=headers)
        resp = conn.getresponse()
        data = resp.read()
        conn.close()
        return resp.status, data

    st, _ = request("PUT", "/bkt")
    assert st == 200, f"make_bucket: {st}"

    # 12 MiB: two pipeline batches at batch_blocks=8 (the worker
    # driver's staged path), 12 GET geoms (past the profitability gate).
    payload = np.random.default_rng(7).integers(
        0, 256, 12 << 20, np.uint8
    ).tobytes()
    st, _ = request("PUT", "/bkt/big", body=payload)
    assert st == 200, f"put_object: {st}"

    # Destroy the k DATA shard part files (erasure.index is the disk's
    # 1-based shard position; data shards sort first), forcing the GET
    # to reconstruct every data block from parity — the worker decode
    # path, not the healthy stream-through.
    k = None
    killed = 0
    for d in disks:
        try:
            fi = d.read_version("bkt", "big")
        except Exception:  # noqa: BLE001 - this disk holds no copy
            continue
        k = fi.erasure.data_blocks
        if fi.erasure.index - 1 < fi.erasure.data_blocks:
            os.remove(os.path.join(
                tmp, d.endpoint(), "bkt", "big", fi.data_dir, "part.1"
            ))
            killed += 1
    assert k is not None and killed == k, (killed, k)

    st, got = request("GET", "/bkt/big")
    assert st == 200, f"degraded get: {st}"
    assert got == payload, "degraded GET not byte-identical"

    st, admin_body = request("GET", "/minio/admin/v3/slow-requests")
    assert st == 200, f"admin slow-requests: {st}"

    trees = spans.slow_requests()
    out = {
        "arm_reason": workers.arm_reason(),
        "pool": pool.snapshot(),
        "trees": [
            {"api": t["api"], "duration_ms": t["duration_ms"],
             "spans": t["spans"]}
            for t in trees
        ],
        "admin": json.loads(admin_body),
        "exposition": [
            line for line in reg.render_prometheus().splitlines()
            if line.startswith("mtpu_span_seconds_count")
        ],
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
