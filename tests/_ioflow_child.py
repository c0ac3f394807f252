"""Forced-multicore child for the byte-flow ledger acceptance proof
(tests/test_ioflow.py): a REAL S3 server with the worker pool armed
serves a signed PUT, a degraded GET (data shards destroyed) and a
single-shard heal, runs one scanner cycle, then emits the ledger
snapshots, the metrics exposition, and the new admin endpoint payloads
as JSON so the parent can reconcile byte totals against the payload
sizes it knows.

cpu_count is pinned to 4 for the worker-pool probe alone
(pipeline/workers.py disarms with reason `cores` under two, and sizes
the pool from the count): the worker processes and shm segments are
real; only the core count is faked (the ledger counts parent-side
syscall bytes, identical either way)."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.pop("MTPU_WORKER_POOL", None)
os.environ.pop("MTPU_IOFLOW", None)
os.cpu_count = lambda: 4  # read by workers.armed() and the governors

PAYLOAD_MIB = 12
K, M = 12, 4


def main(tmp: str) -> None:
    import http.client
    import urllib.parse

    import numpy as np

    from minio_tpu.api import S3Server
    from minio_tpu.api.sign import sign_v4_request
    from minio_tpu.background.heal import MRFHealer
    from minio_tpu.background.scanner import DataScanner
    from minio_tpu.bucket import BucketMetadataSys
    from minio_tpu.iam import IAMSys
    from minio_tpu.object.pools import ErasureServerPools
    from minio_tpu.object.sets import ErasureSets
    from minio_tpu.observability import ioflow
    from minio_tpu.observability.metrics import Metrics
    from minio_tpu.observability.metrics_v2 import MetricsCollector
    from minio_tpu.pipeline import workers
    from minio_tpu.storage.local import LocalStorage

    reg = Metrics()
    access, secret = "tpuadmin", "tpuadmin-secret-key"
    disks = [
        LocalStorage(os.path.join(tmp, f"d{i}"), endpoint=f"d{i}")
        for i in range(K + M)
    ]
    sets = ErasureSets(
        disks, K + M, default_parity=M,
        deployment_id="bb1b6f3a-4b87-4a0c-8164-4f4a51824ed9",
        pool_index=0,
    )
    sets.init_format()
    ol = ErasureServerPools([sets])
    scanner = DataScanner(ol, metrics=reg)
    healer = MRFHealer(ol, metrics=reg)
    srv = S3Server(ol, IAMSys(access, secret), BucketMetadataSys(ol),
                   metrics=reg).start()
    srv.admin.collector = MetricsCollector(
        reg, object_layer=ol, scanner=scanner, mrf=healer,
    )

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

    payload = np.random.default_rng(7).integers(
        0, 256, PAYLOAD_MIB << 20, np.uint8
    ).tobytes()

    # Phases are separated by OP CLASS, not by resets: the ledger is
    # cumulative (monotonic), and put/get-degraded/heal/scan don't
    # overlap, so one final snapshot serves every reconciliation AND
    # the admin/exposition scrape sees the full picture.
    ioflow.reset()
    st, _ = request("PUT", "/bkt/big", body=payload)
    assert st == 200, f"put_object: {st}"

    # A second object for the single-shard heal ratio (the degraded
    # GET below destroys TWO shards of /bkt/big).
    st, _ = request("PUT", "/bkt/healme", body=payload)
    assert st == 200, f"put healme: {st}"

    def kill_data_shards(obj: str, n: int) -> int:
        killed = 0
        for d in disks:
            if killed == n:
                break
            try:
                fi = d.read_version("bkt", obj)
            except Exception:  # noqa: BLE001 - no copy on this disk
                continue
            if fi.erasure.index - 1 < fi.erasure.data_blocks:
                os.remove(os.path.join(
                    tmp, d.endpoint(), "bkt", obj, fi.data_dir, "part.1"
                ))
                killed += 1
        return killed

    # --- degraded GET: 2 data shards gone, worker decode path ---
    assert kill_data_shards("big", 2) == 2
    st, got = request("GET", "/bkt/big")
    assert st == 200, f"degraded get: {st}"
    assert got == payload, "degraded GET not byte-identical"

    # --- single-shard heal: bytes read per byte healed == k ---
    assert kill_data_shards("healme", 1) == 1
    res = ol.heal_object("bkt", "healme")
    assert res["healed"], res

    # --- one scanner cycle: histograms + progress + scan ledger ---
    scanner.scan_cycle()

    final = ioflow.snapshot()
    totals = ioflow.op_totals(final)

    # Scrape AFTER everything so gauges reflect the final state.
    st, metrics_body = request("GET", "/minio/v2/metrics/cluster")
    assert st == 200, f"metrics: {st}"
    st, ioflow_body = request("GET", "/minio/admin/v3/ioflow")
    assert st == 200, f"admin ioflow: {st}"
    st, usage_body = request("GET", "/minio/admin/v3/usage",
                             query=[("histogram", "true")])
    assert st == 200, f"admin usage: {st}"

    out = {
        "arm_reason": workers.arm_reason(),
        "pool": pool.snapshot(),
        "payload_bytes": len(payload),
        "k": K, "m": M,
        "totals": totals,
        "logical": dict(final["logical"]),
        "scanner_progress": scanner.progress(),
        "mrf_stats": [es.mrf_stats() for es in sets.sets],
        "admin_ioflow": json.loads(ioflow_body),
        "admin_usage": json.loads(usage_body),
        "exposition": [
            line for line in metrics_body.decode().splitlines()
            if line.startswith((
                "mtpu_ioflow_bytes_total",
                "mtpu_ioflow_logical_bytes_total",
                "mtpu_heal_bytes_read_per_byte_healed",
                "mtpu_degraded_get_read_amplification",
                "mtpu_scan_bytes_per_object",
                "mtpu_hot_bucket_bytes_total",
                "mtpu_bucket_objects_size_distribution",
                "mtpu_bucket_objects_version_distribution",
                "mtpu_scanner_cycle_progress",
                "mtpu_scanner_objects_per_second",
                "mtpu_mrf_oldest_age_seconds",
                "mtpu_mrf_pending",
                "mtpu_erasure_set_online_disks",
                "mtpu_erasure_set_health",
            )) and not line.startswith("#")
        ],
    }
    srv.stop()
    import gc

    gc.collect()
    workers.shutdown()
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1])
