"""Headline benchmark: the north-star PutObject erasure-encode path
(12+4 @ 1 MiB blocks) measured HOST-FED — data originates in host memory
and shards land in streaming bitrot writers on real storage, matching the
reference harness (/root/reference/cmd/erasure-encode_test.go:210-253,
cmd/benchmark-utils_test.go:32) — plus all five BASELINE.json configs.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "GB/s", "vs_baseline": N, ...}

Engine policy (erasure/registry.select_engine): 'auto' ranks the host
engines by a measured probe and the device/mesh engines by declared
feed bounds that are unmeasured on this attachment (ROADMAP S2); the
device pipeline (async batched MXU encode with fused HighwayHash) is
measured separately below and is one env var away
(MTPU_ENCODE_ENGINE=device).

Exits non-zero without a TPU: a timing from a CPU is never written
under the name of a device metric. Its replacement, a benchmark of the
served path with a ledger, is ROADMAP S1; chip_smoke.py is the proof
that the served path runs on the chip.

`vs_baseline` compares the headline against the ~6 GB/s AVX2
klauspost/reedsolomon 12+4 estimate (BASELINE.md; the reference publishes
no absolute numbers and no Go toolchain exists here), so
"baseline_estimated": true marks it.
"""

from __future__ import annotations

import io
import json
import os
import statistics
import subprocess
import sys
import tempfile
import time

import numpy as np

AVX2_BASELINE_GBPS = 6.0

MIB = 1 << 20


def _bench_dir() -> str:
    base = "/dev/shm" if os.access("/dev/shm", os.W_OK) else None
    return tempfile.mkdtemp(prefix="mtpu-bench-", dir=base)


def _cleanup(path: str):
    """Drop a finished config's data IMMEDIATELY: the bench root lives
    in tmpfs, and letting configs accumulate (~0.5 GB by config 5)
    starves small-RAM hosts into swap, corrupting later numbers."""
    import shutil

    shutil.rmtree(path, ignore_errors=True)


class _Null:
    def write(self, b):
        return len(b)


def _mk_set(root: str, n_disks: int, parity: int):
    from minio_tpu.object.erasure_objects import ErasureObjects
    from minio_tpu.storage.local import LocalStorage

    disks = [
        LocalStorage(os.path.join(root, f"d{i}"), endpoint=f"d{i}")
        for i in range(n_disks)
    ]
    for d in disks:
        d.make_vol(".minio.sys")
    es = ErasureObjects(disks, default_parity=parity)
    es.make_bucket("bench")
    return es, disks


def _hostfed_encode_best(root: str, prefix: str, payload: bytes, reps: int,
                         mk_src, finish=None,
                         telemetry: str = "put") -> float:
    """Best-of-reps GB/s for a host-fed 12+4 encode_stream into
    streaming bitrot writers on real files — the shared scaffolding
    behind the headline number and the pipelined-PUT stage measurement
    (16 disks, per-rep sinks, timing, per-rep shard cleanup)."""
    from minio_tpu.erasure.bitrot import BitrotAlgorithm, StreamingBitrotWriter
    from minio_tpu.erasure.codec import Erasure
    from minio_tpu.erasure.streaming import encode_stream
    from minio_tpu.storage.local import LocalStorage

    erasure = Erasure(12, 4, MIB)
    disks = [
        LocalStorage(os.path.join(root, f"{prefix}{i}"),
                     endpoint=f"{prefix}{i}")
        for i in range(16)
    ]
    for d in disks:
        d.make_vol("bench")
    best = 0.0
    for rep in range(reps):
        sinks = [
            d.create_file_writer("bench", f"shard-{rep}-{i}")
            for i, d in enumerate(disks)
        ]
        writers = [
            StreamingBitrotWriter(s, BitrotAlgorithm.HIGHWAYHASH256S)
            for s in sinks
        ]
        src = mk_src()
        t0 = time.perf_counter()
        encode_stream(erasure, src, writers, 13, telemetry=telemetry)
        if finish is not None:
            finish(src)
        dt = time.perf_counter() - t0
        for s in sinks:
            s.close()
        best = max(best, len(payload) / dt / 1e9)
        for i, d in enumerate(disks):
            try:
                d.delete("bench", f"shard-{rep}-{i}")
            except Exception:  # noqa: BLE001
                pass
    for i in range(16):
        _cleanup(os.path.join(root, f"{prefix}{i}"))
    return best


def bench_headline_encode(root: str, total_mib: int = 64, reps: int = 3):
    """Host-fed 12+4 streaming encode into bitrot writers on real files —
    the reference's BenchmarkErasureEncode conditions."""
    payload = np.random.default_rng(0).integers(
        0, 256, total_mib * MIB, np.uint8
    ).tobytes()
    return _hostfed_encode_best(root, "enc", payload, reps,
                                lambda: io.BytesIO(payload))


def bench_encode_only(total_mib: int = 64, reps: int = 3) -> float:
    """Pure EncodeData 12+4 (klauspost-benchmark-comparable): host memory
    in, parity in host memory out, no hashing, no IO."""
    from minio_tpu.erasure.codec import Erasure

    erasure = Erasure(12, 4, MIB)
    shard = erasure.shard_size()
    blocks = np.random.default_rng(1).integers(
        0, 256, size=(total_mib, 12, shard), dtype=np.uint8
    )
    best = 0.0
    for _ in range(reps):
        t0 = time.perf_counter()
        erasure.encode_batch(blocks)
        dt = time.perf_counter() - t0
        best = max(best, blocks.nbytes / dt / 1e9)
    return best


def bench_config1_put_p50(root: str, n: int = 30):
    """Config 1: single-node 2+2, 1 MiB PutObject p50 latency."""
    from minio_tpu.object.types import ObjectOptions

    es, _ = _mk_set(os.path.join(root, "c1"), 4, 2)
    payload = os.urandom(MIB)
    lat = []
    for i in range(n):
        t0 = time.perf_counter()
        es.put_object("bench", f"o{i}", io.BytesIO(payload), MIB,
                      ObjectOptions())
        lat.append((time.perf_counter() - t0) * 1000)
    return statistics.median(lat)


def bench_config2_roundtrip(root: str, reps: int = 5):
    """Config 2: 12+4, 10 MiB objects, encode+decode round trip GB/s."""
    es, _ = _mk_set(os.path.join(root, "c2"), 16, 4)
    size = 10 * MIB
    payload = os.urandom(size)
    t0 = time.perf_counter()
    moved = 0
    for i in range(reps):
        es.put_object("bench", f"rt{i}", io.BytesIO(payload), size)
        es.get_object("bench", f"rt{i}", _Null())
        moved += 2 * size
    return moved / (time.perf_counter() - t0) / 1e9


def bench_config3_heal(root: str, reps: int = 3):
    """Config 3: 12+4 with 2 drives' shards lost, low-level heal GB/s
    (bytes of object data repaired per second). Best of `reps`
    kill+heal cycles — a single-shot heal was the noisiest number in
    the file (one scheduler hiccup = a 2x swing)."""
    es, disks = _mk_set(os.path.join(root, "c3"), 16, 4)
    size = 10 * MIB
    es.put_object("bench", "heal-me", io.BytesIO(os.urandom(size)), size)
    best = 0.0
    for _ in range(reps):
        killed = 0
        for d in disks:
            if killed == 2:
                break
            try:
                d.delete("bench", "heal-me", recursive=True)
                killed += 1
            except Exception:  # noqa: BLE001
                continue
        t0 = time.perf_counter()
        res = es.heal_object("bench", "heal-me")
        dt = time.perf_counter() - t0
        assert res["healed"], res
        best = max(best, size / dt / 1e9)
    return best


def bench_config4_bitrot_get(root: str, reps: int = 5):
    """Config 4: 8+4 set, bitrot-verified GET GB/s (streaming HighwayHash
    verify on every shard read, fused into decode)."""
    es, _ = _mk_set(os.path.join(root, "c4"), 12, 4)
    size = 10 * MIB
    es.put_object("bench", "get-me", io.BytesIO(os.urandom(size)), size)
    t0 = time.perf_counter()
    for _ in range(reps):
        es.get_object("bench", "get-me", _Null())
    return reps * size / (time.perf_counter() - t0) / 1e9


class _ZeroCopyReader:
    """Stream over a shared payload without the per-PUT BytesIO copy —
    the 4 MiB memcpy per put stole the GIL from the admitted encoder and
    polluted the aggregate number with harness cost. read() hands out
    MEMORYVIEW slices of the shared payload (the c5/c6 harness itself
    must stay off the copy budget — a bytes() per call was one hidden
    pass over every benchmarked byte); readinto() is the strip
    pipeline's production path."""

    def __init__(self, payload: bytes):
        self._mv = memoryview(payload)
        self._pos = 0

    def read(self, n: int = -1) -> memoryview:
        left = len(self._mv) - self._pos
        if n is None or n < 0 or n > left:
            n = left
        out = self._mv[self._pos: self._pos + n]
        self._pos += n
        return out

    def readinto(self, b) -> int:
        view = memoryview(b)
        n = min(len(view), len(self._mv) - self._pos)
        view[:n] = self._mv[self._pos: self._pos + n]
        self._pos += n
        return n


from contextlib import contextmanager


@contextmanager
def _worker_pool_env(on: str = "1"):
    """Arm (or pin off) the GIL-free encode worker pool for one bench
    section; MTPU_WORKER_POOL is read per stream, so the env wrap is
    exact. The pool itself is process-wide and stays warm across
    sections once started."""
    old = os.environ.get("MTPU_WORKER_POOL")
    os.environ["MTPU_WORKER_POOL"] = on
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("MTPU_WORKER_POOL", None)
        else:
            os.environ["MTPU_WORKER_POOL"] = old


@contextmanager
def _admission_env(max_queue: int):
    """Size the admission queue for a closed-loop many-client section
    (the default 8x-slots queue is tuned for open-loop traffic; a
    closed loop with N waiting clients needs N queue slots or the
    harness measures its own rejections), restoring the operator
    config afterwards."""
    from minio_tpu.pipeline import admission

    old = os.environ.get("MTPU_ADMISSION_MAX_QUEUE")
    os.environ["MTPU_ADMISSION_MAX_QUEUE"] = str(max_queue)
    # BOTH governors: the closed loop's GETs ride the read governor
    # (ISSUE 11), which would otherwise keep its default queue and
    # hand the harness self-inflicted 503 retries at high N.
    admission.reconfigure()
    admission.reconfigure_read()
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("MTPU_ADMISSION_MAX_QUEUE", None)
        else:
            os.environ["MTPU_ADMISSION_MAX_QUEUE"] = old
        admission.reconfigure()
        admission.reconfigure_read()


def _mk_pool_layout(base: str):
    from minio_tpu.object.pools import ErasureServerPools
    from minio_tpu.object.sets import ErasureSets
    from minio_tpu.storage.local import LocalStorage

    disks = [
        LocalStorage(os.path.join(base, f"d{i}"), endpoint=f"p{i}")
        for i in range(16)
    ]
    sets = ErasureSets(
        disks, 4,
        deployment_id="benchben-chbe-nchb-ench-benchbenchbe", pool_index=0,
    )
    sets.init_format()
    ol = ErasureServerPools([sets])
    ol.make_bucket("bench")
    return ol


def bench_config5_pool_put(root: str, n_objects: int = 24):
    """Config 5: multi-set pool, batched multi-object PUT aggregate
    GB/s — 8 concurrent clients through the admission governor, with
    the worker pool armed so GF encode + strided hashing run off the
    main interpreter."""
    from concurrent.futures import ThreadPoolExecutor

    from minio_tpu.pipeline.admission import client_context

    ol = _mk_pool_layout(os.path.join(root, "c5"))
    size = 4 * MIB
    payload = os.urandom(size)

    def put(i):
        with client_context(f"c5-client-{i % 8}"):
            ol.put_object("bench", f"batch/o{i}", _ZeroCopyReader(payload),
                          size)

    with _worker_pool_env("1"):
        with ThreadPoolExecutor(max_workers=8) as pool:
            t0 = time.perf_counter()
            list(pool.map(put, range(n_objects)))
            dt = time.perf_counter() - t0
    return n_objects * size / dt / 1e9


def _c6_run(base: str, n_clients: int, ops_per_client: int,
            size: int) -> tuple[float, float, float, int]:
    """One closed-loop round: N concurrent clients, each PUT+GET
    `ops_per_client` objects of `size` bytes. Returns (aggregate GB/s
    over put+get bytes, p50 ms, p99 ms, admission retries). A 503 from
    the governor (queue full / deadline) is retried like a real S3
    client would — counted, never hidden."""
    import threading
    from concurrent.futures import ThreadPoolExecutor

    from minio_tpu.pipeline.admission import client_context
    from minio_tpu.utils.errors import ErrOperationTimedOut

    ol = _mk_pool_layout(base)
    payload = os.urandom(size)
    lat: list = []
    lat_mu = threading.Lock()
    retries = [0]

    def one_op(fn):
        t0 = time.perf_counter()
        while True:
            try:
                fn()
                break
            except ErrOperationTimedOut:
                with lat_mu:
                    retries[0] += 1
                time.sleep(0.005)
        return time.perf_counter() - t0

    def client(ci):
        local = []
        with client_context(f"c6-client-{ci}"):
            for k in range(ops_per_client):
                name = f"c{ci}/o{k}"
                local.append(one_op(lambda: ol.put_object(
                    "bench", name, _ZeroCopyReader(payload), size)))
                local.append(one_op(lambda: ol.get_object(
                    "bench", name, _Null())))
        with lat_mu:
            lat.extend(local)

    with ThreadPoolExecutor(max_workers=n_clients) as pool:
        t0 = time.perf_counter()
        list(pool.map(client, range(n_clients)))
        dt = time.perf_counter() - t0
    moved = n_clients * ops_per_client * size * 2
    lat_ms = sorted(x * 1e3 for x in lat)
    p50 = lat_ms[len(lat_ms) // 2]
    p99 = lat_ms[min(len(lat_ms) - 1, int(0.99 * len(lat_ms)))]
    return moved / dt / 1e9, p50, p99, retries[0]


def bench_config6_closed_loop(root: str, ns=(8, 32, 64),
                              ops_per_client: int = 3,
                              size: int = 2 * MIB, runs: int = 3) -> dict:
    """Config 6: closed-loop many-client fan-in — N∈{8,32,64}
    concurrent PUT+GET clients, aggregate GB/s plus per-op p50/p99,
    under the min-of-N memcpy-normalized repeatability protocol. The
    worker pool is armed and the admission queue sized for the closed
    loop; rejections retried by the harness are reported per entry.
    Skips cleanly on 1-core hosts, where fan-in concurrency cannot
    exist and the numbers would only mislead."""
    if (os.cpu_count() or 1) < 2:
        return {"skipped": "single-core host: no fan-in concurrency"}
    from minio_tpu.pipeline import admission
    from minio_tpu.pipeline import workers as _workers

    out: dict = {}
    with _worker_pool_env("1"), _admission_env(max(ns) * 4):
        for n in ns:
            stats: list = []

            def one_run(i, n=n):
                sub = os.path.join(root, f"c6-{n}-r{i}")
                try:
                    g, p50, p99, retr = _c6_run(sub, n, ops_per_client,
                                                size)
                    stats.append((g, p50, p99, retr))
                    return g
                finally:
                    _cleanup(sub)

            entry = _config_protocol(one_run, "max", runs)
            best = max(stats, key=lambda s: s[0])
            entry["p50_ms"] = round(best[1], 2)
            entry["p99_ms"] = round(best[2], 2)
            entry["admission_retries"] = best[3]
            out[f"n{n}"] = entry
        pool = _workers.get_pool()
        out["worker_pool"] = pool.snapshot() if pool is not None else None
        out["worker_armed"] = _workers.arm_reason()
        out["admission"] = admission.governor().snapshot()
        out["admission_read"] = admission.read_governor().snapshot()
    # Read-side A/B (ISSUE 11): the same closed PUT+GET loop at N=8
    # with the pool OFF — the on/off delta is the direct measure of
    # whether the read side still regresses when GET clients join the
    # PUT load without the worker plane.
    with _worker_pool_env("0"), _admission_env(max(ns) * 4):
        sub = os.path.join(root, "c6-ab-off")
        try:
            g, p50, p99, retr = _c6_run(sub, 8, ops_per_client, size)
        finally:
            _cleanup(sub)
        out["n8_pool_off"] = {
            "value": round(g, 4), "p50_ms": round(p50, 2),
            "p99_ms": round(p99, 2), "admission_retries": retr,
        }
    return out


def bench_config7_loadgen(root: str, clients: int = 64,
                          ops_per_client: int = 4) -> dict:
    """Config 7: the closed-loop load-generation harness at gate scale
    (ISSUE 17) — >= 64 zipfian clients over the signed HTTP plane with
    every fault plane armed (bounded hang included), reporting the soak
    gate's own numbers: memcpy-normalized aggregate throughput, per-op-
    class client p50/p99 off the latency board, span-plane p99
    attribution, the hang-fault fire count the detach proof ran
    against, plus the heal-storm paced-drain figures (degraded-vs-
    baseline p99 ratio, final ledger heal ratio, pacer counters).
    Skips cleanly on 1-core hosts: 64 closed-loop issuers on one core
    measure the scheduler, not the store."""
    if (os.cpu_count() or 1) < 2:
        return {"skipped": "single-core host: 64 closed-loop clients "
                           "would measure the scheduler, not the store"}
    from minio_tpu.faults.scenarios import (
        ScenarioSpec,
        host_memcpy_gbps,
        run_heal_storm,
        run_scenario,
    )

    spec = ScenarioSpec(
        seed=1337, clients=clients, ops_per_client=ops_per_client,
        disks=8, parity=4,
        payload_sizes=(16 << 10, 64 << 10, 256 << 10),
        fault_drives=2, worker_kills=1, peer_blackouts=1,
        remote_disks=2, blip_s=1.0, admission_slots=2, lock_check=False,
    )
    res = run_scenario(spec, os.path.join(root, "loadgen"))
    art = res.to_dict()
    memcpy = host_memcpy_gbps()
    hang_fired = sum(s["fired"] for st in art["fault_status"]
                     for s in st["specs"] if s["kind"] == "hang")
    out: dict = {
        "passed": res.passed,
        "clients": spec.clients,
        "ops_per_client": spec.ops_per_client,
        "bytes_moved": res.bytes_moved,
        "wall_s": round(res.wall_s, 3),
        "aggregate_gbps": round(res.throughput_gbps, 5),
        "value_per_memcpy": round(res.throughput_gbps / memcpy, 7),
        "host_memcpy_gbps": round(memcpy, 2),
        "hang_faults_fired": hang_fired,
        "latency": art["latency"],
        "span_p99": art["span_p99"],
        "violations": {k: v for k, v in res.violations.items() if v},
    }
    # Heal storm under zipfian foreground: the adaptive pacer's
    # headline numbers, recorded alongside the load-gen run they bound.
    storm_spec = ScenarioSpec(
        seed=1337, clients=8, ops_per_client=4, disks=8, parity=4,
        hot_keys=0, fault_drives=0, worker_kills=0,
        payload_sizes=(64 << 10,),
    )
    storm = run_heal_storm(storm_spec, os.path.join(root, "storm"),
                           storm_objects=24, fg_clients=6, fg_ops=25,
                           payload=64 << 10)
    out["heal_storm"] = {
        "passed": storm["passed"],
        "p99_ratio": storm["p99_ratio"],
        "p99_mult": storm["p99_mult"],
        "heal_ratio_final": storm["heal_ratio"]["final"],
        "mrf_left": storm["mrf_left"],
        "pacer": storm["pacer"],
    }
    return out


def _c8_coalescing_proof(base: str, k_clients: int = 8,
                         size: int = 4 * MIB) -> dict:
    """The tier's LOGICAL coalescing counters at K=8 — core-count-
    independent (counts, not wall time), so this proof runs even where
    the A/B must skip: K concurrent GETs of a cold-cache sketch-hot key
    must register exactly one decode leader, with the rest served off
    the shared flight / block cache and the byte-flow ledger's
    dir="read" (shard payload) bytes showing ONE decode's reads."""
    import threading

    from minio_tpu.object import readtier
    from minio_tpu.observability import ioflow

    readtier.reset()
    ioflow.reset()
    ol = _mk_pool_layout(base)
    payload = np.random.default_rng(0xC8).integers(
        0, 256, size, np.uint8).tobytes()
    with ioflow.tag("put", bucket="bench"):
        ol.put_object("bench", "hot/one", _ZeroCopyReader(payload), size)

    def get():
        with ioflow.tag("get", bucket="bench"):
            ol.get_object("bench", "hot/one", _Null())

    def shard_reads():
        return sum(n for (_, _, dr), n in
                   ioflow.snapshot()["bytes"].items() if dr == "read")

    get()  # crosses the per-key hot threshold; leads + warms the cache
    readtier.invalidate("bench", "hot/one")  # cache cold, sketch hot
    r0 = shard_reads()
    get()                                    # ONE decode, re-warms
    one_decode = shard_reads() - r0
    readtier.invalidate("bench", "hot/one")
    before = readtier.snapshot()
    r1 = shard_reads()
    barrier = threading.Barrier(k_clients)

    def client():
        barrier.wait(30)
        get()

    threads = [threading.Thread(target=client) for _ in range(k_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = readtier.snapshot()
    leaders = snap["misses_total"] - before["misses_total"]
    served = (snap["hits_total"] - before["hits_total"]) \
        + (snap["coalesced_total"] - before["coalesced_total"])
    return {
        "k": k_clients,
        "leaders": leaders,
        "served_without_decode": served,
        "coalescing_factor": round(k_clients / max(1, leaders), 2),
        "one_decode_read_bytes": one_decode,
        "k_concurrent_read_bytes": shard_reads() - r1,
    }


def _c8_run(base: str, n_clients: int, ops_per_client: int, n_keys: int,
            size: int, zipf_s: float,
            tier_on: bool) -> tuple[float, float, float, dict | None]:
    """One zipfian closed-loop GET round over a pre-seeded hot set at
    steady state (two untimed warm passes, so both arms measure serving,
    not first-touch): aggregate GB/s, p50/p99 ms, tier snapshot."""
    import random
    import threading
    from concurrent.futures import ThreadPoolExecutor

    from minio_tpu.faults.scenarios import _zipf_rank
    from minio_tpu.object import readtier
    from minio_tpu.observability import ioflow
    from minio_tpu.pipeline.admission import client_context

    os.environ["MTPU_READTIER"] = "on" if tier_on else "off"
    readtier.reset()
    ioflow.reset()
    ol = _mk_pool_layout(base)
    payloads = []
    for k in range(n_keys):
        p = np.random.default_rng(1000 + k).integers(
            0, 256, size, np.uint8).tobytes()
        payloads.append(p)
        with ioflow.tag("put", bucket="bench"):
            ol.put_object("bench", f"hot/o{k:02d}", _ZeroCopyReader(p),
                          size)

    def get(k, writer):
        with ioflow.tag("get", bucket="bench"):
            ol.get_object("bench", f"hot/o{k:02d}", writer)

    for _ in range(2):          # warm: the 2nd pass crosses the per-key
        for k in range(n_keys):  # threshold and fills the block cache
            get(k, _Null())
    lat: list = []
    lat_mu = threading.Lock()

    def client(ci):
        rng = random.Random(0xC8 * 2654435761 + ci)
        local = []
        with client_context(f"c8-client-{ci}"):
            for _ in range(ops_per_client):
                k = _zipf_rank(rng, n_keys, zipf_s)
                t0 = time.perf_counter()
                get(k, _Null())
                local.append(time.perf_counter() - t0)
        with lat_mu:
            lat.extend(local)

    with ThreadPoolExecutor(max_workers=n_clients) as pool:
        t0 = time.perf_counter()
        list(pool.map(client, range(n_clients)))
        dt = time.perf_counter() - t0
    # Byte-correctness spot check through the same (possibly cached)
    # read path the timed loop used.
    for k in (0, n_keys - 1):
        buf = io.BytesIO()
        get(k, buf)
        assert buf.getvalue() == payloads[k], f"c8: key o{k:02d} diverged"
    moved = n_clients * ops_per_client * size
    lat_ms = sorted(x * 1e3 for x in lat)
    p50 = lat_ms[len(lat_ms) // 2]
    p99 = lat_ms[min(len(lat_ms) - 1, int(0.99 * len(lat_ms)))]
    return moved / dt / 1e9, p50, p99, readtier.snapshot()


def bench_config8_hot_get(root: str, n_clients: int = 16,
                          ops_per_client: int = 12, n_keys: int = 16,
                          size: int = MIB, zipf_s: float = 1.1,
                          runs: int = 3) -> dict:
    """Config 8: hot-object serving tier A/B (ISSUE 19) — N zipfian
    closed-loop GET clients over a small hot set, tier on vs off under
    the min-of-N memcpy-normalized protocol, reporting aggregate GB/s,
    per-op p50/p99, the tier's cache hit rate and coalescing factor.
    The A/B skips honestly on 1-core hosts (N closed-loop threads there
    measure the scheduler); the coalescing_proof block is logical
    counters and records on every host."""
    from minio_tpu.object import readtier
    from minio_tpu.observability import ioflow

    saved = os.environ.get("MTPU_READTIER")
    out: dict = {
        "clients": n_clients, "ops_per_client": ops_per_client,
        "keys": n_keys, "size_bytes": size, "zipf_s": zipf_s,
    }
    try:
        os.environ["MTPU_READTIER"] = "on"
        proof_root = os.path.join(root, "c8-proof")
        try:
            out["coalescing_proof"] = _c8_coalescing_proof(proof_root)
        finally:
            _cleanup(proof_root)
        if (os.cpu_count() or 1) < 2:
            out["ab"] = {
                "skipped": "single-core host: closed-loop zipfian GET "
                           "clients measure the scheduler, not the "
                           "tier; coalescing_proof above is "
                           "core-count-independent"
            }
            return out
        with _worker_pool_env("1"), _admission_env(n_clients * 4):
            for arm, tier_on in (("tier_on", True), ("tier_off", False)):
                stats: list = []

                def one_run(i, arm=arm, tier_on=tier_on, stats=stats):
                    sub = os.path.join(root, f"c8-{arm}-r{i}")
                    try:
                        g, p50, p99, snap = _c8_run(
                            sub, n_clients, ops_per_client, n_keys,
                            size, zipf_s, tier_on,
                        )
                        stats.append((g, p50, p99, snap))
                        return g
                    finally:
                        _cleanup(sub)

                entry = _config_protocol(one_run, "max", runs)
                best = max(stats, key=lambda s: s[0])
                entry["p50_ms"] = round(best[1], 2)
                entry["p99_ms"] = round(best[2], 2)
                if tier_on and best[3] is not None:
                    snap = best[3]
                    tier_gets = (snap["hits_total"] + snap["misses_total"]
                                 + snap["coalesced_total"])
                    entry["cache_hit_rate"] = round(
                        snap["hits_total"] / max(1, tier_gets), 4)
                    entry["coalescing_factor"] = round(
                        tier_gets / max(1, snap["misses_total"]), 2)
                    entry["tier"] = snap
                out[arm] = entry
        out["speedup_on_vs_off"] = round(
            out["tier_on"]["value"] / out["tier_off"]["value"], 3)
        return out
    finally:
        if saved is None:
            os.environ.pop("MTPU_READTIER", None)
        else:
            os.environ["MTPU_READTIER"] = saved
        readtier.reset()
        ioflow.reset()


def bench_multipart_parallel(root: str, total_mib: int = 48) -> dict:
    """Single-object ingest two ways: serial PUT (one MD5 stream — the
    measured ~0.66 GB/s wall) vs the parallel multipart driver
    (per-part MD5s composing into the S3 etag-of-parts). The speedup
    column IS the sanctioned route around the wall; byte equality is
    verified in-run."""
    if (os.cpu_count() or 1) < 2:
        return {"skipped": "single-core host: parts cannot overlap"}
    es, _ = _mk_set(os.path.join(root, "mp"), 16, 4)
    payload = np.random.default_rng(23).integers(
        0, 256, total_mib * MIB, np.uint8
    ).tobytes()
    n = len(payload)
    part_size = 8 * MIB
    out: dict = {"parts": -(-n // part_size)}
    with _worker_pool_env("1"):
        best = 0.0
        for _ in range(2):
            t0 = time.perf_counter()
            es.put_object("bench", "big-serial", _ZeroCopyReader(payload),
                          n)
            best = max(best, n / (time.perf_counter() - t0) / 1e9)
        out["serial_put_gbps"] = round(best, 3)
        best = 0.0
        for _ in range(2):
            t0 = time.perf_counter()
            oi = es.put_object_multipart("bench", "big-mp", payload, n,
                                         part_size=part_size)
            best = max(best, n / (time.perf_counter() - t0) / 1e9)
        out["parallel_put_gbps"] = round(best, 3)
        out["etag"] = oi.etag
        sink = io.BytesIO()
        es.get_object("bench", "big-mp", sink)
        assert sink.getvalue() == payload, "multipart bytes differ"
    if out["serial_put_gbps"] > 0:
        out["speedup"] = round(
            out["parallel_put_gbps"] / out["serial_put_gbps"], 2
        )
    return out


def bench_put_stages(root: str, total_mib: int = 32) -> dict:
    """Per-stage breakdown of ONE PutObject stream (12+4 @ 1 MiB blocks)
    on this host, in GB/s of INPUT bytes — the decomposition that locates
    where e2e throughput goes. Stages mirror the PUT pipeline order:
    source read -> md5 (ETag) -> GF encode -> bitrot frame -> shard write
    -> xl.meta commit. Single-threaded, like one admitted PUT stream."""
    import ctypes
    import hashlib

    from minio_tpu import native
    from minio_tpu.erasure.codec import Erasure
    from minio_tpu.ops import gf_native
    from minio_tpu.ops import highwayhash as hhmod
    from minio_tpu.storage.fileinfo import (
        ChecksumInfo, ErasureInfo, FileInfo, new_uuid,
    )
    from minio_tpu.storage.xlmeta import XLMeta

    out: dict = {}
    er = Erasure(12, 4, MIB)
    S = er.shard_size()
    payload = np.random.default_rng(3).integers(
        0, 256, total_mib * MIB, np.uint8
    ).tobytes()
    nbytes = len(payload)

    def rate(fn, reps=3, scale=1.0):
        best = 0.0
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            dt = time.perf_counter() - t0
            best = max(best, nbytes * scale / dt / 1e9)
        return round(best, 3)

    # 1: stream read into the block-major [B, k*S] strip buffer (one
    # contiguous readinto per 1 MiB block — the production fill).
    buf = np.empty((8, 12 * S), dtype=np.uint8)

    def fill():
        src = io.BytesIO(payload)
        for blk in range(total_mib):
            src.readinto(memoryview(buf[blk % 8])[:MIB])

    out["source_read_gbps"] = rate(fill)
    # 2: content md5 (the S3 ETag contract; serial by construction —
    # the hot path hashes the same contiguous block-sized views).
    out["md5_gbps"] = rate(lambda: hashlib.md5(payload))
    # 3: GF(2^8) parity encode (native engine, [B, k, S] batches as the
    # block-major driver dispatches them).
    blocks3 = buf.reshape(8, 12, S)
    out["encode_gbps"] = rate(
        lambda: [gf_native.apply_matrix_batch(er._parity_mat, blocks3)
                 for _ in range(total_mib // 8)]
    )
    # 4: bitrot frame digests — the vectored path hashes chunks in place
    # (hh256_hash_strided), copying nothing; this is the hash-only cost
    # the old frame+copy stage used to bundle with a full memcpy.
    lib = native.load()
    if lib is not None:
        row = np.ascontiguousarray(buf[0])
        n = row.size
        nch = (n + S - 1) // S
        digs = np.empty((nch, 32), dtype=np.uint8)
        u8p = ctypes.POINTER(ctypes.c_uint8)

        def frame():
            for _ in range(nbytes // n):
                lib.hh256_hash_strided(hhmod.MAGIC_KEY,
                                       row.ctypes.data_as(u8p), S, nch, S,
                                       digs.ctypes.data_as(u8p))

        out["bitrot_frame_gbps"] = rate(frame)
        # 5: vectored shard write — [digest||chunk] iovecs straight from
        # the strip buffer via writev, the zero-copy write path.
        wdir = os.path.join(root, "stages")
        os.makedirs(wdir, exist_ok=True)
        iov = []
        for c in range(nch):
            iov.append(memoryview(digs[c]))
            iov.append(memoryview(row)[c * S: (c + 1) * S])

        def shard_write():
            fd = os.open(os.path.join(wdir, "w"),
                         os.O_WRONLY | os.O_CREAT | os.O_TRUNC)
            for _ in range(nbytes // n):
                os.writev(fd, iov)
            os.close(fd)

        out["shard_write_gbps"] = rate(shard_write)
        _cleanup(wdir)
    # 6: metadata commit (16 disks' xl.meta write+rename), in
    # microseconds per PUT rather than GB/s — it is size-independent.
    # Models the production fan-out: ONE serialization per PUT
    # (storage/xlmeta.FanoutMetaPack), each disk stamping its shard
    # index into a copy of the shared buffer. The pre-pack per-disk
    # serializer is measured alongside so the removed setup cost is
    # visible (meta_serialize_us_removed).
    from minio_tpu.storage.xlmeta import FanoutMetaPack

    mdir = os.path.join(root, "stages-meta")
    os.makedirs(mdir, exist_ok=True)
    fi = FileInfo(
        volume="b", name="o", version_id="", data_dir=new_uuid(),
        mod_time_ns=time.time_ns(), size=10 * MIB,
        metadata={"etag": "0" * 32},
        erasure=ErasureInfo(
            data_blocks=12, parity_blocks=4, block_size=MIB, index=1,
            distribution=list(range(1, 17)),
            checksums=[ChecksumInfo(1, "highwayhash256S")],
        ),
    )
    fi.add_part(1, 10 * MIB, 10 * MIB)
    reps = 50
    t0 = time.perf_counter()
    for r in range(reps):
        pack = FanoutMetaPack()
        for d in range(16):
            fi.erasure.index = d + 1
            blob = pack.bytes_for(fi)
            if blob is None:  # template declined: per-disk serializer
                m = XLMeta()
                m.add_version(fi)
                blob = m.to_bytes()
            p = os.path.join(mdir, f"d{d}.xl.meta")
            with open(p + ".tmp", "wb") as f:
                f.write(blob)
            os.replace(p + ".tmp", p)
    out["meta_commit_us_per_put"] = round(
        (time.perf_counter() - t0) / reps * 1e6
    )
    # Serialization-only comparison: once-per-disk packb vs one shared
    # template stamp — the per-PUT cost the fan-out pack removes.
    t0 = time.perf_counter()
    for r in range(reps):
        for d in range(16):
            fi.erasure.index = d + 1
            m = XLMeta()
            m.add_version(fi)
            m.to_bytes()
    per_disk_us = (time.perf_counter() - t0) / reps * 1e6
    t0 = time.perf_counter()
    for r in range(reps):
        pack = FanoutMetaPack()
        for d in range(16):
            fi.erasure.index = d + 1
            pack.bytes_for(fi)
    packed_us = (time.perf_counter() - t0) / reps * 1e6
    out["meta_serialize_us_removed"] = round(per_disk_us - packed_us)
    fi.erasure.index = 1
    _cleanup(mdir)
    # Per-PUT encoder setup removed by the geometry-keyed Erasure cache
    # (object layer reuses one codec per geometry instead of re-deriving
    # the coding/bit matrices each PUT).
    from minio_tpu.erasure.codec import cached_erasure
    from minio_tpu.ops.gf import _bit_matrix_cached

    cached_erasure(12, 4, MIB)  # prime
    t0 = time.perf_counter()
    for _ in range(50):
        _bit_matrix_cached.cache_clear()
        Erasure(12, 4, MIB)
    fresh_us = (time.perf_counter() - t0) / 50 * 1e6
    t0 = time.perf_counter()
    for _ in range(50):
        cached_erasure(12, 4, MIB)
    cached_us = (time.perf_counter() - t0) / 50 * 1e6
    out["put_setup_us_removed"] = round(fresh_us - cached_us)
    # 6b: inline small-object PUT p50 — the whole object (shards ≤ the
    # inline threshold) commits as ONE xl.meta journal write per disk,
    # no staged part files, no rename (MinIO smallFileThreshold parity).
    idir = os.path.join(root, "stages-inline")
    es_i, _ = _mk_set(idir, 4, 2)
    small = os.urandom(64 << 10)
    lat = []
    for i in range(30):
        t0 = time.perf_counter()
        es_i.put_object("bench", f"inl{i}", io.BytesIO(small), len(small))
        lat.append((time.perf_counter() - t0) * 1e6)
    out["inline_put_64k_p50_us"] = round(statistics.median(lat))
    _cleanup(idir)
    # The serial PUT model: input passes once through each byte-rate
    # stage (frame+write carry the 1.33x shard expansion).
    inv = 0.0
    for key, exp in (("source_read_gbps", 1.0), ("md5_gbps", 1.0),
                     ("encode_gbps", 1.0), ("bitrot_frame_gbps", 4 / 3),
                     ("shard_write_gbps", 4 / 3)):
        if key in out and out[key] > 0:
            inv += exp / out[key]
    if inv > 0:
        out["model_put_gbps"] = round(1.0 / inv, 3)
    # Measured md5-vs-encode overlap on THIS host: the r5 pipelined tee
    # (object/types.py TeeMD5Reader) hashes batch N on a second thread
    # while batch N+1 encodes — hashlib and the native encoder both
    # release the GIL, so >=2 cores overlap for real; a 1-core host
    # measures ~1.0 and the serial model stands.
    import threading as _th

    def _overlap_round():
        t0 = time.perf_counter()
        th = _th.Thread(target=lambda: hashlib.md5(payload))
        th.start()
        for _ in range(total_mib // 8):
            gf_native.apply_matrix_batch(er._parity_mat, blocks3)
        th.join()
        return time.perf_counter() - t0

    t_serial = (nbytes / out["md5_gbps"] / 1e9
                + nbytes / out["encode_gbps"] / 1e9)
    t_par = min(_overlap_round() for _ in range(3))
    speedup = t_serial / t_par if t_par > 0 else 1.0
    out["md5_overlap_speedup"] = round(speedup, 3)
    if inv > 0 and out.get("md5_gbps", 0) > 0 \
            and out.get("encode_gbps", 0) > 0:
        # Pipelined model: the md5+encode pair runs at its MEASURED
        # overlap factor; the remaining stages stay serial. speedup=1
        # reproduces model_put_gbps; perfect overlap collapses the pair
        # to its slower member.
        pair_inv = 1.0 / out["md5_gbps"] + 1.0 / out["encode_gbps"]
        inv_pipe = (inv - pair_inv) + pair_inv / max(speedup, 1.0)
        out["model_put_gbps_pipelined"] = round(1.0 / inv_pipe, 3)
    # The REAL pipelined PUT stream end to end: TeeMD5Reader →
    # encode_stream on the staged pipeline (pipeline/executor.py:
    # source-read ∥ md5 ∥ encode ∥ bitrot-frame ∥ shard-write over
    # pooled strip buffers) → bitrot writers on real files. GB/s of
    # INPUT bytes — directly comparable to model_put_gbps: exceeding it
    # means the stages genuinely overlap instead of running
    # back-to-back.
    from minio_tpu.object.types import TeeMD5Reader
    from minio_tpu.pipeline.buffers import COPY

    pdir = os.path.join(root, "stages-pipe")
    COPY.reset()
    out["pipeline_put_gbps"] = round(_hostfed_encode_best(
        pdir, "pipe", payload, 3,
        lambda: TeeMD5Reader(_ZeroCopyReader(payload), size=nbytes),
        finish=lambda tee: tee.md5_hex(),  # PUT drains the hash pre-commit
        telemetry="bench-put",
    ), 3)
    _cleanup(pdir)
    # Per-stage copy accounting of those runs: bytes each hot-path site
    # copied (or freshly materialized). The zero-copy floor for this
    # pipelined PUT is ONE source-read copy per input byte and nothing
    # else — any other site growing here is a regression
    # (pipeline/buffers.CopyCounters; asserted by test_bench_smoke).
    cc = COPY.snapshot()
    out["copy_counters"] = cc
    moved = 3 * nbytes  # 3 reps of the payload
    out["copies_per_input_byte"] = round(sum(cc.values()) / moved, 3)
    # Per-stage telemetry of those runs (items/busy/starve/stall per
    # stage) — the same counters the metrics endpoint exports.
    from minio_tpu.pipeline import stage_stats_snapshot

    out["pipeline_stages"] = stage_stats_snapshot("bench-put")
    # On/off A/B protocol shared by the span-tracing (ISSUE 12) and
    # byte-flow-ledger (ISSUE 14) <=2% overhead gates. Samples are
    # >=16 MiB regardless of the caller's smoke payload — a ~10 ms rep
    # is scheduler-noise-dominated and no pairing statistic recovers a
    # sub-1% signal from +-3% samples. Adjacent pairs with alternating
    # within-pair order: CPU frequency drift across the run cancels PER
    # PAIR, and the MEDIAN of pairwise overheads (unlike best-of sides)
    # is not biased by whichever side caught the fastest window.
    import statistics as _stats

    ab_payload = payload if nbytes >= 16 * MIB else payload * (
        (16 * MIB + nbytes - 1) // nbytes
    )
    ab_nbytes = len(ab_payload)

    def _ab_protocol(run_once, pairs: int = 7) -> dict:
        """run_once(armed: bool) -> GB/s (itself best-of-reps, so a
        single descheduling stall cannot poison a sample). The reported
        overhead is min(median of pairwise overheads, best-vs-best
        overhead): both statistics converge on the true plane cost (a
        real x% tax shifts EVERY sample, hence both), while scheduler
        noise — which only ever slows a sample — inflates each through
        a different failure mode, so the smaller one is the honest
        floor-to-floor estimate. A noisy window (estimate above 1%,
        ~10x the measured plane cost) buys four more pairs before the
        gate judges."""
        on_best = off_best = 0.0
        pair_overheads: list[float] = []
        run_once(False)  # untimed warm-up: dirs, imports, page cache

        def _run_pairs(n: int):
            nonlocal on_best, off_best
            for _ in range(n):
                order = ((True, False) if len(pair_overheads) % 2 == 0
                         else (False, True))
                res = {}
                for armed in order:
                    res[armed] = run_once(armed)
                on_best = max(on_best, res[True])
                off_best = max(off_best, res[False])
                if res[False] > 0:
                    pair_overheads.append(
                        100.0 * (res[False] - res[True]) / res[False]
                    )

        def _overhead() -> float:
            med = (_stats.median(pair_overheads) if pair_overheads
                   else 0.0)
            bestd = (100.0 * (off_best - on_best) / off_best
                     if off_best > 0 else 0.0)
            return min(med, bestd)

        _run_pairs(pairs)
        if _overhead() > 1.0:
            _run_pairs(4)
        return {
            "on_gbps": round(on_best, 3),
            "off_gbps": round(off_best, 3),
            "overhead_pct": round(_overhead(), 2),
            "pair_overheads_pct": [round(p, 2) for p in pair_overheads],
        }

    # Span-tracing on/off A/B (ISSUE 12): the same pipelined PUT with
    # a LIVE request trace (every admission/stage/worker/fanout span
    # recorded) vs MTPU_TRACE=0 (the whole plane disarmed). The plane's
    # contract is <=2% throughput overhead — asserted by
    # test_bench_smoke.
    from minio_tpu.observability import spans as _spans

    adir = os.path.join(root, "stages-trace")
    saved_trace = os.environ.get("MTPU_TRACE")
    saved_slow = os.environ.get("MTPU_TRACE_SLOW_MS")
    # auto-threshold mode: no exemplar capture mid-measurement (the
    # capture scan is the slow path and must not run per request).
    os.environ["MTPU_TRACE_SLOW_MS"] = "auto"

    def _trace_once(traced: bool) -> float:
        os.environ["MTPU_TRACE"] = "1" if traced else "0"
        if traced:
            with _spans.request_trace("bench-put-ab"):
                return _hostfed_encode_best(
                    adir, "tr", ab_payload, 2,
                    lambda: TeeMD5Reader(_ZeroCopyReader(ab_payload),
                                         size=ab_nbytes),
                    finish=lambda tee: tee.md5_hex(),
                    telemetry="bench-trace-ab",
                )
        return _hostfed_encode_best(
            adir, "tr", ab_payload, 2,
            lambda: TeeMD5Reader(_ZeroCopyReader(ab_payload),
                                 size=ab_nbytes),
            finish=lambda tee: tee.md5_hex(),
            telemetry="bench-trace-ab",
        )

    try:
        tr = _ab_protocol(_trace_once)
    finally:
        for var, saved in (("MTPU_TRACE", saved_trace),
                           ("MTPU_TRACE_SLOW_MS", saved_slow)):
            if saved is None:
                os.environ.pop(var, None)
            else:
                os.environ[var] = saved
        _cleanup(adir)
    out["trace_ab"] = {
        "tracing_on_gbps": tr["on_gbps"],
        "tracing_off_gbps": tr["off_gbps"],
        "overhead_pct": tr["overhead_pct"],
        "pair_overheads_pct": tr["pair_overheads_pct"],
    }
    # Byte-flow ledger on/off A/B (ISSUE 14): same protocol, with the
    # ledger armed under a live op tag (every shard write accounted)
    # vs MTPU_IOFLOW=0. Contract: <=2% PUT throughput overhead,
    # asserted in test_bench_smoke.
    from minio_tpu.observability import ioflow as _ioflow

    fdir = os.path.join(root, "stages-ioflow")
    saved_ioflow = os.environ.get("MTPU_IOFLOW")

    def _flow_once(armed: bool) -> float:
        os.environ["MTPU_IOFLOW"] = "1" if armed else "0"
        with _ioflow.tag("put", bucket="bench-ab"):
            return _hostfed_encode_best(
                fdir, "fl", ab_payload, 2,
                lambda: TeeMD5Reader(_ZeroCopyReader(ab_payload),
                                     size=ab_nbytes),
                finish=lambda tee: tee.md5_hex(),
                telemetry="bench-ioflow-ab",
            )

    try:
        fl = _ab_protocol(_flow_once)
    finally:
        if saved_ioflow is None:
            os.environ.pop("MTPU_IOFLOW", None)
        else:
            os.environ["MTPU_IOFLOW"] = saved_ioflow
        _cleanup(fdir)
    out["ioflow_ab"] = {
        "ledger_on_gbps": fl["on_gbps"],
        "ledger_off_gbps": fl["off_gbps"],
        "overhead_pct": fl["overhead_pct"],
        "pair_overheads_pct": fl["pair_overheads_pct"],
    }
    return out


def bench_ioflow(root: str) -> dict:
    """Byte-flow ledger efficiency section (ISSUE 14): measured ledger
    ratios on a 12+4 set — the repair-efficiency numbers every later
    codec/heal PR is judged against.

    - heal_bytes_read_per_byte_healed: 1-shard heal — dense RS reads
      k survivors to rebuild 1, so this is exactly k (12); pinned in
      test_bench_smoke. The 2-down variant reads k per TWO rebuilt
      shards (k/2). A regenerating-code engine must land below these.
    - put_write_bytes_per_payload_byte: (k+m)/k plus framing/meta.
    - degraded_get_read_amplification: full-object degraded GET ~1.0.
    """
    import io as _io

    from minio_tpu.observability import ioflow

    out: dict = {"k": 12, "m": 4}
    size = 8 * MIB
    payload = os.urandom(size)

    def put_one(name: str):
        with ioflow.tag("put", bucket="bench"):
            es.put_object("bench", name, _io.BytesIO(payload), size)

    def heal_ratio(kill: int, name: str) -> float:
        put_one(name)
        killed = 0
        for d in disks:
            if killed == kill:
                break
            try:
                d.delete("bench", name, recursive=True)
                killed += 1
            except Exception:  # noqa: BLE001 - disk without the object
                continue
        ioflow.reset()
        res = es.heal_object("bench", name)
        assert res["healed"], res
        ops = ioflow.op_totals().get("heal", {})
        return round(ops.get("read", 0) / max(1, ops.get("write", 1)), 4)

    es, disks = _mk_set(os.path.join(root, "ioflow"), 16, 4)
    # PUT reconciliation: shard writes == (k+m)/k x payload + framing.
    ioflow.reset()
    put_one("flow-put")
    wr = ioflow.op_totals().get("put", {}).get("write", 0)
    out["put_write_bytes_per_payload_byte"] = round(wr / size, 4)
    out["heal_bytes_read_per_byte_healed"] = heal_ratio(1, "flow-h1")
    out["heal_2down_bytes_read_per_byte_healed"] = heal_ratio(
        2, "flow-h2")
    # Degraded GET: wipe the object (shards AND metadata) on the two
    # disks holding DATA shards 1 and 2 — the shard loss is visible in
    # the metadata phase, so the get-degraded promotion fires before
    # the first byte is read and the amplification number is
    # deterministic (a mid-stream promotion leaves the pre-discovery
    # bytes under plain `get`, which is honest but batch-order-
    # dependent).
    from minio_tpu.object.metadata import hash_order

    put_one("flow-get")
    dist = hash_order("bench/flow-get", len(disks))
    for i, shard in enumerate(dist):
        if shard in (1, 2):  # 1-based shard index; 1..12 are data
            disks[i].delete("bench", "flow-get", recursive=True)
    ioflow.reset()
    sink = _io.BytesIO()
    with ioflow.tag("get", bucket="bench"):
        es.get_object("bench", "flow-get", sink)
    assert sink.getvalue() == payload
    snap = ioflow.snapshot()
    eff = ioflow.efficiency(snap)
    out["degraded_get_read_amplification"] = eff[
        "degraded_get_read_amplification"]
    out["degraded_get_ops"] = {
        k: v for k, v in ioflow.op_totals(snap).items()
    }
    ioflow.reset()
    return out


def bench_device_stage_breakdown() -> dict:
    """Per-stage timing of ONE 8-block device-engine batch — the
    instrumentation that explains
    device_stream_hostfed_gbps: is it H2D, dispatch latency, compute, or
    D2H that serializes? All figures are ms per 8 MiB batch, best of 3,
    measured through the fused single-dispatch engine
    (erasure/device_engine): `dispatch_ms` is the async call overhead
    (submit + start of the output D2H) that the r5 accounting left
    unattributed, so stage_sum_ms now includes it and
    `model_residual_ms` shows how far the model is from adding up.
    `d2h_*_ms` are the RESIDUAL waits after the async host copies
    started at dispatch time — near zero means the overlap is real.
    `null_dispatch_ms` is the round trip of a 1-byte op — the floor any
    per-batch dispatch pays."""
    import jax
    import jax.numpy as jnp

    from minio_tpu.erasure import device_engine
    from minio_tpu.erasure.codec import Erasure
    from minio_tpu.utils import ceil_frac

    out: dict = {}
    K, M, B = 12, 4, 8
    shard = ceil_frac(MIB, K)
    er = Erasure(K, M, MIB)
    codec = device_engine.for_geometry(K, M)
    data_np = np.random.default_rng(5).integers(
        0, 256, size=(B, K, shard), dtype=np.uint8
    )

    def best(fn, reps=3):
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            times.append(time.perf_counter() - t0)
        return min(times) * 1e3

    # Dispatch round-trip floor: trivial 1-element op, host-blocked.
    one = jax.device_put(np.ones(1, dtype=np.uint8))
    jnp.add(one, one).block_until_ready()
    out["null_dispatch_ms"] = round(
        best(lambda: jnp.add(one, one).block_until_ready()), 2
    )
    # H2D: ship the [8, 12, S] batch.
    jax.device_put(data_np).block_until_ready()
    out["h2d_ms"] = round(
        best(lambda: jax.device_put(data_np).block_until_ready()), 2
    )
    # Warm/compile the fused function once (input is donated — every
    # call below stages a fresh device batch).
    p, h = codec.encode_async(jax.device_put(data_np), True)
    p.block_until_ready()

    # Dispatch overhead: encode_async returns after submitting the
    # fused computation and starting the async D2H — this is the
    # per-batch invocation cost that is NOT h2d/compute/d2h.
    def timed_round():
        dev = jax.device_put(data_np)
        dev.block_until_ready()
        t0 = time.perf_counter()
        pp, hh = codec.encode_async(dev, True)
        t_dispatch = time.perf_counter() - t0
        t0 = time.perf_counter()
        pp.block_until_ready()
        hh.block_until_ready()
        t_compute = time.perf_counter() - t0
        t0 = time.perf_counter()
        np.asarray(pp)
        t_dp = time.perf_counter() - t0
        t0 = time.perf_counter()
        np.asarray(hh)
        t_dh = time.perf_counter() - t0
        return t_dispatch, t_compute, t_dp, t_dh

    rounds = [timed_round() for _ in range(3)]
    out["dispatch_ms"] = round(min(r[0] for r in rounds) * 1e3, 2)
    out["compute_ms"] = round(min(r[1] for r in rounds) * 1e3, 2)
    out["d2h_parity_ms"] = round(min(r[2] for r in rounds) * 1e3, 2)
    out["d2h_hashes_ms"] = round(min(r[3] for r in rounds) * 1e3, 2)

    # Full per-batch round trip exactly as the streaming drivers do it:
    # H2D -> one fused dispatch (donated input, async D2H) -> np.asarray
    # both outputs.
    def full_batch():
        pf, hf = er.encode_batch_async(data_np, with_hashes=True)
        np.asarray(pf)
        np.asarray(hf)

    prior_engine = os.environ.get("MTPU_ENCODE_ENGINE")
    os.environ["MTPU_ENCODE_ENGINE"] = "device"
    try:
        full_batch()  # warm/compile
        out["full_batch_ms"] = round(best(full_batch), 2)
    finally:
        if prior_engine is None:
            os.environ.pop("MTPU_ENCODE_ENGINE", None)
        else:
            os.environ["MTPU_ENCODE_ENGINE"] = prior_engine
    out["stage_sum_ms"] = round(
        out["h2d_ms"] + out["dispatch_ms"] + out["compute_ms"]
        + out["d2h_parity_ms"] + out["d2h_hashes_ms"], 2,
    )
    # The accounting gap r5 could not attribute (was ~98 ms): with the
    # dispatch overhead measured explicitly this should be ~0.
    out["model_residual_ms"] = round(
        out["full_batch_ms"] - out["stage_sum_ms"], 2
    )
    batch_bytes = B * MIB
    out["implied_hostfed_gbps"] = round(
        batch_bytes / (out["full_batch_ms"] / 1e3) / 1e9, 3
    )
    return out


def bench_device_batch_sweep() -> dict:
    """Batch-size sweep of the fused device encode: B ∈ {4, 16, 64}
    blocks per dispatch, full host-fed round trip (H2D + one fused
    dispatch + parity/digest D2H). Shows how the fixed per-dispatch
    overhead (null_dispatch_ms in device_stages) amortizes: per_block_ms
    should fall toward the pure transfer cost as B grows."""
    import jax

    from minio_tpu.erasure import device_engine
    from minio_tpu.utils import ceil_frac

    K, M = 12, 4
    shard = ceil_frac(MIB, K)
    codec = device_engine.for_geometry(K, M)
    device_engine.reset_stats()  # dispatch_stats must cover the sweep only
    out: dict = {}
    for B in (4, 16, 64):
        data_np = np.random.default_rng(11).integers(
            0, 256, size=(B, K, shard), dtype=np.uint8
        )

        def full():
            dev = jax.device_put(data_np)
            pf, hf = codec.encode_async(dev, True)
            # The sweep measures SERIALIZED per-batch latency on
            # purpose (amortization denominator, not throughput).
            np.asarray(pf)  # jax-ok: serialized on purpose
            np.asarray(hf)  # jax-ok: serialized on purpose

        full()  # warm/compile this batch shape
        t_best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            full()
            t_best = min(t_best, time.perf_counter() - t0)
        batch_bytes = B * MIB
        out[f"B{B}"] = {
            "batch_ms": round(t_best * 1e3, 2),
            "per_block_ms": round(t_best * 1e3 / B, 3),
            "gbps": round(batch_bytes / t_best / 1e9, 3),
        }
    s = device_engine.stats_snapshot()
    out["dispatch_stats"] = {
        "dispatches": s["dispatches"], "traces": s["traces"],
        "donated_batches": s["donated_batches"],
    }
    return out


def bench_mesh(total_mib: int = 32,
               geometry: tuple[int, int] = (12, 4),
               block_size: int = MIB) -> dict:
    """Mesh serving-engine sweep: host-fed encode_stream through
    MTPU_ENCODE_ENGINE=mesh for every (dp, lane) shape the local device
    count accepts, with the fused-dispatch invariants measured in vivo
    (dispatches per dp-group batch, steady-state retraces, estimated
    collective bytes per input byte). Skips cleanly — no mesh work at
    all — without multiple devices: a 1-device "mesh" number would only
    mislead the shape-choice guidance in DEPLOYMENT.md. `geometry` /
    `block_size` default to the 12+4 @ 1 MiB north star; the CI smoke
    passes a small geometry so the reporting contract is pinned without
    paying the full compile."""
    import jax

    n_dev = jax.local_device_count()
    if n_dev < 2:
        return {"skipped": f"single {jax.devices()[0].platform} device; "
                           "mesh needs jax.local_device_count() > 1"}
    from minio_tpu.erasure.bitrot import (
        BitrotAlgorithm,
        StreamingBitrotWriter,
    )
    from minio_tpu.erasure.codec import Erasure
    from minio_tpu.erasure.streaming import encode_stream
    from minio_tpu.parallel import meshcheck
    from minio_tpu.parallel import metrics as mesh_metrics

    k, m = geometry
    shapes = meshcheck.shapes_for(n_dev, k + m)
    if not shapes:
        return {"skipped": f"no (dp, lane) split of {n_dev} devices "
                           f"fits {k + m} shards"}
    out: dict = {"devices": n_dev}
    # The shared save/set/restore (meshcheck.forced_mesh_env) wraps
    # EVERYTHING, payload allocation included — an exception anywhere
    # must not leak the forced engine into later bench sections.
    with meshcheck.forced_mesh_env():
        payload = np.random.default_rng(17).integers(
            0, 256, (total_mib * MIB // block_size) * block_size, np.uint8
        ).tobytes()
        erasure = Erasure(k, m, block_size)
        for dp, lanes in shapes:
            os.environ["MTPU_MESH_SHAPE"] = f"{dp}x{lanes}"

            def run():
                writers = [
                    StreamingBitrotWriter(_Null(),
                                          BitrotAlgorithm.HIGHWAYHASH256S)
                    for _ in range(k + m)
                ]
                t0 = time.perf_counter()
                encode_stream(erasure, io.BytesIO(payload), writers,
                              k + 1)
                return time.perf_counter() - t0

            run()  # warm/compile this shape
            mesh_metrics.reset_stats()
            dt = min(run() for _ in range(3))
            s = mesh_metrics.stats_snapshot()
            out[f"dp{dp}_lane{lanes}"] = {
                "encode_gbps": round(len(payload) / dt / 1e9, 3),
                "dispatches_per_batch": round(
                    s["mesh_dispatches_total"]
                    / max(1, s["mesh_batches_total"]), 2
                ),
                "steady_state_retraces": s["mesh_retraces_total"],
                "collective_bytes_per_input_byte": round(
                    s["mesh_collective_bytes_total"]
                    / (3 * len(payload)), 3
                ),
            }
    return out


def bench_device() -> dict:
    """Device-kernel diagnostics: device-resident einsum GB/s and the
    host-fed device-engine stream (H2D + MXU + fused hashes + D2H)."""
    out: dict = {}
    import jax

    from minio_tpu.ops import gf
    from minio_tpu.ops.rs import _apply_bits
    from minio_tpu.utils import ceil_frac

    out["platform"] = jax.devices()[0].platform
    K, M, BATCH, ITERS = 12, 4, 64, 8
    shard = ceil_frac(MIB, K)
    import jax.numpy as jnp

    bitmat = jnp.asarray(gf.bit_matrix(gf.parity_matrix(K, M)),
                         dtype=jnp.int8)
    blocks_np = np.random.default_rng(0).integers(
        0, 256, size=(BATCH, K, shard), dtype=np.uint8
    )
    blocks = jax.device_put(blocks_np)
    data_bytes = BATCH * K * shard

    def measure(fn, args):
        o = fn(*args)
        o.block_until_ready()
        t0 = time.perf_counter()
        for _ in range(ITERS):
            o = fn(*args)
        o.block_until_ready()
        return data_bytes * ITERS / (time.perf_counter() - t0) / 1e9

    out["einsum_gbps"] = round(measure(jax.jit(_apply_bits),
                                       (bitmat, blocks)), 3)
    # H2D bandwidth: the quantity that decides the host-vs-device engine
    # policy. The device pipeline is feed-bound, so it beats the native
    # host engine exactly when H2D GB/s exceeds the native host-fed rate
    # (the crossover recorded in the main result).
    h2d_src = np.random.default_rng(7).integers(
        0, 256, 64 * MIB, np.uint8
    )
    jax.device_put(h2d_src[: MIB]).block_until_ready()  # warm
    t0 = time.perf_counter()
    jax.device_put(h2d_src).block_until_ready()
    out["h2d_gbps"] = round(h2d_src.nbytes / (time.perf_counter() - t0) / 1e9, 3)
    # SUSTAINED H2D: 8 consecutive 8 MiB batches, the shape the encode
    # pipeline actually ships. A link's burst rate (h2d_gbps above) can
    # far exceed its sustained rate — the sustained figure is what
    # bounds device_stream_hostfed_gbps (see device_stages).
    chunk = np.ascontiguousarray(h2d_src[: 8 * MIB])
    t0 = time.perf_counter()
    for _ in range(8):
        jax.device_put(chunk).block_until_ready()
    out["h2d_sustained_gbps"] = round(
        8 * chunk.nbytes / (time.perf_counter() - t0) / 1e9, 3
    )
    # Host-fed device-engine stream: the full async overlap pipeline
    # (staged H2D ∥ one fused dispatch per batch ∥ async parity/
    # digest D2H ∥ shard-write fan-out).
    from minio_tpu.erasure import device_engine
    from minio_tpu.erasure.bitrot import (
        BitrotAlgorithm,
        StreamingBitrotWriter,
    )
    from minio_tpu.erasure.codec import Erasure
    from minio_tpu.erasure.streaming import encode_stream

    prior_engine = os.environ.get("MTPU_ENCODE_ENGINE")
    os.environ["MTPU_ENCODE_ENGINE"] = "device"
    try:
        erasure = Erasure(12, 4, MIB)
        payload = blocks_np.tobytes()[: 32 * MIB]
        writers = [
            StreamingBitrotWriter(_Null(),
                                  BitrotAlgorithm.HIGHWAYHASH256S)
            for _ in range(16)
        ]
        encode_stream(erasure, io.BytesIO(payload), writers, 13)  # warm
        writers = [
            StreamingBitrotWriter(_Null(),
                                  BitrotAlgorithm.HIGHWAYHASH256S)
            for _ in range(16)
        ]
        device_engine.reset_stats()
        t0 = time.perf_counter()
        encode_stream(erasure, io.BytesIO(payload), writers, 13)
        out["device_stream_hostfed_gbps"] = round(
            len(payload) / (time.perf_counter() - t0) / 1e9, 3
        )
        # The fused-dispatch invariant, measured in vivo: one
        # dispatch per 8-block batch (32 MiB / 8 MiB = 4 batches),
        # zero retraces in steady state.
        stats = device_engine.stats_snapshot()
        n_batches = len(payload) // (8 * MIB)
        out["dispatches_per_batch"] = round(
            stats["dispatches"] / max(1, n_batches), 2
        )
        out["steady_state_traces"] = stats["traces"]
        out["donated_batches"] = stats["donated_batches"]
    finally:
        if prior_engine is None:
            os.environ.pop("MTPU_ENCODE_ENGINE", None)
        else:
            os.environ["MTPU_ENCODE_ENGINE"] = prior_engine
    return out


def bench_soak(root: str) -> dict:
    """Seeded mini-soak through the scenario engine (ISSUE 15): the
    tier-2 gate's shape at bench scale — mixed op classes, drive
    faults, a worker kill, an admission squeeze — reported with the
    memcpy-normalized throughput the gate's floor is written against
    (MTPU_SOAK_FLOOR; docs/SOAK.md). `passed` carries the full
    invariant verdict: a round where it is false is measuring a broken
    build, not a slow one."""
    from minio_tpu.faults.scenarios import (
        ScenarioSpec,
        host_memcpy_gbps,
        run_scenario,
    )

    spec = ScenarioSpec(
        seed=1337, clients=4, ops_per_client=8, disks=8, parity=4,
        payload_sizes=(256 << 10, 1 << 20), fault_drives=2,
        worker_kills=1, admission_slots=2, lock_check=False,
    )
    res = run_scenario(spec, root)
    # The SAME normalizer the gate's floor is written against
    # (scenarios.host_memcpy_gbps, best-of-3) — value_per_memcpy here
    # must be the number an operator retunes MTPU_SOAK_FLOOR from.
    memcpy = host_memcpy_gbps()
    art = res.to_dict()
    return {
        "passed": res.passed,
        "clients": spec.clients,
        "ops_per_client": spec.ops_per_client,
        "bytes_moved": res.bytes_moved,
        "wall_s": round(res.wall_s, 3),
        "soak_gbps": round(res.throughput_gbps, 5),
        "value_per_memcpy": round(res.throughput_gbps / memcpy, 7),
        "floor_value_per_memcpy": 2e-5,
        "host_memcpy_gbps": round(memcpy, 2),
        "drive_faults_fired": art["drive_faults_fired"],
        "verify_requeued": art["verify_requeued"],
        "counts": res.counts,
        "violations": {k: v for k, v in res.violations.items() if v},
    }


def bench_codec_sweep() -> dict:
    """Per-codec encode/decode/heal throughput through the registry's
    matrices on the strongest host kernel (ISSUE 16): every registered
    codec x the canonical geometries, each op under the min-of-3
    memcpy-normalized repeatability protocol. All codecs ride the SAME
    native any-matrix kernel, so the sweep isolates what the codec
    itself costs: matrix derivation is excluded (derived once, like the
    steady-state caches), the applications are what stream per byte.
    The cauchy entry also records its XOR-schedule accounting (xor
    count, CSE savings) per geometry — the numbers the bit-matrix
    literature (GT13) predicts wins from on XOR-only hardware.

    The schedule-interpreted numpy path and a worker-shm A/B need
    cores to mean anything; on a 1-core container those entries say
    {"skipped"} honestly rather than publishing a fake comparison."""
    from minio_tpu.erasure import registry
    from minio_tpu.ops import gf_native

    geometries = ((2, 2), (8, 4), (12, 4))
    shard = 1 << 20
    batch = 4
    native_ok = gf_native.available()
    out: dict = {
        "shard_bytes": shard,
        "batch": batch,
        "engine": "native" if native_ok else "numpy",
        "codecs": {},
    }
    rng = np.random.default_rng(0xC0DEC)

    def apply_rate(mat, blocks, entry):
        """GB/s of input shard bytes through one matrix application."""
        if native_ok:
            fn = lambda: gf_native.apply_matrix_batch(mat, blocks)  # noqa: E731
        else:
            fn = lambda: entry.host_apply(mat, blocks)  # noqa: E731
        fn()  # warm (kernel tables, schedule compilation)
        t0 = time.perf_counter()
        fn()
        return blocks.nbytes / (time.perf_counter() - t0) / 1e9

    for cid in registry.codec_ids():
        entry = registry.get(cid)
        per_geo = {}
        for k, m in geometries:
            if not entry.geometry_ok(k, m):
                per_geo[f"{k}+{m}"] = {"skipped": "geometry unsupported"}
                continue
            a = entry.alpha(k, m)
            blocks = rng.integers(0, 256, size=(batch, k, shard),
                                  dtype=np.uint8)
            # Sub-packetized codecs address sub-shards: the expanded
            # matrices ride the same kernel over a byte-identical
            # [batch, k·α, shard/α] view (codec._subshard_view).
            xb = (blocks.reshape(batch, k * a, shard // a) if a > 1
                  else blocks)
            n_lost = min(2, k, m)
            lost = list(range(n_lost))
            present = [i for i in range(k + m) if i not in lost][:k]
            mats = {
                "encode": entry.parity_matrix(k, m),
                # decode: rebuild the lost data shards from k survivors.
                "decode": entry.reconstruct_matrix(k, m, present, lost),
                # heal: the lost data plus one parity shard, the shape
                # a 2-down heal actually dispatches.
                "heal": entry.reconstruct_matrix(k, m, present,
                                                 lost + [k]),
            }
            geo = {}
            for op, mat in mats.items():
                geo[op] = _config_protocol(
                    lambda i, mat=mat: apply_rate(mat, xb, entry),
                    "max",
                )
            if entry.schedule_stats is not None:
                geo["schedule"] = entry.schedule_stats(mats["encode"])
            plan = (entry.repair_plan(k, m, 0)
                    if entry.repair_plan is not None else None)
            if plan is not None:
                # The regen row: single-shard repair-matrix application
                # over the β-symbols the plan actually reads — GB/s of
                # SYMBOL bytes in (the repair plane's per-byte cost),
                # alongside the declared disk-read fraction the e2e
                # ledger gate (c9) verifies.
                sx = rng.integers(
                    0, 256,
                    size=(batch, plan.total_symbols, shard // plan.alpha),
                    dtype=np.uint8,
                )
                geo["repair"] = _config_protocol(
                    lambda i, mat=plan.matrix, sx=sx: apply_rate(
                        mat, sx, entry),
                    "max",
                )
                geo["repair"]["read_fraction"] = round(
                    entry.declared_repair_fraction(k, m), 3)
            per_geo[f"{k}+{m}"] = geo
        out["codecs"][cid] = per_geo

    single_core = (os.cpu_count() or 1) < 2
    if single_core:
        out["numpy_schedule_ab"] = {
            "skipped": "single-core host: the schedule-interpreted "
                       "numpy path is GIL-bound here; an A/B against "
                       "native would measure the interpreter, not the "
                       "XOR schedule"
        }
        out["worker_shm_ab"] = {
            "skipped": "single-core host: the worker pool refuses to "
                       "arm (children would compete with the driver "
                       "for the one core)",
            "owed": "multicore round: per-codec worker-shm encode A/B "
                    "vs in-process native",
        }
    else:
        probe = {}
        for cid in registry.codec_ids():
            probe[cid] = _config_protocol(
                lambda i, cid=cid: registry.probe_geometry_gbps.__wrapped__(
                    cid, 8, 4
                ),
                "max",
            )
        out["numpy_schedule_ab"] = probe
        out["worker_shm_ab"] = {
            "owed": "wire the pool-armed per-codec A/B when a "
                    "multicore round runs"
        }
    return out


def bench_config9_repair(root: str) -> dict:
    """Config 9 (ISSUE 20): end-to-end single-shard heal A/B at 4+4 —
    dense RS vs the regenerating codec (msr-pm) — through the object
    layer with the byte-flow ledger attributing every heal byte, and
    three of the eight disks served over a REAL storage-REST loopback
    so the wire cost of remote repair symbols is measured, not
    modeled. Per arm (min-of-3, memcpy-normalized): heal GB/s, the
    ledger's heal_bytes_read_per_byte_healed (dense reads k = 4; the
    repair plane reads (n-1)/m = 1.75), and
    repair_wire_bytes_per_byte_healed (whole shards cross the wire
    dense; only β-slices cross under msr-pm)."""
    from minio_tpu.distributed.storage_rest import (
        RemoteStorage,
        StorageRESTServer,
    )
    from minio_tpu.object.erasure_objects import ErasureObjects
    from minio_tpu.object.types import ObjectOptions
    from minio_tpu.observability import ioflow
    from minio_tpu.storage.local import LocalStorage

    size = 8 * MIB
    n_remote = 3
    out: dict = {"object_mib": size // MIB, "geometry": "4+4",
                 "remote_survivors": n_remote}

    def run(i: int, codec: str) -> tuple[float, dict]:
        sub = os.path.join(root, f"r{i}-{codec or 'dense'}")
        raw = [
            LocalStorage(os.path.join(sub, f"d{j}"), endpoint=f"d{j}")
            for j in range(8)
        ]
        for d in raw:
            d.make_vol(".minio.sys")
        srv = StorageRESTServer(raw[-n_remote:], "c9secret",
                                "127.0.0.1", 0).start()
        try:
            disks = raw[:-n_remote] + [
                RemoteStorage(srv.endpoint, d.endpoint(), "c9secret")
                for d in raw[-n_remote:]
            ]
            es = ErasureObjects(disks, default_parity=4)
            es.make_bucket("bench")
            es.put_object("bench", "heal-me",
                          io.BytesIO(os.urandom(size)), size,
                          ObjectOptions(codec=codec))
            # ONE local disk loses its shard: the single-shard repair
            # shape the regenerating plan serves.
            raw[0].delete("bench", "heal-me", recursive=True)
            snap0 = ioflow.snapshot()["bytes"]
            t0 = time.perf_counter()
            res = es.heal_object("bench", "heal-me")
            dt = time.perf_counter() - t0
            assert res["healed"], res
            snap1 = ioflow.snapshot()["bytes"]
            remote_eps = {d.endpoint() for d in raw[-n_remote:]}
            delta = {"read": 0, "write": 0, "rwire": 0, "remote_read": 0}
            for (drive, op, dir_), n in snap1.items():
                if op != "heal":
                    continue
                n -= snap0.get((drive, op, dir_), 0)
                if dir_ in delta:
                    delta[dir_] += n
                if dir_ == "read" and drive in remote_eps:
                    # Bytes a remote survivor's DISK served this heal =
                    # bytes that crossed the wire on the dense path
                    # (read_file_stream ships the whole shard); the
                    # repair plane ships only β-slices (rwire).
                    delta["remote_read"] += n
            return size / dt / 1e9, delta
        finally:
            srv.stop()
            _cleanup(sub)

    for label, codec in (("dense_rs_gf8", ""), ("msr_pm", "msr-pm")):
        deltas: list[dict] = []

        def one(i: int, codec=codec, deltas=deltas) -> float:
            gbps, delta = run(i, codec)
            deltas.append(delta)
            return gbps

        proto = _config_protocol(one, "max")
        reads = [d["read"] / max(1, d["write"]) for d in deltas]
        wires = [d["rwire"] / max(1, d["write"]) for d in deltas]
        proto["heal_bytes_read_per_byte_healed"] = round(
            statistics.median(reads), 3)
        proto["repair_wire_bytes_per_byte_healed"] = round(
            statistics.median(wires), 3)
        proto["wire_bytes"] = deltas[-1]["rwire"]
        proto["remote_survivor_read_bytes"] = deltas[-1]["remote_read"]
        out[label] = proto

    dr = out["dense_rs_gf8"]["heal_bytes_read_per_byte_healed"]
    mr = out["msr_pm"]["heal_bytes_read_per_byte_healed"]
    out["disk_read_savings_x"] = round(dr / mr, 2) if mr else None
    # Wire honesty: with >= k local survivors the dense path reads k
    # full LOCAL shards and never touches the wire, so a dense-vs-msr
    # wire ratio would be vacuous here. The claim that matters is that
    # each remote survivor ships only its β-slice (β/α = 1/m of a
    # shard) instead of the whole shard a dense remote read would ship.
    mw = out["msr_pm"]["remote_survivor_read_bytes"]
    full_shards = n_remote * (size // 4)  # 4 = data shards at 4+4
    out["msr_wire_fraction_of_full_shards"] = (
        round(mw / full_shards, 3) if full_shards else None)
    return out


def bench_analysis_gate() -> dict:
    """Wall-time of the tier-1 static-analysis gate (tools/analysis).
    The scan runs on every CI pass, so its cost rides along with the
    throughput numbers it protects — a rule whose walk goes quadratic
    shows up here before it shows up as CI latency."""
    from tools.analysis import engine as _analysis

    report = _analysis.run()
    return {
        "wall_time_s": round(report.wall_time_s, 3),
        "files_scanned": report.files_scanned,
        "findings_new": len(report.new),
        "findings_waived": len(report.waived),
        "baseline_size": report.baseline_size,
    }


def _memcpy_gbps(size_mib: int = 128) -> float:
    """One host memcpy sample — the bandwidth bound every host-fed
    pipeline lives under (~5 passes per stream). Sampled ADJACENT to
    each config by the repeatability protocol, because the bench hosts'
    memcpy swings >2x with load and a single up-front sample cannot
    normalize a config measured minutes later."""
    a = np.random.default_rng(2).integers(0, 256, size_mib * MIB, np.uint8)
    b = np.empty_like(a)
    np.copyto(b, a)  # fault the destination pages in first
    t0 = time.perf_counter()
    np.copyto(b, a)
    return a.nbytes / (time.perf_counter() - t0) / 1e9


def _config_protocol(fn, better: str = "max", runs: int = 3) -> dict:
    """Bench repeatability protocol: min-of-N per config
    (best rate / lowest latency), host memcpy sampled adjacent to the
    runs, `value_per_memcpy` normalization and run dispersion emitted
    per config — so a round-to-round swing is attributable to the code
    or to the host, never ambiguous. `fn(i)` runs attempt i in its own
    directory; `better` is "max" for throughput, "min" for latency."""
    memcpy = _memcpy_gbps()
    vals = [float(fn(i)) for i in range(runs)]
    best = max(vals) if better == "max" else min(vals)
    med = statistics.median(vals)
    # Host-speed normalization must cancel the host term: throughput
    # scales WITH host speed H (T/H is invariant) but latency scales as
    # 1/H, so dividing a latency by memcpy would yield ~1/H^2 — more
    # host-dependent than the raw number. Latency configs multiply.
    norm = best / memcpy if better == "max" else best * memcpy
    return {
        "value": round(best, 3),
        "runs": [round(v, 3) for v in vals],
        "dispersion": round((max(vals) - min(vals)) / med, 3) if med else 0.0,
        "host_memcpy_gbps": round(memcpy, 2),
        "value_per_memcpy": round(norm, 4),
    }


def main() -> None:
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.exit(f"bench.py needs a TPU; jax found platform={platform}")

    from minio_tpu.ops import gf_native

    root = _bench_dir()
    engine = {2: "native-gfni", 1: "native-ssse3", 0: "native-scalar"}.get(
        gf_native.engine_kind(), "numpy"
    )

    memcpy_gbps = _memcpy_gbps()

    headline = bench_headline_encode(root)
    encode_only = bench_encode_only()
    configs = {}
    for key, fn, sub, better in (
        ("c1_put_2p2_1mib_p50_ms", bench_config1_put_p50, "c1", "min"),
        ("c2_roundtrip_12p4_10mib_gbps", bench_config2_roundtrip, "c2",
         "max"),
        ("c3_heal_12p4_2down_gbps", bench_config3_heal, "c3", "max"),
        ("c4_bitrot_get_8p4_gbps", bench_config4_bitrot_get, "c4", "max"),
        ("c5_pool_batched_put_gbps", bench_config5_pool_put, "c5", "max"),
    ):
        def one_run(i, fn=fn, sub=sub):
            sub_root = os.path.join(root, f"{sub}-r{i}")
            try:
                return fn(sub_root)
            finally:
                _cleanup(sub_root)

        configs[key] = _config_protocol(one_run, better)
    # Config 6: closed-loop many-client fan-in (its own driver — the
    # per-N entries each carry the full repeatability protocol).
    try:
        configs["c6_many_client_closed_loop"] = bench_config6_closed_loop(
            root
        )
    except Exception as exc:  # noqa: BLE001 - diagnostics are best-effort
        configs["c6_many_client_closed_loop"] = {
            "error": f"{type(exc).__name__}: {exc}"
        }
    # Config 7: closed-loop load generation at soak-gate scale with
    # every fault plane armed, plus the paced heal storm (ISSUE 17).
    try:
        c7_root = os.path.join(root, "c7-loadgen")
        try:
            configs["c7_loadgen"] = bench_config7_loadgen(c7_root)
        finally:
            _cleanup(c7_root)
    except Exception as exc:  # noqa: BLE001 - diagnostics are best-effort
        configs["c7_loadgen"] = {"error": f"{type(exc).__name__}: {exc}"}
    # Config 8: hot-object tier A/B — zipfian many-client GETs tier
    # on/off, plus the core-count-independent coalescing proof
    # (ISSUE 19).
    try:
        c8_root = os.path.join(root, "c8-hotget")
        try:
            configs["c8_hot_get"] = bench_config8_hot_get(c8_root)
        finally:
            _cleanup(c8_root)
    except Exception as exc:  # noqa: BLE001 - diagnostics are best-effort
        configs["c8_hot_get"] = {"error": f"{type(exc).__name__}: {exc}"}
    # Config 9: repair-bandwidth A/B — heal one lost shard dense vs
    # msr-pm with 3 of 8 survivors behind a loopback storage-REST
    # server, proving the β-slice wire/disk savings end to end
    # (ISSUE 20).
    try:
        c9_root = os.path.join(root, "c9-repair")
        try:
            configs["c9_repair"] = bench_config9_repair(c9_root)
        finally:
            _cleanup(c9_root)
    except Exception as exc:  # noqa: BLE001 - diagnostics are best-effort
        configs["c9_repair"] = {"error": f"{type(exc).__name__}: {exc}"}
    try:
        stages = bench_put_stages(root)
    except Exception as exc:  # noqa: BLE001 - diagnostics are best-effort
        stages = {"error": f"{type(exc).__name__}: {exc}"}
    result = {
        "metric": ("PutObject erasure-encode 12+4 @1MiB, host-fed into "
                   "streaming bitrot writers (the reference's "
                   "BenchmarkErasureEncode conditions)"),
        "value": round(headline, 3),
        "unit": "GB/s",
        # vs_baseline describes `value` against the same quantity's AVX2
        # estimate. There is no published reference e2e number, so the
        # conservative proxy is the 6 GB/s PURE-encode estimate — the
        # reference harness would also lose its IO/hash passes on this
        # host, making this ratio a LOWER bound on parity. The
        # like-for-like pure-encode ratio is reported separately.
        "vs_baseline": round(headline / AVX2_BASELINE_GBPS, 3),
        "vs_baseline_encode_only": round(encode_only / AVX2_BASELINE_GBPS, 3),
        # Normalization for cross-round comparability: e2e numbers are
        # memory-bandwidth-bound, and the bench hosts' memcpy varies
        # >2x day to day; value/memcpy cancels the host weather.
        "value_per_memcpy": round(headline / memcpy_gbps, 3),
        "engine": engine,
        "encode_only_gbps": round(encode_only, 3),
        "host_memcpy_gbps": round(memcpy_gbps, 2),
        "cpu_count": os.cpu_count(),
        "configs": configs,
        # Per-stage serial decomposition of PUT: the e2e number is the
        # harmonic composition of these (model_put_gbps); md5 (the S3
        # ETag contract) is the dominant serial stage on 1-core hosts.
        "put_stages": stages,
        # The device engine beats the native host engine when the
        # attachment's H2D bandwidth exceeds the native host-fed rate;
        # see device.h2d_gbps for what this attachment provides.
        "device_crossover_h2d_gbps": round(headline, 3),
        "baseline_estimated": True,
    }
    try:
        result["device"] = bench_device()
    except Exception as exc:  # noqa: BLE001 - device section is best-effort
        result["device"] = {"error": f"{type(exc).__name__}: {exc}"}
    try:
        result["device_stages"] = bench_device_stage_breakdown()
    except Exception as exc:  # noqa: BLE001 - diagnostics
        result["device_stages"] = {
            "error": f"{type(exc).__name__}: {exc}"
        }
    try:
        result["device_batch_sweep"] = bench_device_batch_sweep()
    except Exception as exc:  # noqa: BLE001 - diagnostics
        result["device_batch_sweep"] = {
            "error": f"{type(exc).__name__}: {exc}"
        }
    # Mesh serving engine: dp×lane sweep when this host has a
    # multi-device backend; a clean {"skipped": ...} otherwise.
    try:
        result["mesh"] = bench_mesh()
    except Exception as exc:  # noqa: BLE001 - diagnostics
        result["mesh"] = {"error": f"{type(exc).__name__}: {exc}"}
    # Parallel multipart vs serial single-stream PUT: the etag-of-parts
    # route around the single-stream MD5 wall, measured head to head.
    try:
        mp_root = os.path.join(root, "mp-bench")
        result["multipart_parallel"] = bench_multipart_parallel(mp_root)
        _cleanup(mp_root)
    except Exception as exc:  # noqa: BLE001 - diagnostics
        result["multipart_parallel"] = {
            "error": f"{type(exc).__name__}: {exc}"
        }
    # Byte-flow ledger efficiency (ISSUE 14): heal read/healed ratio
    # (the regenerating-codes baseline), PUT write reconciliation,
    # degraded-GET read amplification.
    try:
        flow_root = os.path.join(root, "ioflow-bench")
        result["ioflow"] = bench_ioflow(flow_root)
        _cleanup(flow_root)
    except Exception as exc:  # noqa: BLE001 - diagnostics
        result["ioflow"] = {"error": f"{type(exc).__name__}: {exc}"}
    # Scenario soak (ISSUE 15): the tier-2 gate's throughput-floor
    # numbers, recorded every round.
    try:
        soak_root = os.path.join(root, "soak-bench")
        result["soak"] = bench_soak(soak_root)
        _cleanup(soak_root)
    except Exception as exc:  # noqa: BLE001 - diagnostics
        result["soak"] = {"error": f"{type(exc).__name__}: {exc}"}
    # Codec registry sweep (ISSUE 16): encode/decode/heal per codec x
    # geometry, plus the cauchy XOR-schedule accounting.
    try:
        result["codec_sweep"] = bench_codec_sweep()
    except Exception as exc:  # noqa: BLE001 - diagnostics
        result["codec_sweep"] = {"error": f"{type(exc).__name__}: {exc}"}
    # Static-analysis gate cost (tools/analysis): tracked so the tier-1
    # scan stays visibly cheap.
    try:
        result["analysis_gate"] = bench_analysis_gate()
    except Exception as exc:  # noqa: BLE001 - diagnostics
        result["analysis_gate"] = {"error": f"{type(exc).__name__}: {exc}"}
    import shutil

    shutil.rmtree(root, ignore_errors=True)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
