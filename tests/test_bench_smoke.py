"""bench.py smoke: the driver runs it once per round on real hardware —
a syntax error or broken helper there silently zeroes the round's
benchmark record, so the pieces must stay importable and runnable."""

import io


def test_bench_helpers_produce_sane_numbers(tmp_path):
    import bench

    root = str(tmp_path)
    # Best of two: on a multi-core host the first stream's first batch
    # waits for a worker-pool child to finish booting (about a second),
    # which is spawn time, not encode throughput.
    v = bench.bench_headline_encode(root, total_mib=8, reps=2)
    assert v > 0.01
    assert bench.bench_encode_only(total_mib=8, reps=1) > 0.1
    p50 = bench.bench_config1_put_p50(root, n=4)
    assert 0 < p50 < 10_000
    stages = bench.bench_put_stages(root, total_mib=4)
    for key in ("source_read_gbps", "md5_gbps", "encode_gbps",
                "model_put_gbps"):
        assert stages.get(key, 0) > 0, (key, stages)
    assert stages["meta_commit_us_per_put"] > 0
    # The A/B pairs stay reported, not asserted: a CPU A/B of 4 MiB
    # cannot hold a 2% line under a loaded host. The <=2% contract of the
    # span plane rests on PERF.md's chip table (MTPU_TRACE=0 against
    # default on n16dev1-put10m); here, what a CPU run holds exactly.
    ab = stages["trace_ab"]
    assert ab["tracing_on_gbps"] > 0 and ab["tracing_off_gbps"] > 0
    fab = stages["ioflow_ab"]
    assert fab["ledger_on_gbps"] > 0 and fab["ledger_off_gbps"] > 0
    _span_plane_cost_is_counted(root)


# ring records a 10 MiB PUT over four wrapped drives may append (it
# appends some 30: 8 disk ops, 6 stages, the waits, the layer spans)
PUT_10MIB_RECORDS_MAX = 64


def _span_plane_cost_is_counted(root: str) -> None:
    """Off, a PUT appends no ring record and observes no histogram
    sample; on, a 10 MiB PUT appends a bounded number of records."""
    import os

    from minio_tpu.object.erasure_objects import ErasureObjects
    from minio_tpu.observability import spans
    from minio_tpu.observability.metrics import Metrics
    from minio_tpu.storage.diskcheck import DiskHealth, MetricsDisk
    from minio_tpu.storage.local import LocalStorage

    reg = Metrics()
    es = ErasureObjects(
        [MetricsDisk(LocalStorage(os.path.join(root, f"sp{i}"),
                                  endpoint=f"sp{i}"),
                     reg, health=DiskHealth(f"sp{i}")) for i in range(4)],
        default_parity=2)
    es.make_bucket("spans")
    body = os.urandom(10 << 20)

    def put(key: str) -> int:
        spans.reset()
        with spans.request_trace("put_object"):
            es.put_object("spans", key, io.BytesIO(body), len(body))
        with spans._rings_mu:
            return sum(r.n for r in spans._rings.values())

    saved = os.environ.get("MTPU_TRACE")
    spans.set_metrics(reg)
    try:
        os.environ["MTPU_TRACE"] = "0"
        assert put("off") == 0
        assert "span_seconds" not in reg.render_prometheus()
        os.environ.pop("MTPU_TRACE")
        appended = put("on")
        assert 0 < appended <= PUT_10MIB_RECORDS_MAX, appended
        assert 'span_seconds_count{kind="request",op="put_object"} 1' \
            in reg.render_prometheus()
    finally:
        spans.set_metrics(None)
        spans.reset()
        if saved is None:
            os.environ.pop("MTPU_TRACE", None)
        else:
            os.environ["MTPU_TRACE"] = saved


def test_zero_copy_reader_contract():
    from bench import _ZeroCopyReader

    payload = bytes(range(256)) * 10
    r = _ZeroCopyReader(payload)
    first = r.read(100)
    assert first == payload[:100]
    # The c5/c6 harness must stay off the copy budget: read() hands
    # out VIEWS of the shared payload, not per-call bytes copies.
    assert isinstance(first, memoryview)
    assert first.obj is payload
    buf = bytearray(50)
    assert r.readinto(buf) == 50
    assert bytes(buf) == payload[100:150]
    rest = r.read()
    assert rest == payload[150:]
    assert r.read(10) == b""
    assert not r.read(10)  # exhausted view is falsy, like b""


def test_heal_bench_survives_reps(tmp_path):
    import bench

    v = bench.bench_config3_heal(str(tmp_path), reps=2)
    assert v > 0.001


def test_ioflow_efficiency_pins(tmp_path):
    """ISSUE 14: the ledger's repair-efficiency numbers are exact
    physics for dense RS — bitrot framing is proportional on both
    sides of each ratio, so a single-shard 12+4 heal reads EXACTLY k
    bytes per byte healed (the baseline regenerating codes must beat),
    a 2-down heal reads k/2, a full-object degraded GET amplifies ~1x,
    and PUT writes (k+m)/k x payload plus framing."""
    import bench

    out = bench.bench_ioflow(str(tmp_path))
    assert out["heal_bytes_read_per_byte_healed"] == 12.0, out
    assert out["heal_2down_bytes_read_per_byte_healed"] == 6.0, out
    assert 0.99 <= out["degraded_get_read_amplification"] <= 1.05, out
    # (k+m)/k = 1.3333...; framing adds ~0.04% (32B per 8 KiB frame).
    assert 1.333 <= out["put_write_bytes_per_payload_byte"] <= 1.35, out


def test_put_stages_reports_pipelined_path(tmp_path):
    """The pipeline executor drives the bench's real pipelined PUT
    measurement: pipeline_put_gbps must come from actual encode_stream
    runs (with per-stage telemetry), and the overlap figure must be
    present for the acceptance gate to read."""
    import bench

    # >1 batch (8 blocks @1MiB): single-batch streams short-circuit to
    # the inline path, which records no pipeline stage stats.
    stages = bench.bench_put_stages(str(tmp_path), total_mib=12)
    assert stages.get("pipeline_put_gbps", 0) > 0.01, stages
    assert "md5_overlap_speedup" in stages
    # Some pipelined driver ran for real — its stage counters must be
    # present. Which stages exist depends on the engine (native:
    # encode/frame-write; device/numpy batched: dispatch/flush-write),
    # so assert on the shared labels.
    pstages = {k: v for k, v in
               stages.get("pipeline_stages", {}).items()
               if k.startswith("bench-put/")}
    assert pstages, stages.get("pipeline_stages")
    assert any(v["items"] > 0 for v in pstages.values()), pstages


def test_pipelined_put_no_copy_invariant(tmp_path):
    """The zero-copy floor: a pipelined host-fed PUT copies each payload
    byte exactly ONCE (the source read into the strip buffer). Framing
    copies must be zero on the vectored write path, and the shared strip
    pool must not grow while the vectored writers run."""
    import bench
    from minio_tpu.erasure.codec import Erasure
    from minio_tpu.ops import gf_native
    from minio_tpu.pipeline.buffers import COPY, _shared

    if not gf_native.available():
        import pytest

        pytest.skip("native engine unavailable: vectored path inactive")
    total_mib = 8
    stages = bench.bench_put_stages(str(tmp_path), total_mib=total_mib)
    cc = stages.get("copy_counters", {})
    assert cc, stages
    moved = 3 * total_mib << 20  # 3 reps over the payload
    # Floor: exactly one ingest copy per payload byte...
    assert cc.get("put.source_read", 0) == moved, cc
    # ...and ZERO framing copies (writev ships views directly).
    assert cc.get("put.frame_copy", 0) == 0, cc
    assert stages.get("copies_per_input_byte", 99) <= 1.05, stages
    # Pool no-growth across the vectored write runs.
    er = Erasure(12, 4, 1 << 20)
    key = ("blocks-major", 12, 8, er.shard_size())
    if key in _shared:
        stats = _shared[key].stats()
        assert stats["allocated"] <= stats["capacity"], stats
        assert stats["in_use"] == 0, stats
        # A second measured run must be fully recycled.
        before = stats["allocated"]
        COPY.reset()
        bench.bench_put_stages(str(tmp_path), total_mib=total_mib)
        after = _shared[key].stats()
        assert after["allocated"] == before, after
        assert after["reused"] > stats["reused"], after


def test_device_fused_path_is_one_dispatch_per_batch(monkeypatch):
    """Regression guard for the fused device engine: a device-engine
    PUT stream must cost exactly ONE device dispatch per [B, k, S]
    batch (GF parity + bitrot digests fused), and steady-state streams
    of the same geometry must not retrace/recompile. Runs on CPU — the
    dispatch accounting is platform-independent."""
    import io
    import os

    import numpy as np

    from minio_tpu.erasure import device_engine
    from minio_tpu.erasure.bitrot import StreamingBitrotWriter
    from minio_tpu.erasure.codec import Erasure
    from minio_tpu.erasure.streaming import encode_stream

    monkeypatch.setenv("MTPU_ENCODE_ENGINE", "device")
    k, m = 2, 2
    block_size = k * 4096  # shard 4096 >= device threshold
    er = Erasure(k, m, block_size)
    payload = np.random.default_rng(2).integers(
        0, 256, 6 * block_size, np.uint8
    ).tobytes()  # 6 full blocks -> 3 batches at batch_blocks=2

    def run():
        writers = [StreamingBitrotWriter(io.BytesIO()) for _ in range(k + m)]
        n = encode_stream(er, io.BytesIO(payload), writers, quorum=k + 1,
                          batch_blocks=2)
        assert n == len(payload)

    run()  # warm: compiles the fused fn for this batch shape
    device_engine.reset_stats()
    run()
    stats = device_engine.stats_snapshot()
    assert stats["dispatches"] == 3, stats  # ONE dispatch per batch
    assert stats["traces"] == 0, stats  # steady state: no recompiles
    assert stats["donated_batches"] == 3, stats
    # Second steady-state stream: still 1/batch, still no retrace.
    run()
    stats = device_engine.stats_snapshot()
    assert stats["dispatches"] == 6 and stats["traces"] == 0, stats
    assert os.environ["MTPU_ENCODE_ENGINE"] == "device"


def test_bench_refuses_to_run_without_a_tpu():
    """A CPU timing is never written under a device metric's name:
    without a TPU bench.py exits non-zero before it measures anything."""
    import pytest

    import bench

    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code not in (0, None)
    assert "platform=cpu" in str(exc.value.code)


def test_bench_mesh_skips_cleanly_on_single_device():
    """The mesh sweep must report a clean {"skipped": ...} — not an
    error, not CPU numbers — when only one device exists (the normal
    bench-host condition). Needs a subprocess: this test process runs
    on conftest's forced 8-device mesh."""
    import json
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""  # no forced 8-device host platform
    code = (
        "import json; import bench; print(json.dumps(bench.bench_mesh()))"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=180, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert r.returncode == 0, (r.stdout, r.stderr)
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert "skipped" in out, out


def test_bench_mesh_sweep_reports_dispatch_invariants(monkeypatch):
    """One shape of the mesh sweep on the in-process 8-device mesh: the
    section must report throughput plus the fused-dispatch guards
    (1 dispatch per dp-group batch, zero steady-state retraces). The
    full dp×lane sweep is covered by the mesh-marked serving tests;
    the smoke pins the reporting contract on a single shape."""
    import bench
    from minio_tpu.parallel import meshcheck

    monkeypatch.setattr(meshcheck, "shapes_for",
                        lambda n, total_shards=16: [(2, 4)])
    # Small geometry: the reporting contract is identical to the 12+4
    # default but the pjit compile is seconds, not half a minute.
    out = bench.bench_mesh(total_mib=4, geometry=(4, 4),
                           block_size=1 << 16)
    assert out["devices"] == 8, out
    entry = out["dp2_lane4"]
    assert entry["encode_gbps"] > 0, entry
    assert entry["dispatches_per_batch"] == 1.0, entry
    assert entry["steady_state_retraces"] == 0, entry
    assert entry["collective_bytes_per_input_byte"] > 0, entry


def test_c6_closed_loop_config_shape(tmp_path):
    """ISSUE 7 satellite: the c6 many-client config must carry the
    repeatability-protocol fields (runs/dispersion/memcpy) PLUS the
    closed-loop latency percentiles for every N, and skip cleanly on
    1-core hosts."""
    import os

    import bench

    if (os.cpu_count() or 1) < 2:
        out = bench.bench_config6_closed_loop(str(tmp_path))
        assert out == {
            "skipped": "single-core host: no fan-in concurrency"
        }
        return
    out = bench.bench_config6_closed_loop(
        str(tmp_path), ns=(2,), ops_per_client=1, size=1 << 20, runs=1
    )
    entry = out["n2"]
    for field in ("value", "runs", "dispersion", "host_memcpy_gbps",
                  "value_per_memcpy", "p50_ms", "p99_ms",
                  "admission_retries"):
        assert field in entry, (field, entry)
    assert entry["value"] > 0
    assert 0 < entry["p50_ms"] <= entry["p99_ms"]
    assert "admission" in out and out["admission"]["admitted_total"] > 0


def test_c6_skips_on_one_core(tmp_path, monkeypatch):
    import os

    import bench

    monkeypatch.setattr(os, "cpu_count", lambda: 1)
    out = bench.bench_config6_closed_loop(str(tmp_path))
    assert out == {"skipped": "single-core host: no fan-in concurrency"}


def test_c7_loadgen_skips_honestly_on_one_core(tmp_path, monkeypatch):
    """ISSUE 17: the load-gen section must publish {"skipped": ...} on
    a 1-core host — 64 closed-loop threads there measure the scheduler,
    and a fake number would poison every cross-round comparison."""
    import os

    import bench

    monkeypatch.setattr(os, "cpu_count", lambda: 1)
    out = bench.bench_config7_loadgen(str(tmp_path))
    assert set(out) == {"skipped"}
    assert "single-core" in out["skipped"]


def test_c7_loadgen_reports_gate_numbers(tmp_path):
    """Multicore only: a small c7 run must carry the soak gate's own
    numbers — latency board, span p99 attribution, hang fire count,
    and the heal-storm pacer block."""
    import os

    import bench

    if (os.cpu_count() or 1) < 2:
        import pytest

        pytest.skip("single-core host: c7 skips by contract")
    out = bench.bench_config7_loadgen(str(tmp_path), clients=64,
                                      ops_per_client=2)
    assert out["passed"], out.get("violations")
    assert out["clients"] >= 64
    assert out["hang_faults_fired"] > 0
    assert out["latency"]["all"]["count"] >= 64
    assert out["span_p99"].get("request")
    storm = out["heal_storm"]
    assert storm["passed"]
    assert storm["mrf_left"] == 0
    assert storm["p99_ratio"] <= storm["p99_mult"]
    assert storm["pacer"]["grants_total"] >= 24


def test_c8_hot_get_records_coalescing_proof_and_skips_ab_on_one_core(
        tmp_path, monkeypatch):
    """ISSUE 19: on a 1-core host the c8 A/B must publish {"skipped"}
    honestly, while the coalescing proof — logical counters, not wall
    time — still records: K=8 concurrent GETs of a cold-cache hot key
    register ONE leader decode and a factor > 4, with the ledger's
    shard-read bytes equal to one decode's."""
    import os

    import bench

    monkeypatch.setattr(os, "cpu_count", lambda: 1)
    out = bench.bench_config8_hot_get(str(tmp_path))
    assert set(out["ab"]) == {"skipped"}
    assert "single-core" in out["ab"]["skipped"]
    proof = out["coalescing_proof"]
    assert proof["k"] == 8
    assert proof["leaders"] == 1
    assert proof["served_without_decode"] == 7
    assert proof["coalescing_factor"] > 4
    assert proof["one_decode_read_bytes"] > 0
    assert proof["k_concurrent_read_bytes"] == \
        proof["one_decode_read_bytes"]
    # The knob is restored: the bench must not leak tier state into the
    # process that ran it.
    from minio_tpu.object import readtier

    assert readtier.snapshot() is None


def test_c8_hot_get_ab_shape(tmp_path):
    """Multicore only: both arms carry the repeatability protocol plus
    latency percentiles; the on-arm adds hit rate, coalescing factor,
    and the tier snapshot."""
    import os

    import bench

    if (os.cpu_count() or 1) < 2:
        import pytest

        pytest.skip("single-core host: the c8 A/B skips by contract")
    out = bench.bench_config8_hot_get(
        str(tmp_path), n_clients=4, ops_per_client=3, n_keys=4,
        runs=1,
    )
    for arm in ("tier_on", "tier_off"):
        entry = out[arm]
        for field in ("value", "runs", "dispersion", "host_memcpy_gbps",
                      "value_per_memcpy", "p50_ms", "p99_ms"):
            assert field in entry, (arm, field, entry)
        assert entry["value"] > 0
        assert 0 < entry["p50_ms"] <= entry["p99_ms"]
    on = out["tier_on"]
    assert on["cache_hit_rate"] > 0
    assert on["coalescing_factor"] >= 1
    assert on["tier"]["hits_total"] > 0
    assert out["speedup_on_vs_off"] > 0
    assert out["coalescing_proof"]["leaders"] == 1


def test_worker_pool_path_keeps_copy_floor(tmp_path, monkeypatch):
    """copies_per_input_byte must be UNCHANGED under the worker-pool
    path: the shm strip is filled by the same one-readinto-per-block
    source read, and no payload byte crosses the worker pipe."""
    import io
    import os

    import numpy as np

    from minio_tpu.erasure.bitrot import (
        BitrotAlgorithm,
        StreamingBitrotWriter,
    )
    from minio_tpu.erasure.codec import Erasure
    from minio_tpu.erasure.streaming import encode_stream
    from minio_tpu.ops import gf_native
    from minio_tpu.pipeline import workers
    from minio_tpu.pipeline.buffers import COPY

    if (os.cpu_count() or 1) < 2 or not gf_native.available():
        import pytest

        pytest.skip("worker pool inactive on this host")
    monkeypatch.setenv("MTPU_WORKER_POOL", "1")
    assert workers.ensure_pool() is not None
    er = Erasure(4, 2, 1 << 18)
    size = (1 << 18) * 12
    payload = np.random.default_rng(8).integers(
        0, 256, size, np.uint8
    ).tobytes()
    COPY.reset()
    writers = [StreamingBitrotWriter(io.BytesIO(),
                                     BitrotAlgorithm.HIGHWAYHASH256S)
               for _ in range(6)]
    n = encode_stream(er, io.BytesIO(payload), writers, 5)
    assert n == size
    cc = COPY.snapshot()
    moved = sum(cc.values())
    # Exactly one ingest copy per input byte, nothing else.
    assert cc.get("put.source_read", 0) == size, cc
    assert cc.get("put.frame_copy", 0) == 0, cc
    assert cc.get("put.pack_copy", 0) == 0, cc
    assert round(moved / size, 3) <= 1.05, cc


def test_multipart_parallel_bench_shape(tmp_path):
    import os

    import bench

    out = bench.bench_multipart_parallel(str(tmp_path), total_mib=8)
    if (os.cpu_count() or 1) < 2:
        assert "skipped" in out
        return
    assert out["serial_put_gbps"] > 0
    assert out["parallel_put_gbps"] > 0
    assert out["etag"].endswith(f"-{out['parts']}")


def test_config_repeatability_protocol(monkeypatch):
    """BENCH JSON per-config contract: min-of-3, runs,
    dispersion, adjacent host memcpy, value_per_memcpy."""
    import bench

    monkeypatch.setattr(bench, "_memcpy_gbps", lambda: 4.0)
    out = bench._config_protocol(lambda i: 10.0 + i, better="max", runs=3)
    assert out["value"] == 12.0
    assert out["runs"] == [10.0, 11.0, 12.0]
    assert out["host_memcpy_gbps"] == 4.0
    assert 0 <= out["dispersion"] < 1
    # Normalization direction: throughput divides by host speed,
    # latency MULTIPLIES (latency/memcpy would scale as 1/H^2 — more
    # host-dependent than the raw number, not less).
    assert out["value_per_memcpy"] == 3.0  # 12 / 4
    lat = bench._config_protocol(lambda i: 5.0 - i, better="min", runs=3)
    assert lat["value"] == 3.0
    assert lat["value_per_memcpy"] == 12.0  # 3 * 4


def test_meta_commit_reports_shared_serialization(tmp_path):
    """The metadata-commit stage must exercise the FanoutMetaPack path
    (serialize once per PUT, stamp per disk) and report the per-disk
    serialization cost it removed."""
    import bench

    stages = bench.bench_put_stages(str(tmp_path), total_mib=4)
    assert stages["meta_commit_us_per_put"] > 0
    assert "meta_serialize_us_removed" in stages
    assert "put_setup_us_removed" in stages


def test_pipeline_executor_smoke():
    """Fast end-to-end of the executor itself (the machinery every
    bench pipeline number rides on): ordering, telemetry, completion."""
    from minio_tpu.pipeline import Pipeline, Stage

    pipe = Pipeline("smoke", [
        Stage("a", lambda x: x + 1),
        Stage("b", lambda x: x * 3, bytes_of=lambda x: 8),
    ], queue_depth=2)
    assert list(pipe.results(range(16))) == [(x + 1) * 3 for x in range(16)]
    stats = pipe.stage_stats()
    assert stats["a"]["items"] == 16
    assert stats["b"]["bytes"] == 16 * 8


def test_bench_records_analysis_gate_cost():
    """The tier-1 static-analysis gate's wall-time rides in every bench
    record (ISSUE 6 satellite): a rule whose AST walk goes quadratic
    must show up as a number, not as mystery CI latency."""
    import bench

    gate = bench.bench_analysis_gate()
    assert gate["files_scanned"] > 100, gate
    # ISSUE 13: the gate parallelizes across cpu_count files-per-worker
    # workers, so wall time stays flat as rules grow — 15 s is the
    # budget even on the 1-core container running all ten rules
    # serially (measured ~4 s there).
    assert 0 < gate["wall_time_s"] <= 15, gate
    # The repo itself must be clean — same invariant the tier-1 gate
    # (test_static_analysis) enforces, visible here as a zero.
    assert gate["findings_new"] == 0, gate
