"""Tier-1 proofs for the scenario engine (minio_tpu/faults/scenarios):
plan determinism (same seed => same fault sequence and op streams),
mini mixed-workload soaks through the real S3 handlers (clean path and
under drive faults + admission pressure), invariant checkers that
actually DETECT violations, the faults admin active-listing, and the
versioned-overwrite + delete-marker + lifecycle-expiry-under-faults
coverage. The full-size gate lives in tests/test_chaos_soak.py
(`pytest -m soak`)."""

import io
import json
import os
import random
import threading
import time
import types

import pytest

from minio_tpu import faults
from minio_tpu.faults import scenarios
from minio_tpu.faults.scenarios import (
    ALL_OPS,
    BUCKET_EXP,
    BUCKET_VER,
    ScenarioHarness,
    ScenarioSpec,
    build_fault_plan,
    client_stream,
    inv_admission_conserved,
    inv_expiry,
    inv_no_loss,
    run_scenario,
    scenario_plan,
)


def _mini_spec(**kw) -> ScenarioSpec:
    base = dict(seed=42, clients=3, ops_per_client=6, disks=4, parity=2,
                payload_sizes=(16 << 10, 64 << 10), fault_drives=0,
                worker_kills=0, lock_check=False)
    base.update(kw)
    return ScenarioSpec(**base)


# ---------------------------------------------------------------------------
# determinism


def test_plan_is_a_pure_function_of_the_seed():
    """Same seed => identical plan (drive schedules, process events,
    every client's op stream); different seed => different plan."""
    a = scenario_plan(_mini_spec())
    b = scenario_plan(_mini_spec())
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
    c = scenario_plan(_mini_spec(seed=43))
    assert json.dumps(a, sort_keys=True) != json.dumps(c, sort_keys=True)


def test_fault_plan_composes_all_three_planes():
    spec = _mini_spec(fault_drives=2, worker_kills=2, peer_blackouts=1,
                      disks=8, parity=4)
    plan = build_fault_plan(spec, [f"soak-d{i}" for i in range(8)])
    assert len(plan["drive_schedules"]) == 2
    kinds = [e["kind"] for e in plan["events"]]
    assert kinds.count("worker_kill") == 2
    assert kinds.count("peer_blackout") == 1
    # Events are ordered by trigger op: the fault SEQUENCE is total.
    ats = [e["at_op"] for e in plan["events"]]
    assert ats == sorted(ats)


def test_streams_cover_every_op_class_at_gate_scale():
    """At the soak gate's default scale every op class appears — the
    acceptance criterion's 'all op classes' is a property of the plan,
    checkable without running anything."""
    spec = ScenarioSpec(seed=1337, clients=8, ops_per_client=10)
    ops = {o["op"] for c in range(spec.clients)
           for o in client_stream(spec, c)}
    assert ops == set(ALL_OPS)


def test_streams_mix_codecs_at_gate_scale():
    """Every PUT-like op carries a planned codec id and, at gate scale,
    every registered codec appears — the soak bucket interleaves codec
    identities, so the drain invariants run across codec boundaries
    (ISSUE 16), not on a homogeneous bucket."""
    from minio_tpu.erasure import registry

    spec = ScenarioSpec(seed=1337, clients=8, ops_per_client=10)
    put_like = [o for c in range(spec.clients)
                for o in client_stream(spec, c) if "size" in o]
    assert all(o.get("codec") in registry.codec_ids() for o in put_like)
    assert {o["codec"] for o in put_like} == set(registry.codec_ids())


# ---------------------------------------------------------------------------
# mini soaks (the engine end to end, tier-1 sized)


def test_mini_soak_clean_path(tmp_path):
    """No faults armed: every op succeeds, every invariant holds, and
    the ioflow clean-path equality (put writes == (k+m)/k x payload)
    is enforced by the gate itself."""
    res = run_scenario(_mini_spec(seed=5, clients=2, ops_per_client=5,
                                  payload_sizes=(32 << 10,)),
                       str(tmp_path))
    art = res.to_dict()
    assert res.passed, json.dumps(art, indent=2)
    assert art["drive_faults_fired"] == 0
    failed = {op: c["failed"] for op, c in res.counts.items()
              if isinstance(c, dict) and c.get("failed")}
    assert not failed, failed


def test_mini_soak_under_faults_and_pressure(tmp_path):
    """Drive faults on one drive + a 2-slot admission squeeze: ops may
    legally fail, but every invariant — no loss at quorum, MRF dry,
    pools settled, admission conservation, ledger reconciliation —
    holds at drain."""
    res = run_scenario(
        _mini_spec(fault_drives=1, admission_slots=2, worker_kills=1),
        str(tmp_path),
    )
    assert res.passed, json.dumps(res.to_dict(), indent=2)
    # The schedule really fired (deterministic for this seed: every op
    # makes dozens of disk calls against p≈0.2 specs on the victim).
    assert res.to_dict()["drive_faults_fired"] > 0


def test_artifact_shape_and_replay_plan(tmp_path):
    """The failure artifact is self-contained: JSON-able, and its
    embedded plan equals a fresh build from the same spec (the
    seed-replay workflow of docs/SOAK.md)."""
    spec = _mini_spec(seed=9, clients=2, ops_per_client=4)
    res = run_scenario(spec, str(tmp_path))
    art = json.loads(json.dumps(res.to_dict()))
    for key in ("passed", "plan", "counts", "fault_log", "violations",
                "wall_s", "bytes_moved", "throughput_gbps",
                "verify_requeued", "drive_faults_fired",
                "fault_status", "latency", "span_p99"):
        assert key in art, key
    # The load-gen telemetry is populated, not vestigial: per-op-class
    # latency quantiles and span-plane p99 attribution.
    assert art["latency"].get("all", {}).get("count", 0) > 0
    assert "request" in art["span_p99"]
    fresh = scenario_plan(_mini_spec(seed=9, clients=2, ops_per_client=4))
    assert json.dumps(art["plan"], sort_keys=True) == \
        json.dumps(fresh, sort_keys=True)


# ---------------------------------------------------------------------------
# the invariants detect violations (not just pass on good runs)


def test_no_loss_invariant_detects_quorum_loss(tmp_path):
    """Destroy more shards than parity behind the engine's back: the
    no-loss checker must flag the object, not shrug."""
    spec = _mini_spec()
    h = ScenarioHarness(str(tmp_path), spec)
    try:
        body = b"\xabQ" * 40_000
        st, _, _ = h.request("PUT", "/soak/c0/victim", body=body)
        assert st == 200
        oracle = scenarios._Oracle()
        oracle.commit("soak", "c0/victim", body)
        assert inv_no_loss(h, oracle) == []
        killed = 0
        for d in h.raw_disks:
            try:
                fi = d.read_version("soak", "c0/victim")
            except Exception:  # noqa: BLE001 - no copy here
                continue
            part = os.path.join(str(tmp_path), d.endpoint(), "soak",
                                "c0/victim", fi.data_dir, "part.1")
            if os.path.exists(part):
                os.remove(part)
                killed += 1
        assert killed > spec.parity
        violations = inv_no_loss(h, oracle)
        assert violations and "c0/victim" in violations[0]
    finally:
        h.close()


def test_expiry_invariant_detects_unfreed_shards(tmp_path):
    """An 'expired' object whose part files survive must be flagged:
    expiry has to reclaim bytes, not just hide keys."""
    h = ScenarioHarness(str(tmp_path), _mini_spec())
    try:
        body = b"\x11" * 50_000
        st, _, _ = h.request("PUT", f"/{BUCKET_EXP}/exp/c0/e0", body=body)
        assert st == 200
        oracle = scenarios._Oracle()
        oracle.expiring[(BUCKET_EXP, "exp/c0/e0")] = body
        violations = inv_expiry(h, oracle)
        # Not expired yet: both the 200 GET and the on-disk part files
        # must fire.
        assert any("want 404" in v for v in violations)
        assert any("part file" in v for v in violations)
        h.scanner.scan_cycle()
        assert inv_expiry(h, oracle) == []
    finally:
        h.close()


def test_admission_conservation_identity_and_detection():
    """The conservation identity holds on a real governor under grant /
    queue-full-reject traffic, and a tampered counter is detected."""
    from minio_tpu.pipeline.admission import (
        AdmissionConfig,
        AdmissionGovernor,
    )
    from minio_tpu.utils.errors import ErrOperationTimedOut

    gov = AdmissionGovernor(AdmissionConfig(
        slots=1, per_client_cap=1, max_queue=0, deadline_s=0.05))
    gov.acquire("a")
    with pytest.raises(ErrOperationTimedOut):
        gov.acquire("b")  # queue-full fast reject
    gov.release("a")
    s = gov.snapshot()
    assert s["arrivals_total"] == 2
    assert (s["admitted_total"] + s["rejected_queue_full"]
            + s["rejected_deadline"] - s["late_grant_returns"]) == 2
    fake_h = types.SimpleNamespace(governor=gov, read_governor=gov)
    assert inv_admission_conserved(fake_h, None) == []
    gov.admitted_total += 1  # a leaked grant
    violations = inv_admission_conserved(fake_h, None)
    assert violations and "admission" in violations[0]


# ---------------------------------------------------------------------------
# faults admin: active listing with remaining-trigger counts


def test_faults_admin_active_listing(tmp_path):
    """GET /minio/admin/v3/faults?active=true lists currently-armed
    schedules with per-spec fired and remaining-trigger counts — the
    mid-run fault-plane verification."""
    h = ScenarioHarness(str(tmp_path), _mini_spec())
    try:
        faults.arm("soak-d1", {"seed": 3, "specs": [
            {"kind": "error", "calls": [4, 5, 6],
             "error": "ErrDiskNotFound"},
            {"kind": "latency", "probability": 0.5, "latency_s": 0.001},
        ]})
        st, _, raw = h.request("GET", "/minio/admin/v3/faults",
                               query=[("active", "true")])
        assert st == 200
        armed = json.loads(raw)["armed"]
        assert "soak-d1" in armed
        specs = armed["soak-d1"]["specs"]
        assert specs[0]["remaining"] == 3   # scripted: finite countdown
        assert specs[1]["remaining"] is None  # probabilistic: unbounded
        # Burn calls through the armed disk; remaining drains.
        disk = h.raw_disks[1]
        fd = faults.FaultDisk(disk)  # registry-driven by endpoint
        for _ in range(10):
            try:
                fd.stat_vol("soak")
            except Exception:  # noqa: BLE001 - injected, expected
                pass
        st, _, raw = h.request("GET", "/minio/admin/v3/faults",
                               query=[("active", "true")])
        specs = json.loads(raw)["armed"]["soak-d1"]["specs"]
        assert specs[0]["remaining"] == 0
        assert specs[0]["fired"] == 3
        # Disarmed schedules drop from the active view but stay in the
        # unfiltered one until replaced.
        faults.disarm("soak-d1")
        st, _, raw = h.request("GET", "/minio/admin/v3/faults",
                               query=[("active", "true")])
        assert json.loads(raw)["armed"] == {}
    finally:
        faults.disarm()
        h.close()


def test_heal_replicates_a_delete_marker(tmp_path):
    """Regression (found by the soak's MRF-dry invariant): healing a
    delete-marker version must replicate the marker to the disks its
    write fan-out missed — not crash building a 0x0 erasure codec and
    leave the marker permanently un-replicable."""
    from minio_tpu.object.erasure_objects import ErasureObjects
    from minio_tpu.object.types import ObjectOptions
    from minio_tpu.storage.local import LocalStorage

    disks = [LocalStorage(str(tmp_path / f"d{i}"), endpoint=f"d{i}")
             for i in range(4)]
    for d in disks:
        d.make_vol(".minio.sys")
    es = ErasureObjects(disks)
    es.make_bucket("vb")
    body = b"\x42" * 200_000
    es.put_object("vb", "doc", io.BytesIO(body), len(body),
                  ObjectOptions(versioned=True))
    # One disk misses the marker write (offline during the delete).
    es.disks[3] = None
    oi = es.delete_object("vb", "doc", ObjectOptions(versioned=True))
    assert oi.delete_marker and oi.version_id
    es.disks[3] = disks[3]
    with pytest.raises(Exception):
        disks[3].read_version("vb", "doc", oi.version_id)
    res = es.heal_object("vb", "doc", oi.version_id)
    assert disks[3].endpoint() in res["healed"], res
    fi = disks[3].read_version("vb", "doc", oi.version_id)
    assert fi.deleted, "healed marker lost its tombstone bit"


# ---------------------------------------------------------------------------
# satellite: versioned overwrite + delete marker + lifecycle expiry
# UNDER injected drive faults


def test_versioned_lifecycle_under_drive_faults(tmp_path):
    """Lifecycle was only ever proven on healthy disks. With a seeded
    error/latency schedule armed on one drive: (a) no version loss at
    quorum — every surviving version reads back byte-identical,
    (b) the delete marker hides the key, (c) the noncurrent-expired
    version is GONE and its shard part files are actually freed."""
    from minio_tpu.object.types import ObjectOptions

    spec = _mini_spec()
    h = ScenarioHarness(str(tmp_path), spec)
    sched = None
    try:
        day_ns = 86_400 * 10**9
        now = __import__("time").time_ns()
        v1 = b"\x01v1" * 30_000
        v2 = b"\x02v2" * 30_000
        # Backdated versions via the object layer (mod_time is not an
        # S3-API surface), THROUGH the wrapped (faultable) disks.
        oi1 = h.ol.put_object(
            BUCKET_VER, "doc", io.BytesIO(v1), len(v1),
            ObjectOptions(versioned=True, mod_time_ns=now - 3 * day_ns),
        )
        oi2 = h.ol.put_object(
            BUCKET_VER, "doc", io.BytesIO(v2), len(v2),
            ObjectOptions(versioned=True, mod_time_ns=now - 1 * day_ns),
        )
        # Noncurrent expiry after 1 day on the versioned bucket.
        lc = (b'<LifecycleConfiguration><Rule><ID>nc</ID>'
              b'<Status>Enabled</Status><Filter><Prefix></Prefix>'
              b'</Filter><NoncurrentVersionExpiration>'
              b'<NoncurrentDays>1</NoncurrentDays>'
              b'</NoncurrentVersionExpiration></Rule>'
              b'</LifecycleConfiguration>')
        st, _, _ = h.request("PUT", f"/{BUCKET_VER}",
                             query=[("lifecycle", "")], body=lc)
        assert st == 200

        # NOW arm the chaos: seeded error + latency on one drive.
        fd = h.fault_disks[1]
        sched = fd.arm({"seed": 77, "specs": [
            {"kind": "latency", "probability": 0.1, "latency_s": 0.01},
            {"kind": "error", "probability": 0.06,
             "error": "ErrDiskNotFound"},
        ]})

        # Delete marker lands under faults (versioned DELETE).
        st, _, _ = h.request("DELETE", f"/{BUCKET_VER}/doc")
        assert st in (200, 204)
        # No version loss at quorum BEFORE the sweep: both versions
        # read back byte-identical through the fault schedule.
        for vid, want in ((oi1.version_id, v1), (oi2.version_id, v2)):
            st, _, got = h.request("GET", f"/{BUCKET_VER}/doc",
                                   query=[("versionId", vid)])
            assert st == 200 and got == want, f"version {vid} lost"
        # Plain GET: the marker hides the key.
        st, _, _ = h.request("GET", f"/{BUCKET_VER}/doc")
        assert st == 404

        # The sweep runs UNDER the same fault schedule. v1 became
        # noncurrent 1 day ago (v2's mod time): expired. v2 became
        # noncurrent when the marker landed (now): survives.
        h.scanner.scan_cycle()

        st, _, got = h.request("GET", f"/{BUCKET_VER}/doc",
                               query=[("versionId", oi2.version_id)])
        assert st == 200 and got == v2, "surviving version lost"
        st, _, _ = h.request("GET", f"/{BUCKET_VER}/doc",
                             query=[("versionId", oi1.version_id)])
        assert st == 404, "expired version still readable"
        # The expired version's shard files are actually freed: no
        # disk holds more than one data dir for the key.
        for d in h.raw_disks:
            obj_dir = os.path.join(str(tmp_path), d.endpoint(),
                                   BUCKET_VER, "doc")
            if not os.path.isdir(obj_dir):
                continue
            data_dirs = [e for e in os.listdir(obj_dir)
                         if os.path.isdir(os.path.join(obj_dir, e))]
            assert len(data_dirs) <= 1, (
                f"{d.endpoint()}: expired version's shards not freed: "
                f"{data_dirs}")
    finally:
        if sched is not None:
            sched.disarm()
        h.close()


# ---------------------------------------------------------------------------
# ISSUE 17: bounded hang faults, zipfian load generation, stall-bound /
# mesh-STATS invariants, and the paced heal storm


def test_default_plan_arms_bounded_hang_without_op_filter():
    """The default soak plan carries a hang-kind fault: bounded
    (hold_s = 2 x op_deadline_s, an NFS-blip shape the detach/hedge
    machinery must ride out) and armed on the shared call counter, not
    an op filter — FaultSpec.matches() checks the ops filter FIRST, so
    an op-filtered scripted hang could burn its call numbers on ops it
    never fires for."""
    spec = _mini_spec(fault_drives=2, disks=8, parity=4)
    eps = [f"soak-d{i}" for i in range(8)]
    plan = build_fault_plan(spec, eps)
    hangs = [s for _, sch in plan["drive_schedules"]
             for s in sch["specs"] if s["kind"] == "hang"]
    assert len(hangs) == spec.hang_drives == 1
    h = hangs[0]
    assert h["hold_s"] == 2 * spec.op_deadline_s
    assert not h.get("ops"), "hang must fire on the shared call counter"
    assert h["calls"] == sorted(h["calls"]) and len(h["calls"]) == 2
    # hang_drives=0 disarms the hang plane entirely.
    plan0 = build_fault_plan(
        _mini_spec(fault_drives=2, disks=8, parity=4, hang_drives=0), eps)
    assert not any(s["kind"] == "hang" for _, sch in plan0["drive_schedules"]
                   for s in sch["specs"])


def test_zipf_draws_leave_legacy_streams_unchanged():
    """Plan-compat proof: the zipfian hot-GET draws come from a DERIVED
    rng, so disabling them (hot_keys=0) changes nothing but the `hot`
    tags — every pre-existing plan field stays byte-identical and old
    replay seeds keep reproducing their exact op streams."""
    a = [dict(o) for o in scenarios.client_stream(_mini_spec(hot_keys=16), 0)]
    b = [dict(o) for o in scenarios.client_stream(_mini_spec(hot_keys=0), 0)]
    assert any("hot" in o for o in a) or True  # tags optional per seed
    for o in a:
        o.pop("hot", None)
    assert a == b


def test_zipf_rank_deterministic_and_skewed():
    rng = random.Random(7)
    seq = [scenarios._zipf_rank(rng, 16, 1.1) for _ in range(600)]
    rng2 = random.Random(7)
    assert seq == [scenarios._zipf_rank(rng2, 16, 1.1) for _ in range(600)]
    counts = [seq.count(r) for r in range(16)]
    assert counts[0] == max(counts), "rank 0 must be the hottest key"
    assert counts[0] > 3 * max(1, counts[15]), "zipf tail not skewed"
    assert all(0 <= r < 16 for r in seq)


def test_bounded_hang_stalls_then_proceeds():
    """hold_s bounds the stall: the op blocks for the hold, then
    PROCEEDS normally — whether the caller already detached at its
    deadline is the tolerance machinery's decision, not the fault's."""
    from minio_tpu.faults.injector import FaultSchedule

    sched = FaultSchedule([{"kind": "hang", "hold_s": 0.05, "calls": [1]}],
                          seed=3)
    t0 = time.monotonic()
    assert sched.apply("stat_vol") is None
    assert time.monotonic() - t0 >= 0.04, "bounded hang did not stall"
    assert sched.fired == 1
    t0 = time.monotonic()
    assert sched.apply("stat_vol") is None  # call 2: clean and fast
    assert time.monotonic() - t0 < 0.04
    # Round-trips through the plan wire format.
    d = sched.specs[0].to_dict()
    assert d["hold_s"] == 0.05
    from minio_tpu.faults.injector import FaultSpec

    assert FaultSpec.from_dict(d).hold_s == 0.05


def test_legacy_hang_wedges_until_disarm():
    """hold_s=0 keeps the legacy wedge: the op blocks until disarm
    (or MAX_HANG_S) — the shape diskcheck's per-op deadline exists
    for."""
    from minio_tpu.faults.injector import FaultSchedule

    sched = FaultSchedule([{"kind": "hang", "calls": [1]}], seed=3)
    out = {}

    def call():
        out["r"] = sched.apply("read_file")

    t = threading.Thread(target=call)
    t.start()
    time.sleep(0.15)
    assert t.is_alive(), "legacy hang must wedge until released"
    sched.disarm()
    t.join(5.0)
    assert not t.is_alive() and out["r"] is None


def test_stall_bound_invariant_detects_and_noops():
    board = scenarios._LatencyBoard()
    board.note("get", 0.5)
    h = types.SimpleNamespace(latency=board, stall_bound_s=1.0)
    assert scenarios.inv_stall_bounded(h, None) == []
    board.note("multipart", 1.7)
    violations = scenarios.inv_stall_bounded(h, None)
    assert violations and "multipart" in violations[0]
    # Harnesses that never attach a board (unit tests) are a no-op.
    assert scenarios.inv_stall_bounded(types.SimpleNamespace(), None) == []


def test_mesh_stats_invariant_detects_dispatch_batch_skew(monkeypatch):
    from minio_tpu.parallel.metrics import STATS

    base = dict(STATS)
    h = types.SimpleNamespace(mesh_stats0=dict(STATS))
    # Host-einsum engine: always a no-op.
    monkeypatch.delenv("MTPU_ENCODE_ENGINE", raising=False)
    assert scenarios.inv_mesh_stats_clean(h, None) == []
    monkeypatch.setenv("MTPU_ENCODE_ENGINE", "mesh")
    try:
        assert scenarios.inv_mesh_stats_clean(h, None) == []
        STATS["mesh_dispatches_total"] += 1
        violations = scenarios.inv_mesh_stats_clean(h, None)
        assert violations and "dispatches" in violations[0]
        STATS["mesh_batches_total"] += 1
        assert scenarios.inv_mesh_stats_clean(h, None) == []
        # Retraces only count once warmed (the subprocess gate's second
        # run sets MTPU_MESH_WARM=1).
        STATS["mesh_retraces_total"] += 1
        assert scenarios.inv_mesh_stats_clean(h, None) == []
        monkeypatch.setenv("MTPU_MESH_WARM", "1")
        violations = scenarios.inv_mesh_stats_clean(h, None)
        assert violations and "retrace" in violations[0]
    finally:
        STATS.update(base)


def test_latency_board_quantiles_and_over():
    board = scenarios._LatencyBoard()
    for i in range(100):
        board.note("get", (i + 1) / 1000.0)
    board.note("put", 2.0)
    s = board.summary()
    assert s["get"]["count"] == 100
    assert s["get"]["p50_s"] <= s["get"]["p99_s"] <= s["get"]["max_s"]
    assert s["all"]["count"] == 101 and s["all"]["max_s"] == 2.0
    over = board.over(0.95)
    assert over == [("put", 2.0)]


def test_span_p99_extraction_from_histogram():
    from minio_tpu.observability.metrics import Metrics

    m = Metrics()
    for _ in range(10):
        m.observe("span_seconds", 0.003, kind="disk")
    for _ in range(90):     # a kind's ops are summed
        m.observe("span_seconds", 0.7, kind="disk", op="put_object")
    for _ in range(50):
        m.observe("span_seconds", 0.002, kind="fanout")
    p = scenarios._span_p99s(m)
    assert 0.5 <= p["disk"] <= 1.0, p
    assert p["fanout"] <= 0.005, p


def test_mini_hot_object_scenario(tmp_path):
    """Tier-1-sized hot-object chaos run (ISSUE 19): zipfian readers
    through the hot tier while overwrite / versioned-delete / heal /
    drive-fault planes mutate the same sketch-hot keys, then the
    leader-crash proof and the full drain gate. Passing means: zero
    stale hits, zero corrupt bytes, every doomed-decode GET failed
    clean, the tier actually served (hits or coalesced > 0), and
    hot_object_coherent held at drain."""
    spec = _mini_spec(seed=11, hot_keys=6)
    art = scenarios.run_hot_object(
        spec, str(tmp_path), readers=3, reader_ops=8, overwrites=5,
        ver_keys=2, ver_cycles=2, heal_kills=1, crash_gets=4,
    )
    assert art["passed"], json.dumps(
        {k: v for k, v in art.items() if k != "spec"}, indent=2)
    assert art["counts"]["stale_hits"] == 0
    assert art["counts"]["reads_ok"] > 0
    tier = art["tier"]
    assert tier["hits_total"] + tier["coalesced_total"] > 0
    assert tier["leader_crashes_total"] >= 1
    # Every crash-phase GET failed clean: non-200 or severed, never an
    # intact 200 (there were no bytes below quorum to build one from).
    assert art["crash_outcomes"]
    assert not any(o == "intact-200" for o in art["crash_outcomes"])
    # The tier's served-byte ledger classification moved.
    assert sum(art["served_bytes"].values()) > 0
    # Teardown restored the knobs and dropped the pinned-threshold tier.
    from minio_tpu.object import readtier

    assert readtier._tier is None


def test_hot_coherent_invariant_detects_poisoned_cache(tmp_path):
    """The hot_object_coherent checker DETECTS divergence, not just
    passes on good runs: poison a cached decoded block behind the
    tier's back and the invariant must flag the key."""
    from minio_tpu.object import readtier

    saved = {k: os.environ.get(k)
             for k in ("MTPU_READTIER", "MTPU_READTIER_HOT_BYTES")}
    os.environ["MTPU_READTIER"] = "on"
    os.environ["MTPU_READTIER_HOT_BYTES"] = "1"
    readtier.reset()
    h = ScenarioHarness(str(tmp_path), _mini_spec(hot_keys=2))
    try:
        key = sorted(h.hot_bodies)[0]
        # First GET marks the key tier-hot and leads the caching
        # decode; the invariant passes while the cache is honest.
        st, _, got = h.request("GET", f"/{scenarios.BUCKET}/{key}")
        assert st == 200 and got == h.hot_bodies[key]
        assert scenarios.inv_hot_object_coherent(h, None) == []
        t = readtier.tier()
        with t._mu:
            poisoned = 0
            for ck, block in t._blocks.items():
                if ck[0] == scenarios.BUCKET and ck[1] == key:
                    block[0] ^= 0xFF
                    poisoned += 1
        assert poisoned, "the leading GET cached nothing"
        violations = scenarios.inv_hot_object_coherent(h, None)
        assert violations and any("diverges" in v for v in violations), \
            violations
    finally:
        h.close()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        readtier.reset()


def test_mini_heal_storm_paces_drains_and_restores(tmp_path):
    """Tier-1-sized heal storm: dead drive + MRF storm under zipfian
    foreground load with the pacer armed — backlog dry, victim
    restored byte-identical, ledger ratio inside the dense-RS bounds,
    every heal through the pace plane."""
    spec = _mini_spec(hot_keys=0)
    art = scenarios.run_heal_storm(spec, str(tmp_path), storm_objects=6,
                                   fg_clients=2, fg_ops=8,
                                   payload=32 << 10)
    assert art["passed"], json.dumps(
        {k: v for k, v in art.items() if k != "spec"}, indent=2)
    assert art["mrf_left"] == 0
    assert art["victim_restored"] == 6
    assert art["pacer"]["grants_total"] >= 6
    k, m = spec.disks - spec.parity, spec.parity
    assert art["heal_ratio"]["final"] >= (k / m) * 0.98
    # Teardown left no process pacer behind.
    from minio_tpu.background import healpace

    assert healpace.installed() is None


def test_mini_heal_storm_msr_repair_plane(tmp_path):
    """Tier-1-sized ISSUE 20 gate: the mini storm forced onto the
    regenerating codec (msr-pm at 2+2, clay arm, α=4) must drain with
    the heal disk-read ratio at or under the 4.5 acceptance ceiling at
    every sample — the repair plane reads (n-1)/m = 1.5 bytes per byte
    healed where dense reads k = 2."""
    spec = _mini_spec(hot_keys=0)
    art = scenarios.run_heal_storm(spec, str(tmp_path), storm_objects=6,
                                   fg_clients=2, fg_ops=8,
                                   payload=32 << 10, codec="msr-pm",
                                   repair_ceiling=4.5)
    assert art["passed"], json.dumps(
        {k: v for k, v in art.items() if k != "spec"}, indent=2)
    assert art["codec"] == "msr-pm"
    assert art["mrf_left"] == 0
    assert art["victim_restored"] == 6
    assert art["heal_ratio"]["final"] <= 4.5, art["heal_ratio"]
    k, m = spec.disks - spec.parity, spec.parity
    # Strictly under the dense k/1 = 2.0 economics: ~1.5 proves the
    # β-slice reads happened rather than a silent dense fallback.
    assert art["heal_ratio"]["final"] <= 1.6, art["heal_ratio"]
    assert art["heal_ratio"]["final"] >= (k / m) * 0.98
