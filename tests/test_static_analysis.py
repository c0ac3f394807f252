"""Tier-1 gate for the analysis plane (tools/analysis):

- the FULL repo scan must report zero findings beyond baseline.json —
  a new unwaived finding anywhere in the scanned tree fails CI;
- every lint rule must still FIRE on its positive fixture and stay
  SILENT on its negative fixture (falsifiability: a rule that stops
  detecting its bug class fails here, not in production);
- baseline and annotation waiver machinery round-trips;
- the runtime lock-order checker detects a seeded A->B / B->A cycle,
  tolerates reentrant RLocks and consistent orders, reports hold-time
  outliers, and keeps threading.Condition working while armed.
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from tools.analysis import engine, lockgraph

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "analysis_fixtures")
REPO = engine.repo_root()


def _scan_fixture(name: str) -> engine.Report:
    return engine.run(
        paths=[os.path.join(FIXTURES, name)],
        force_all_rules=True,
        use_baseline=False,
    )


def _rule_findings(report: engine.Report, rule: str) -> list:
    return [f for f in report.findings if f.rule == rule]


# --- the gate itself ---

def test_repo_scan_is_clean():
    """THE tier-1 gate: zero findings beyond baseline.json. If this
    fails, either fix the new finding, annotate it with a reasoned
    `# <rule>-ok:` comment, or (for an accepted pre-existing issue)
    pin it via `python -m tools.analysis --write-baseline` — see
    docs/ANALYSIS.md for the decision guide."""
    report = engine.run()
    assert not report.parse_errors, report.parse_errors
    assert report.files_scanned > 100  # the scan actually covered the repo
    new = [f.to_dict() for f in report.new]
    assert new == [], (
        f"{len(new)} unwaived analysis finding(s):\n"
        + "\n".join(
            f"  {f['rule']} {f['path']}:{f['line']} {f['message']}"
            for f in new
        )
    )


def test_the_core_count_fork_and_what_went_with_it_stay_out():
    """One path per job (ISSUE 33): the fork on the host's core count,
    the Pallas RS kernel with its knob, and the engine selector's shim
    in erasure/codec.py are gone from the product tree and its docs.
    The names are spelled in halves so that a grep of tests/ for them
    finds nothing either."""
    import re

    gone = re.compile("|".join((
        "SINGLE" + "_CORE",
        "MTPU_RS" + "_KERNEL",
        "rs" + "_pallas",
        r"\b_select" + "_engine\\b",
        "_DEVICE_SHARD" + "_THRESHOLD",
    )))
    paths = [os.path.join(REPO, "README.md")]
    for top in ("minio_tpu", "docs"):
        for root, _dirs, files in os.walk(os.path.join(REPO, top)):
            paths += [os.path.join(root, f) for f in files
                      if f.endswith((".py", ".md", ".c", ".h"))]
    assert len(paths) > 100
    hits = []
    for path in paths:
        with open(path, encoding="utf-8", errors="replace") as fh:
            hits += [f"{os.path.relpath(path, REPO)}:{n}: {line.strip()}"
                     for n, line in enumerate(fh, 1) if gone.search(line)]
    assert hits == [], "\n".join(hits)


def test_self_check_scans_the_analyzer():
    paths = engine.discover(REPO)
    assert "tools/analysis/engine.py" in paths
    assert "tools/analysis/lockgraph.py" in paths
    assert "minio_tpu/erasure/streaming.py" in paths
    assert "bench.py" in paths
    assert not any(p.startswith("tests") for p in paths)


# --- per-rule falsifiability: positive fires, negative is silent ---

RULE_CASES = [
    ("copy-lint", "copy_pos.py", "copy_neg.py", 6),
    ("lock-lint", "lock_pos.py", "lock_neg.py", 4),
    ("pool-lint", "pool_pos.py", "pool_neg.py", 1),
    ("pool-lint", "shmpool_pos.py", "shmpool_neg.py", 1),
    ("pool-lint", "readpool_pos.py", "readpool_neg.py", 2),
    ("jax-lint", "jax_pos.py", "jax_neg.py", 5),
    ("jax-lint", "readjax_pos.py", "readjax_neg.py", 1),
    ("except-lint", "except_pos.py", "except_neg.py", 2),
    ("metrics-lint", "metrics_pos.py", "metrics_neg.py", 3),
    # Dead-series direction (ISSUE 14): catalog entry with no write
    # site anywhere fires; literal/f-string/table evidence is silent.
    ("metrics-lint", "metricsdead_pos.py", "metricsdead_neg.py", 1),
    # Dataflow rules (ISSUE 13).
    ("lifetime-lint", "lifetime_pos.py", "lifetime_neg.py", 5),
    ("shm-lint", "shm_pos.py", "shm_neg.py", 4),
    ("guardedby-lint", "guardedby_pos.py", "guardedby_neg.py", 6),
    ("knob-lint", "knob_pos.py", "knob_neg.py", 6),
]


@pytest.mark.parametrize("rule,pos,neg,min_pos",
                         RULE_CASES, ids=[c[0] for c in RULE_CASES])
def test_rule_fires_on_violation_and_not_on_clean(rule, pos, neg,
                                                  min_pos):
    pos_found = _rule_findings(_scan_fixture(pos), rule)
    assert len(pos_found) >= min_pos, (
        f"{rule} missed its injected violations: "
        f"{[f.to_dict() for f in pos_found]}"
    )
    neg_found = _rule_findings(_scan_fixture(neg), rule)
    assert neg_found == [], (
        f"{rule} false-positives on the clean fixture: "
        f"{[f.to_dict() for f in neg_found]}"
    )


def test_copy_lint_validates_annotation_labels():
    """A copy-ok label that feeds no copy_add() is itself a finding —
    stale labels cannot silently un-count a copy."""
    found = _rule_findings(_scan_fixture("copy_pos.py"), "copy-lint")
    assert any("no.such.counter" in f.message for f in found), (
        [f.message for f in found]
    )


def test_baseline_waives_by_fingerprint_not_line(tmp_path):
    raw = _scan_fixture("copy_pos.py")
    assert raw.new
    baseline = {
        f.fingerprint: {"fingerprint": f.fingerprint}
        for f in raw.findings
    }
    waived = engine.run(
        paths=[os.path.join(FIXTURES, "copy_pos.py")],
        force_all_rules=True,
        baseline=baseline,
    )
    assert waived.new == []
    assert len(waived.waived) == len(raw.findings)
    # write/load round-trip
    path = tmp_path / "baseline.json"
    n = engine.write_baseline(raw, str(path))
    assert n == len(raw.findings)
    loaded = engine.load_baseline(str(path))
    assert set(loaded) == set(baseline)


def test_injected_violation_fails_the_gate(tmp_path):
    """End to end: a fresh violation in a (copied) hot-path module is
    NEW against the real baseline — exactly what CI would report."""
    victim = tmp_path / "streaming_violation.py"
    victim.write_text(
        "import threading\n"
        "import time\n"
        "_mu = threading.Lock()\n"
        "def bad(arr):\n"
        "    with _mu:\n"
        "        time.sleep(1)\n"
        "    return arr.tobytes()\n"
    )
    report = engine.run(paths=[str(victim)], force_all_rules=True)
    rules = {f.rule for f in report.new}
    assert "lock-lint" in rules and "copy-lint" in rules, (
        [f.to_dict() for f in report.new]
    )


def test_cli_exits_zero_and_emits_json():
    r = subprocess.run(
        [sys.executable, "-m", "tools.analysis", "--quiet"],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    out = json.loads(r.stdout)
    assert out["counts"]["new"] == 0
    assert out["wall_time_s"] > 0


# --- dataflow rules: the ISSUE 13 acceptance proofs ---

def test_shm_lint_proves_workers_clean_today():
    """The acceptance criterion verbatim: the zero-payload-over-pipe
    invariant HOLDS over pipeline/workers.py as it exists — every
    enc/rec/vfy reply tuple and task message is payload-free."""
    report = engine.run(paths=["minio_tpu/pipeline/workers.py"],
                        rules=["shm-lint"], use_baseline=False, jobs=1)
    assert report.files_scanned == 1
    assert [f.to_dict() for f in report.findings] == []


def test_shm_lint_fires_on_smuggled_strip_view(tmp_path):
    """...and FIRES the moment a reply smuggles a strip view — the
    exact regression the rule exists to block."""
    victim = tmp_path / "workers_smuggled.py"
    victim.write_text(
        "import pickle\n"
        "def _child_loop(strip, out):\n"
        "    reply = ('ok', strip.parity[:1].tobytes(), 0)\n"
        "    pickle.dump(reply, out)\n"
    )
    report = engine.run(paths=[str(victim)], force_all_rules=True,
                        use_baseline=False, jobs=1)
    assert any(f.rule == "shm-lint" for f in report.new), (
        [f.to_dict() for f in report.new]
    )


def test_guardedby_declarations_live_on_real_tree():
    """The five annotated modules carry live declarations (a regex
    regression that silently dropped them would leave the rule
    checking nothing) and scan clean."""
    from tools.analysis import astutil, guardedby_lint

    expect = {
        "minio_tpu/pipeline/admission.py": ("_governor", "_inflight"),
        "minio_tpu/pipeline/workers.py": ("_pool", "_workers"),
        "minio_tpu/storage/diskcheck.py": ("_faulty",),
        "minio_tpu/utils/fanout.py": ("LATE_DROPS", "_extra"),
        "minio_tpu/observability/spans.py": ("_rings", "_slow_store"),
    }
    for rel, names in expect.items():
        with open(os.path.join(REPO, rel), encoding="utf-8") as f:
            ctx = astutil.parse_module(rel, f.read())
        mod, cls, pre = guardedby_lint._collect_decls(ctx)
        declared = set(mod)
        for fields in cls.values():
            declared.update(fields)
        for name in names:
            assert name in declared, (rel, name, sorted(declared))
    report = engine.run(
        paths=list(expect), rules=["guardedby-lint"], jobs=1,
        use_baseline=False,
    )
    assert [f.to_dict() for f in report.new] == []


def test_lifetime_lint_parked_reader_scribble_shape(tmp_path):
    """Seeded regression for the PR8 hazard: a ring-slot view escapes
    into a fan-out thread and the slot is released before the join —
    the scribble window lifetime-lint exists to catch. With the
    deferred-release handshake (release gated on the in-flight
    counter), the same flow is silent."""
    scribble = tmp_path / "parked_reader_pos.py"
    scribble.write_text(
        "from minio_tpu.pipeline.buffers import BufferPool\n"
        "ring_pool = BufferPool(lambda: bytearray(1 << 18))\n"
        "def read_batch(executor, phys):\n"
        "    slot = ring_pool.acquire()\n"
        "    view = memoryview(slot)[:phys]\n"
        "    fut = executor.submit(_readinto, view)\n"
        "    ring_pool.release(slot)  # parked reader still holds view\n"
        "    return fut\n"
        "def _readinto(v):\n"
        "    return len(v)\n"
    )
    report = engine.run(paths=[str(scribble)], force_all_rules=True,
                        use_baseline=False, jobs=1)
    fired = [f for f in report.new if f.rule == "lifetime-lint"]
    assert fired and "thread" in fired[0].message, (
        [f.to_dict() for f in report.new]
    )

    handshake = tmp_path / "parked_reader_neg.py"
    handshake.write_text(
        "import threading\n"
        "from minio_tpu.pipeline.buffers import BufferPool\n"
        "ring_pool = BufferPool(lambda: bytearray(1 << 18))\n"
        "_mu = threading.Lock()\n"
        "_inflight = 0\n"
        "def read_batch(executor, phys):\n"
        "    slot = ring_pool.acquire()\n"
        "    view = memoryview(slot)[:phys]\n"
        "    fut = executor.submit(_readinto, view)\n"
        "    with _mu:\n"
        "        if _inflight == 0:\n"
        "            ring_pool.release(slot)  # deferred handshake\n"
        "    return fut\n"
        "def _readinto(v):\n"
        "    return len(v)\n"
    )
    report = engine.run(paths=[str(handshake)], force_all_rules=True,
                        use_baseline=False, jobs=1)
    assert [f.to_dict() for f in report.new
            if f.rule == "lifetime-lint"] == []


def test_guardedby_reentrant_with_nesting_stays_held(tmp_path):
    """Nested `with` on the same re-entrant lock must not un-hold it
    at the inner exit (hold COUNTS, not a set)."""
    mod = tmp_path / "reentrant.py"
    mod.write_text(
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._mu = threading.RLock()\n"
        "        self._n = 0  # guarded-by: _mu\n"
        "    def reenter(self):\n"
        "        with self._mu:\n"
        "            with self._mu:\n"
        "                self._n += 1\n"
        "            self._n += 1  # outer hold still live\n"
    )
    report = engine.run(paths=[str(mod)], force_all_rules=True,
                        use_baseline=False, jobs=1)
    assert [f.to_dict() for f in report.new
            if f.rule == "guardedby-lint"] == []


def test_guardedby_nested_def_access_reported_once(tmp_path):
    """A guarded access inside a closure is one site — the nested def
    must be walked via the enclosing flow's hook only, not also as a
    top-level function (double-reporting splits one violation across
    two occurrence ordinals)."""
    mod = tmp_path / "nested.py"
    mod.write_text(
        "import threading\n"
        "_mu = threading.Lock()\n"
        "_metrics = None  # guarded-by: _mu\n"
        "def outer():\n"
        "    def inner():\n"
        "        return _metrics\n"
        "    return inner\n"
    )
    report = engine.run(paths=[str(mod)], force_all_rules=True,
                        use_baseline=False, jobs=1)
    gb = [f for f in report.new if f.rule == "guardedby-lint"]
    assert len(gb) == 1, [f.to_dict() for f in gb]


def test_knob_docs_match_is_whole_word(tmp_path):
    """docs naming MTPU_TRACE_SLOW_MS must not count as documenting a
    hypothetical MTPU_TRACE_SLOW — substring containment would pass
    any prefix of a longer documented knob."""
    from tools.analysis import astutil, knob_lint

    src = "import os\nX = os.environ.get('MTPU_TRACE_SLOW', '1')\n"
    ctx = astutil.parse_module("minio_tpu/fake.py", src)
    found = list(knob_lint.RULE.check(ctx))
    assert any("documented nowhere" in f.message for f in found), (
        [f.message for f in found]
    )


def test_changed_since_includes_untracked_files():
    """--since is the local-iteration mode: the file being iterated on
    is often brand-new (untracked), and skipping it would report clean
    for a file that was never scanned."""
    import uuid

    name = f"tools/analysis/_since_probe_{uuid.uuid4().hex[:8]}.py"
    path = os.path.join(REPO, name)
    with open(path, "w", encoding="utf-8") as f:
        f.write("x = 1\n")
    try:
        assert name in engine.changed_since("HEAD")
    finally:
        os.remove(path)


def test_injected_dataflow_violations_fail_the_gate(tmp_path):
    """End to end for the new rules: lifetime + guardedby + knob
    violations in a fresh module are NEW against the real baseline."""
    victim = tmp_path / "hotpath_violation.py"
    victim.write_text(
        "import os\n"
        "import threading\n"
        "from minio_tpu.pipeline.buffers import BufferPool\n"
        "pool = BufferPool(lambda: bytearray(64))\n"
        "_mu = threading.Lock()\n"
        "_state = {}  # guarded-by: _mu\n"
        "KNOB = os.environ.get('MTPU_FIXTURE_MISSING_KNOB')\n"
        "def bad():\n"
        "    buf = pool.acquire()\n"
        "    pool.release(buf)\n"
        "    _state['x'] = len(buf)\n"
    )
    report = engine.run(paths=[str(victim)], force_all_rules=True)
    rules = {f.rule for f in report.new}
    assert {"lifetime-lint", "guardedby-lint", "knob-lint"} <= rules, (
        [f.to_dict() for f in report.new]
    )


# --- engine plumbing: parallel scan, --since, --rule, report schema ---

def test_parallel_scan_matches_serial():
    """The files-per-worker parallel scan returns the identical
    finding stream (fingerprints, order, parse errors) — wall time is
    the only thing it may change."""
    serial = engine.run(use_baseline=False, jobs=1)
    parallel = engine.run(use_baseline=False, jobs=2)
    assert parallel.files_scanned == serial.files_scanned
    assert ([f.fingerprint for f in parallel.findings]
            == [f.fingerprint for f in serial.findings])
    assert parallel.parse_errors == serial.parse_errors


def test_rule_filter_cli():
    r = subprocess.run(
        [sys.executable, "-m", "tools.analysis", "--rule", "knob-lint",
         "--quiet", "minio_tpu/pipeline/workers.py"],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    # Unknown rule names are an explicit error, not a silent no-op.
    r = subprocess.run(
        [sys.executable, "-m", "tools.analysis", "--rule", "no-such"],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    assert r.returncode == 2
    assert "unknown rule" in r.stderr


def test_since_mode_cli():
    """--since HEAD scans only changed files (possibly none) and still
    exits by the finding count."""
    r = subprocess.run(
        [sys.executable, "-m", "tools.analysis", "--since", "HEAD",
         "--quiet"],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    out = json.loads(r.stdout)
    assert out["counts"]["new"] == 0


REPORT_SCHEMA_KEYS = {
    "version", "files_scanned", "wall_time_s", "baseline_size",
    "counts", "by_rule", "new_findings", "waived_findings",
    "parse_errors",
}

FINDING_SCHEMA_KEYS = {
    "rule", "path", "line", "col", "scope", "message", "snippet",
    "occurrence", "fingerprint", "waived_by",
}


def test_json_report_schema_is_pinned():
    """The --json report is a consumed interface (CI, bench, dashboards
    that parse new_findings): its key set is pinned here so a schema
    change is a deliberate diff, not an accident."""
    r = subprocess.run(
        [sys.executable, "-m", "tools.analysis", "--json",
         "tests/analysis_fixtures/knob_pos.py", "--all-rules",
         "--no-baseline"],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    assert r.returncode == 1, r.stdout + r.stderr  # findings exist
    out = json.loads(r.stdout)
    assert set(out) == REPORT_SCHEMA_KEYS, sorted(out)
    assert out["version"] == 1
    assert set(out["counts"]) == {"total", "new", "waived"}
    assert out["new_findings"], "fixture must produce findings"
    for f in out["new_findings"]:
        assert set(f) == FINDING_SCHEMA_KEYS, sorted(f)


# --- lockgraph: the runtime checker ---

@pytest.fixture
def armed_lockgraph():
    lockgraph.reset()
    lockgraph.enable()
    try:
        yield lockgraph
    finally:
        lockgraph.disable()
        lockgraph.reset()


def test_lockgraph_detects_seeded_ab_ba_cycle(armed_lockgraph):
    """The canonical deadlock seed: thread 1 takes A then B, thread 2
    takes B then A. No deadlock occurs (a barrier keeps the holds
    disjoint in time) — the GRAPH still convicts the ordering."""
    lock_a = threading.Lock()
    lock_b = threading.Lock()
    gate = threading.Barrier(2, timeout=10)

    def ab():
        with lock_a:
            with lock_b:
                pass
        gate.wait()

    def ba():
        gate.wait()  # strictly after ab's holds: no actual deadlock
        with lock_b:
            with lock_a:
                pass

    t1 = threading.Thread(target=ab)
    t2 = threading.Thread(target=ba)
    t1.start(); t2.start()
    t1.join(10); t2.join(10)
    cycles = lockgraph.GRAPH.cycles()
    assert cycles, lockgraph.report()
    with pytest.raises(AssertionError):
        lockgraph.assert_no_cycles()


def test_lockgraph_consistent_order_is_clean(armed_lockgraph):
    lock_a = threading.Lock()
    lock_b = threading.Lock()

    def ab():
        for _ in range(50):
            with lock_a:
                with lock_b:
                    pass

    ts = [threading.Thread(target=ab) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(10)
    rep = lockgraph.report()
    assert rep["cycles"] == []
    assert rep["acquisitions"] >= 400
    assert rep["edges"] >= 1  # the A->B edge was observed


def test_lockgraph_reentrant_rlock_no_false_cycle(armed_lockgraph):
    rl = threading.RLock()
    with rl:
        with rl:  # reentrant: same instance, no ordering edge
            pass
    rep = lockgraph.report()
    assert rep["cycles"] == []
    assert rep["self_nesting"] == {}


def test_lockgraph_reports_hold_outliers(armed_lockgraph):
    slow = threading.Lock()
    with slow:
        time.sleep(0.12)
    outliers = lockgraph.GRAPH.hold_outliers(threshold_s=0.1)
    assert outliers and outliers[0]["max_hold_s"] >= 0.1


def test_lockgraph_condition_keeps_working(armed_lockgraph):
    """threading.Condition built while armed uses a CheckedLock RLock
    under the hood — wait/notify must behave and leave no cycles."""
    cv = threading.Condition()
    ready = []

    def waiter():
        with cv:
            while not ready:
                cv.wait(5)

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    with cv:
        ready.append(1)
        cv.notify_all()
    t.join(10)
    assert not t.is_alive()
    assert lockgraph.GRAPH.cycles() == []


def test_lockgraph_enable_disable_roundtrip():
    real_lock_type = type(threading.Lock())
    lockgraph.enable()
    try:
        assert isinstance(threading.Lock(), lockgraph.CheckedLock)
    finally:
        lockgraph.disable()
        lockgraph.reset()
    assert isinstance(threading.Lock(), real_lock_type)


def test_lockgraph_env_knob(monkeypatch):
    monkeypatch.setenv("MTPU_LOCK_CHECK", "0")
    assert lockgraph.enable_from_env() is False
    monkeypatch.setenv("MTPU_LOCK_CHECK", "1")
    try:
        assert lockgraph.enable_from_env() is True
        assert lockgraph.enabled()
    finally:
        lockgraph.disable()
        lockgraph.reset()
