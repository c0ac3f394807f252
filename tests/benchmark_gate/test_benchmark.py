"""The benchmark's own tests: a whole run at a tiny size on the CPU, the
proof that a run leaves nothing behind, the faults that have to turn
`correct` false, and the yardstick's arithmetic.

    JAX_PLATFORMS=cpu python -m pytest tests/benchmark_gate -q

The directory is one of the benchmark's own (`paths` in BENCHMARK.json) and
lies under tests/, so the repo's tier-1 run (`pytest tests/`) collects it.
One file, so that one xdist worker owns its server children. Generous
timeouts, nothing timed. Every server child runs in a process of its own
and on the CPU; the client process it starts never imports jax.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import child as childmod  # noqa: E402
from benchmark.harness import readers, reference, roofline  # noqa: E402
from benchmark.harness import trace_reduce as tr  # noqa: E402
from benchmark.harness.traffic import Op, Window  # noqa: E402

MIB = 1 << 20
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "checks"}

# The tiny heal mix: its warm-up heals one object of every shard rotation
# of its four drives, as `heal2.json`'s does of sixteen; crc32 is linear,
# so the first four `warm/` names fall on two of the four rotations and
# the first eight on all (test_warm_up_covers_every_rotation pins both).
TINY_HEAL = {"kind": "heal", "payload_pool": 2,
             "preload": {"objects": 6, "size": MIB, "clients": 2},
             "warmup_objects": 8, "wipe_drives": 1, "poll_s": 0.2,
             "check_sample": 3}

# python -c body that drives the harness's Python entry on the CPU
DRIVE = """
import sys
sys.path.insert(0, {repo!r})
from benchmark.harness.runner import run_cell
sys.exit(run_cell({wl!r}, {seed}, {seconds}, {trace}, bench_json={bj!r},
                  data_root={dr!r}, require_platform="cpu",
                  extra_env={{"JAX_PLATFORMS": "cpu"}}, fault={fault!r}))
"""


def _tree_hashes(root: str) -> dict[str, str]:
    out = {}
    for dirpath, dirs, files in os.walk(root):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            p = os.path.join(dirpath, name)
            with open(p, "rb") as f:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """A copy of benchmark/ and BENCHMARK.json to which a configuration,
    two traffic mixes, a layer metric and three cells are ADDED as files
    and entries; no file that was there is edited."""
    top = tmp_path_factory.mktemp("bench-copy")
    data = os.path.join(top, "benchmark")
    shutil.copytree(os.path.join(REPO, "benchmark"), data,
                    ignore=shutil.ignore_patterns("__pycache__", ".work"))
    before = _tree_hashes(data)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(data, "configs", "node4-ec2p2-dev1.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "tiny-ec2p2"
    with open(os.path.join(data, "configs", "tiny-ec2p2.json"), "w") as f:
        json.dump(cfg, f)
    mixes = {
        "tinyput": {"kind": "closed_loop", "clients": 2,
                    "ops": [{"op": "PUT", "size": MIB}], "payload_pool": 2,
                    "warmup_ops_per_client": 1, "check_sample": 3},
        "tinymix": {"kind": "closed_loop", "clients": 3, "payload_pool": 2,
                    "ops": [{"op": "PUT", "size": MIB, "weight": 3},
                            {"op": "GET", "weight": 3},
                            {"op": "STAT", "weight": 2},
                            {"op": "DELETE", "weight": 1},
                            {"op": "LIST", "weight": 1}],
                    "preload": {"objects": 4, "size": MIB, "clients": 2},
                    "warmup_ops_per_client": 1, "check_sample": 3},
        "tinyheal": TINY_HEAL,
    }
    for name, mix in mixes.items():
        with open(os.path.join(data, "traffic", name + ".json"), "w") as f:
            json.dump(mix, f)
    with open(os.path.join(data, "layer_metrics", "requests_per_op.json"),
              "w") as f:
        json.dump({"reader": "counter_delta_per_op",
                   "pattern": "s3_requests_total"}, f)
    bench["configs"].append({"name": "tiny-ec2p2", "source": "a test",
                             "file": "benchmark/configs/tiny-ec2p2.json",
                             "reduced": [], "why": "a test"})
    cells = {"tiny-put": "tinyput", "tiny-mix": "tinymix",
             "tiny-heal": "tinyheal"}
    for name, mix in cells.items():
        bench["workloads"].append({"name": name, "config": "tiny-ec2p2",
                                   "traffic": mix, "chips": 1,
                                   "why": "a test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" not in m:
            continue
        if m["name"] == "ops_per_s" or m["name"].endswith(".ops"):
            m["workloads"] += ["tiny-put", "tiny-mix"]
        if m["name"] == "heal_mibps" or m["name"].endswith(".heal"):
            m["workloads"].append("tiny-heal")
    bench["per_layer"].append({
        "name": "requests_per_op", "unit": "1/op", "better": "lower",
        "source": "program_counter", "layer": "S3 front end",
        "moves": "ops_per_s", "workloads": ["tiny-mix"]})
    bj = os.path.join(top, "BENCHMARK.json")
    with open(bj, "w") as f:
        json.dump(bench, f)
    yield {"bench_json": bj, "data_root": data}
    after = _tree_hashes(data)
    assert {k: v for k, v in after.items() if k in before} == before, \
        "a file of the benchmark that was there was edited"


def _drive(copy, wl, seed=7, seconds=2, trace=0, fault=None, **popen):
    code = DRIVE.format(repo=REPO, wl=wl, seed=seed, seconds=seconds,
                        trace=bool(trace), bj=copy["bench_json"],
                        dr=copy["data_root"], fault=fault)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.Popen([sys.executable, "-c", code], env=env,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True, cwd=REPO, **popen)


def _finish(proc, timeout=300):
    out, err = proc.communicate(timeout=timeout)
    return proc.returncode, out, err


def _run_facts(err: str) -> tuple[str, str, int]:
    """marker, tmpfs root and port, from the run's first stderr line."""
    line = next(ln for ln in err.splitlines() if "run marker=" in ln)
    words = dict(w.split("=", 1) for w in line.split() if "=" in w)
    return words["marker"], words["root"], int(words["port"])


def _assert_nothing_left(err: str) -> None:
    marker, root, port = _run_facts(err)
    assert childmod.carriers(marker) == {}
    assert not os.path.exists(root)
    assert not childmod.port_open(port)
    assert "left behind: no process carries" in err


def _last_json(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


# --- a whole run, and what it leaves ---------------------------------------


def test_whole_run_prints_the_contracts_line_and_leaves_nothing(copy):
    rc, out, err = _finish(_drive(copy, "tiny-put", seed=3_000_000_019))
    assert rc == 0, err[-3000:]
    line = _last_json(out)
    assert set(line) == RESULT_KEYS
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"ops_per_s", "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"
    assert all(v == 0 and lim == 0 for v, lim in line["checks"].values())
    # each number compared stands beside its limit at the end of stderr
    tail = err.strip().splitlines()[-len(line["checks"]):]
    assert all(ln.startswith("check ") and "(limit 0)" in ln for ln in tail)
    _assert_nothing_left(err)


def test_added_mix_and_metric_are_found_by_name_and_run(copy):
    """GET, STAT, DELETE and LIST are op kinds the generator knows; the
    mix, the configuration and the layer metric exist only as added
    files."""
    rc, out, err = _finish(_drive(copy, "tiny-mix", seconds=3, trace=1))
    assert rc == 0, err[-3000:]
    line = _last_json(out)
    assert line["correct"] is True, err[-3000:]
    assert line["failed"] == 0, err[-3000:]
    assert line["metrics"]["requests_per_op"]["value"] > 0
    # the tail stands beside the median among the per-layer metrics: a
    # closed loop at capacity has no bound that holds it (PERF.md, PR 32)
    assert {"op_p50_ms.ops", "op_p95_ms.ops"} <= set(line["metrics"])
    # no device plane on the CPU: a roofline or idle share is left out,
    # never reported as 0
    assert "codec_roofline.ops" not in line["metrics"]
    assert "device_idle_share.ops" not in line["metrics"]
    _assert_nothing_left(err)


def test_heal_run_compares_the_rebuilt_shard_files(copy):
    rc, out, err = _finish(_drive(copy, "tiny-heal", seconds=8))
    assert rc == 0, err[-3000:]
    line = _last_json(out)
    assert line["correct"] is True, err[-3000:]
    assert line["metrics"]["heal_mibps"]["value"] > 0
    assert {"heal_failed", "healed_files_missing",
            "healed_files_differ"} <= set(line["checks"])
    _assert_nothing_left(err)


def test_sigterm_mid_window_leaves_nothing(copy):
    proc = _drive(copy, "tiny-put", seconds=60)
    seen = ""
    deadline = time.monotonic() + 240
    # the window is open once the server is up and warm-up is over; the
    # run says neither, so wait for the server and then a little
    while "server up after" not in seen:
        assert time.monotonic() < deadline and proc.poll() is None, seen
        seen += proc.stderr.readline()
    time.sleep(8)
    marker, root, port = _run_facts(seen)
    assert childmod.carriers(marker), "the child should be running"
    proc.send_signal(signal.SIGTERM)
    rc, out, err = _finish(proc, timeout=120)
    assert rc != 0 and out == ""
    _assert_nothing_left(seen + err)


# --- faults under the timed path: `correct` has to come out false ----------


@pytest.mark.parametrize("cell,fault,row", [
    ("tiny-put", "parity_flip", "parity_bytes_differ"),
    ("tiny-put", "digest_flip", "digest_bytes_differ"),
    ("tiny-put", "half_batch", "parity_bytes_differ"),
    ("tiny-heal", "recon_flip", "healed_files_differ"),
])
def test_a_fault_where_the_answer_is_produced_fails_the_run(copy, cell,
                                                            fault, row):
    """An answer altered where it is produced (one bit of parity, of a
    digest, of a rebuilt shard) and half of a batch left out. A state
    returned unchanged and an exchange between chips left out are not
    faults a one-chip object store's cell can have."""
    rc, out, err = _finish(_drive(copy, cell, seconds=6, fault=fault))
    assert rc == 0, err[-3000:]
    line = _last_json(out)
    assert line["correct"] is False
    value, limit = line["checks"][row]
    assert value > limit == 0
    assert f"check {row}: {value} (limit 0)  <-- over" in err
    _assert_nothing_left(err)


# --- the command line ------------------------------------------------------


def test_client_modules_import_without_jax():
    code = ("import sys; sys.path.insert(0, %r)\n"
            "from benchmark.harness import (check, child, client, readers,"
            " reference, roofline, runner, spec, trace_reduce, traffic)\n"
            "assert 'jax' not in sys.modules and 'jaxlib' not in sys.modules"
            % REPO)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stderr


def test_command_line_needs_a_tpu_and_prints_nothing_without():
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "n4dev1-put1m",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert r.returncode != 0 and r.stdout == ""
    assert "never falls back" in r.stderr
    _assert_nothing_left(r.stderr)


def test_command_line_fails_in_a_directory_without_the_program(tmp_path):
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "n4dev1-put1m",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0 and r.stdout == ""


def test_benchmark_json_names_files_that_exist():
    from benchmark.harness.spec import load_cell

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = load_cell(w["name"])
        assert cell.chips == w["chips"]
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert m["reader"]["reader"] in readers.READERS


# --- what the heal cell's warm-up rests on, and where a slice is cued ---------


@pytest.mark.parametrize("case", ["heal2-warm-up",
                                  "heal2-windows-first-objects",
                                  "tiny-heal-warm-up"])
def test_warm_up_covers_every_rotation(case):
    """The program places an object's shards by `crc32(bucket/key) %
    drives` (the reference's hashOrder) and compiles one reconstruct
    function per failure pattern, which is the place of the wiped drives
    in that rotation. The heal cell's warm-up therefore heals one object
    of every rotation, and its window traces nothing; that the window's
    first objects fall on every rotation too is why a warm-up of two
    left it 28 traces (PERF.md, PR 32)."""
    import zlib

    from benchmark.harness.spec import load_cell
    from benchmark.harness.traffic import BUCKET, preload_key, warm_key

    cell = load_cell("n16dev1-heal2")
    make, n, drives = {
        "heal2-warm-up": (warm_key, int(cell.traffic["warmup_objects"]),
                          cell.drives),
        "heal2-windows-first-objects": (preload_key, cell.drives,
                                        cell.drives),
        "tiny-heal-warm-up": (warm_key, TINY_HEAL["warmup_objects"], 4),
    }[case]
    keys = [make(i) for i in range(n)]
    assert {zlib.crc32(f"{BUCKET}/{k}".encode()) % drives
            for k in keys} == set(range(drives))
    # the program's own word, while it has one
    metadata = pytest.importorskip("minio_tpu.object.metadata")
    if hasattr(metadata, "hash_order"):
        assert len({tuple(metadata.hash_order(f"{BUCKET}/{k}", drives))
                    for k in keys}) == drives


def test_every_seed_wipes_a_set_with_as_many_failure_patterns():
    """Two drives half the ring apart have 8 failure patterns over the 16
    rotations where every other pair has 16: a seed that drew them paid
    half the warm-up's traces (16 s of set-up against 33; my chip runs,
    PR 32). Such a set is drawn again; every other seed keeps its draw."""
    from benchmark.harness.traffic import draw_wiped

    seen = set()
    for seed in range(300):
        pair = draw_wiped(reference.rng_for(seed, 1), 16, 2)
        assert pair == draw_wiped(reference.rng_for(seed, 1), 16, 2)
        assert len(pair) == 2 and 1 <= pair[0] < pair[1] <= 16
        assert pair[1] - pair[0] != 8
        plain = sorted(int(d) + 1 for d in reference.rng_for(
            seed, 1).choice(16, size=2, replace=False))
        assert pair == plain or plain[1] - plain[0] == 8
        seen.update(pair)
    assert seen == set(range(1, 17))
    assert len(draw_wiped(reference.rng_for(5, 1), 4, 1)) == 1


@pytest.mark.parametrize("mix,share", [
    ("put10m", 0.5), ("put1m", 0.5), ("heal2", 0.15),
    ({"kind": "closed_loop"}, 0.5),
    ({"kind": "heal", "trace_cue_share": 0.25}, 0.25)])
def test_the_traced_slice_is_cued_at_the_mixs_share_of_the_window(mix, share):
    """Absent, the cue falls at `seconds / 2`, where it fell before the
    field was there: the three PUT cells' files do not name it."""
    from benchmark.harness.runner import trace_cue_at

    if isinstance(mix, str):
        with open(os.path.join(REPO, "benchmark", "traffic",
                               mix + ".json")) as f:
            mix = json.load(f)
    assert ("trace_cue_share" in mix) == (share != 0.5)
    assert trace_cue_at(mix, 1000.0, 30.0) == pytest.approx(
        1000.0 + 30.0 * share)
    assert trace_cue_at(mix, 0.0, 8.0) == pytest.approx(8.0 * share)


# --- the yardstick's arithmetic ----------------------------------------------


def test_reference_agrees_with_the_programs_host_oracle():
    """The reference is a copy kept apart; while the program's own numpy
    oracle is there, the two have to agree."""
    cauchy = pytest.importorskip("minio_tpu.ops.cauchy")
    gf = pytest.importorskip("minio_tpu.ops.gf")
    highwayhash = pytest.importorskip("minio_tpu.ops.highwayhash")
    for mod, name in ((gf, "parity_matrix"), (gf, "gf_matmul_shards_ref"),
                      (cauchy, "cauchy_parity_matrix"),
                      (highwayhash, "hash256_batch")):
        if not hasattr(mod, name):
            pytest.skip(f"the program no longer has {name}")
    for k, m in ((12, 4), (2, 2), (8, 4)):
        assert np.array_equal(reference.parity_matrix("dense-gf8", k, m),
                              gf.parity_matrix(k, m))
        assert np.array_equal(reference.parity_matrix("cauchy-xor", k, m),
                              cauchy.cauchy_parity_matrix(k, m))
    rng = np.random.default_rng(1)
    for length in (3, 17, 33, 64, 100, 2731):
        x = rng.integers(0, 256, (3, 2, length), dtype=np.uint8)
        assert np.array_equal(reference.highwayhash256(x),
                              highwayhash.hash256_batch(x))
    x = rng.integers(0, 256, (12, 999), dtype=np.uint8)
    assert np.array_equal(
        reference.apply_matrix(gf.parity_matrix(12, 4), x),
        gf.gf_matmul_shards_ref(gf.parity_matrix(12, 4), x))


def test_reference_shards_of_an_object():
    body = reference.payload(5, "k", 2 * MIB)
    chunks, digests = reference.expected_shards([body], 12, 4, MIB,
                                                "dense-gf8")
    assert chunks.shape == (1, 2, 16, 87382)
    assert digests.shape == (1, 2, 16, 32)
    flat = chunks[0, 0, :12].reshape(-1)
    assert flat[:MIB].tobytes() == body[:MIB] and not flat[MIB:].any()
    assert reference.payload(5, "k", 64) == reference.payload(5, "k", 64)
    assert reference.payload(2**31 + 5, "k", 64) != reference.payload(
        5, "k", 64)
    with pytest.raises(KeyError):
        reference.parity_matrix("no-such-codec", 2, 2)


@pytest.mark.parametrize("k,m,ops_per_byte,bytes_per_byte", [
    (12, 4, 512, 16 / 12), (2, 2, 256, 2.0)])
def test_roofline_of_an_encode(k, m, ops_per_byte, bytes_per_byte):
    s = -(-MIB // k)
    work = roofline.coding_work(MIB, k, m, MIB)     # one block
    assert work["int8_ops"] == pytest.approx(ops_per_byte * k * s)
    assert work["hbm_bytes"] == pytest.approx(bytes_per_byte * k * s)
    peaks = roofline.peaks_for("TPU v5 lite")
    secs, bound = roofline.least_seconds(work, peaks)
    assert bound == "hbm"
    assert secs == pytest.approx(work["hbm_bytes"] / 819e9)


def test_roofline_of_a_heal_and_of_an_unknown_device():
    work = roofline.coding_work(10 * MIB, 12, 2, MIB)
    assert work["int8_ops"] == pytest.approx(10 * 87382 * 2 * 16 * 96)
    assert work["hbm_bytes"] == pytest.approx(10 * 87382 * 14)
    with pytest.raises(KeyError):
        roofline.peaks_for("TPU v99")


NAMES = {"plane_prefix": "/device:TPU:", "module_line": "XLA Modules",
         "op_line": "XLA Ops"}


def _trace():
    ms = 1_000_000
    mods = [("jit_impl", 0, 10 * ms), ("jit_impl", 5 * ms, 10 * ms),
            ("jit_impl", 40 * ms, 10 * ms), ("jit_other", 90 * ms, 10 * ms)]
    ops = [("while", 0, 9 * ms), ("fusion.1", 9 * ms, 1 * ms),
           ("while", 40 * ms, 9 * ms), ("copy", 90 * ms, 2 * ms)]
    host = [("H2D", 16 * ms, 22 * ms), ("tiny", 50 * ms, 50_000)]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": mods},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "thread", "events": host}]},
    ]}


def test_trace_reduction_on_a_hand_built_trace():
    ms = 1_000_000
    mods = _trace()["planes"][0]["lines"][0]["events"]
    assert tr.busy_union_ns(mods) == 35 * ms       # 0-15, 40-50, 90-100
    assert tr.busy_union_ns([]) == 0
    assert tr.gaps(mods, 0, 100 * ms) == [(15 * ms, 25 * ms),
                                          (50 * ms, 40 * ms)]
    assert tr.idle_share(0.035, 0.1) == pytest.approx(0.65)
    assert tr.idle_share(1.0, 0.0) is None
    s = tr.reduce_trace(_trace(), NAMES)
    assert s["window_s"] == pytest.approx(0.1)
    dev = s["devices"][0]
    assert dev["busy_s"] == pytest.approx(0.035)
    # pieces that the trace's ends clipped do not shorten an execution
    assert dev["execution_s"] == pytest.approx(0.010)
    assert tr.execution_ns(mods + [("jit_impl", 99 * ms, 1 * ms)]) == 10 * ms
    assert tr.execution_ns([("a", 0, 10 * ms), ("b", 20 * ms, 6 * ms)]) == \
        8 * ms
    assert tr.execution_ns([]) is None
    assert dev["device_ops"][0] == ["while", pytest.approx(0.018)]
    assert dev["module_events"] == 4 and dev["op_events"] == 4
    # the 25 ms gap lies under a 22 ms host span; the other has none
    assert dev["idle_gaps"] == [["unattributed", pytest.approx(0.040)],
                                ["H2D", pytest.approx(0.025)]]
    # a slice longer than the events' extent widens the window
    assert tr.reduce_trace(_trace(), NAMES, 0.2)["window_s"] == \
        pytest.approx(0.2)
    assert tr.reduce_trace({"planes": []}, NAMES) == {"devices": []}


def _evidence(ops=(), polls=()):
    class Cell:
        k, m, block_size = 12, 4, MIB
    win = Window(t0=100.0, seconds=10.0, end=110.0, ops=list(ops),
                 heal_polls=list(polls), heal_object_size=10 * MIB)
    return readers.Evidence(cell=Cell(), window=win, setup_s=12.5,
                            device_kind="TPU v5 lite")


def _op(sent, done, size=10 * MIB, ok=True, kind="PUT"):
    return Op(kind, "k", size, 0, due=sent, sent=sent, done=done, ok=ok,
              error="" if ok else "503")


def test_rates_are_all_the_work_over_all_the_window():
    ops = [_op(100.0, 101.0), _op(101.0, 103.0), _op(108.0, 111.0),
           _op(102.0, 102.5, ok=False)]
    ev = _evidence(ops)
    # the request answered after the close counts for the tail, not the rate
    assert readers.bytes_per_s(ev, {"scale": MIB}) == pytest.approx(2.0)
    assert readers.ops_per_s(ev, {}) == pytest.approx(0.2)
    assert readers.latency_quantile_ms(ev, {"q": 0.95}) == \
        pytest.approx(3000.0)
    assert readers.latency_quantile_ms(ev, {"q": 0.5}) == \
        pytest.approx(2000.0)
    # a run that traces reads its latencies before the tracer was started
    ev.traced_from = 103.5
    assert readers.latency_quantile_ms(ev, {"q": 0.95}) == \
        pytest.approx(2000.0)
    assert readers.setup_s(ev, {}) == 12.5
    assert readers.quantile([], 0.5) is None
    assert readers.heal_bytes_per_s(ev, {}) is None


def test_heal_rate_is_all_the_window():
    polls = [(100.0, 0, 0), (105.0, 5, 0), (110.0, 9, 0), (110.4, 10, 0)]
    ev = _evidence(polls=polls)
    assert readers.heal_bytes_per_s(ev, {"scale": MIB}) == \
        pytest.approx(9.0)
    # the ninth result came at 108 s and the tenth 0.3 s after the close:
    # 2 of its 2.3 s lay in the window, and the seconds run to the close
    ev3 = _evidence(polls=[(100.0, 0, 0), (104.0, 5, 0), (108.0, 9, 0),
                           (110.0, 9, 0), (110.3, 10, 0)])
    assert readers.heal_bytes_per_s(ev3, {"scale": MIB}) == \
        pytest.approx((9 + 2 / 2.3) * 10 / 10)
    # a sequence that hangs on its fifth object until the close reads
    # lower: all the window's seconds count, and an object that never
    # comes is credited nothing
    hung = [(100.0, 0, 0), (104.0, 4, 0), (110.0, 4, 0)]
    assert readers.heal_bytes_per_s(_evidence(polls=hung), {"scale": MIB}) \
        == pytest.approx(4.0)
    late = hung + [(140.0, 5, 0)]
    assert readers.heal_bytes_per_s(_evidence(polls=late), {"scale": MIB}) \
        == pytest.approx(4 + 6 / 36)


def test_readers_over_counters_and_trace():
    ev = _evidence([_op(100.0, 101.0), _op(101.0, 102.0)])
    name = 'mtpu_mtpu_codec_dispatch_total{codec="dense-gf8",engine="device"}'
    ev.before, ev.after = {name: 10.0}, {name: 14.0, "other": 3.0}
    p = {"pattern": 'codec_dispatch_total\\{[^}]*engine="(device|mesh)"'}
    assert readers.counter_delta_per_op(ev, p) == pytest.approx(2.0)
    assert readers.counter_delta_per_s(ev, p) == pytest.approx(0.4)
    assert readers.counter_delta_per_op(ev, {"pattern": "absent"}) is None
    # no trace: nothing to read, and nothing is reported
    assert readers.idle_share_pct(ev, {}) is None
    assert readers.roofline_share_pct(ev, {"work": "encode"}) is None
    ev.trace = tr.reduce_trace(_trace(), NAMES)
    assert readers.idle_share_pct(ev, {}) == pytest.approx(65.0)
    # two PUTs of 10 MiB in four dispatches: 5 MiB a dispatch, against the
    # 10 ms a whole execution takes in the slice, however many it caught
    rp = {"work": "encode", "dispatches": p["pattern"], "ops": ["PUT"]}
    work = roofline.coding_work(5 * MIB, 12, 4, MIB)
    least = work["hbm_bytes"] / 819e9
    assert readers.roofline_share_pct(ev, rp) == \
        pytest.approx(100 * least / 0.010)
    assert readers.roofline_share_pct(ev, {**rp, "dispatches": "absent"}) \
        is None
    # a slice without gaps may hold only pieces of longer executions: the
    # device is busy all the window's 10 s, which the four dispatches of
    # the two requests answered in it share
    full = _trace()
    full["planes"][0]["lines"][0]["events"] = [
        ("jit_impl", 0, 60_000_000), ("jit_impl", 60_000_000, 40_000_000)]
    ev.trace = tr.reduce_trace(full, NAMES)
    assert readers.roofline_share_pct(ev, rp) == \
        pytest.approx(100 * least / 2.5)
    ev.trace = tr.reduce_trace(_trace(), NAMES)
    assert readers.trace_busy_pct(ev, {"pattern": "^while"}) == \
        pytest.approx(100 * 0.018 / 0.035)
