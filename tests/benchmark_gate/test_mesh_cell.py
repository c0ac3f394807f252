"""The four-chip cell (ISSUE 29), through the harness on four virtual CPU
devices: a tiny copy of `node16-ec12p4-mesh4` (12+4, `chips: 4`; 2 MiB
PUTs, since a 1 MiB object's 87,382-byte shards would ride inline in
`xl.meta` and the reference takes shard files) is ADDED to a copy of the
benchmark, as `test_benchmark.py`'s fixture adds its cells, and driven
through `run_cell`. The mesh engine has to do the work (its dispatch
counter moves, the one-chip engine's does not), the answers have to be
the reference's, a fault planted under `MeshCodec` has to turn `correct`
false, and nothing is left behind.

    JAX_PLATFORMS=cpu python -m pytest tests/benchmark_gate/test_mesh_cell.py -q

Nothing is timed against a limit; every server child runs on the CPU.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import subprocess
import sys

import pytest
import test_benchmark as gate

from benchmark.harness import readers
from benchmark.harness.spec import load_cell

REPO = gate.REPO
CELL, CONFIG = "n16mesh4-put10m", "node16-ec12p4-mesh4"
MESH_METRICS = ("collective_bytes_per_op.mesh", "padded_blocks_per_op.mesh")
SHARD = 87382                       # ceil(1 MiB / 12)

# the harness's Python entry, with the server child on four virtual devices
DRIVE = """
import sys
sys.path.insert(0, {repo!r})
from benchmark.harness.runner import run_cell
sys.exit(run_cell("tiny-mesh", {seed}, {seconds}, {trace},
                  bench_json={bj!r}, data_root={dr!r},
                  require_platform="cpu", fault={fault!r},
                  extra_env={{"JAX_PLATFORMS": "cpu", "XLA_FLAGS":
                      "--xla_force_host_platform_device_count=4"}}))
"""


def _bench() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def mesh_copy(tmp_path_factory):
    """A copy of benchmark/ and BENCHMARK.json with one more cell: the
    new configuration's file under another name, a mix of 2 MiB PUTs, and
    two metrics that keep the engines' dispatch counters apart."""
    top = tmp_path_factory.mktemp("mesh-copy")
    data = os.path.join(top, "benchmark")
    shutil.copytree(os.path.join(REPO, "benchmark"), data,
                    ignore=shutil.ignore_patterns("__pycache__", ".work"))
    before = gate._tree_hashes(data)
    bench = _bench()
    with open(os.path.join(data, "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    cfg["name"] = "tiny-mesh4"
    with open(os.path.join(data, "configs", "tiny-mesh4.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(data, "traffic", "tinyput2m.json"), "w") as f:
        json.dump({"kind": "closed_loop", "clients": 2,
                   "ops": [{"op": "PUT", "size": 2 * gate.MIB}],
                   "payload_pool": 2, "warmup_ops_per_client": 1,
                   "check_sample": 3}, f)
    for engine in ("mesh", "device"):
        name = f"{engine}_dispatches_per_op"
        with open(os.path.join(data, "layer_metrics", name + ".json"),
                  "w") as f:
            json.dump({"reader": "counter_delta_per_op", "ops": ["PUT"],
                       "pattern": "codec_dispatch_total\\{[^}]*engine=\""
                                  + engine + "\""}, f)
        bench["per_layer"].append({
            "name": name, "unit": "1/op", "better": "lower",
            "source": "program_counter", "layer": "device engine",
            "moves": "goodput_mibps", "workloads": ["tiny-mesh"]})
    bench["configs"].append({"name": "tiny-mesh4", "source": "a test",
                             "file": "benchmark/configs/tiny-mesh4.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "tiny-mesh", "config": "tiny-mesh4",
                               "traffic": "tinyput2m", "chips": 4,
                               "why": "a test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny-mesh")
    bj = os.path.join(top, "BENCHMARK.json")
    with open(bj, "w") as f:
        json.dump(bench, f)
    yield {"bench_json": bj, "data_root": data}
    after = gate._tree_hashes(data)
    assert {k: v for k, v in after.items() if k in before} == before, \
        "a file of the benchmark that was there was edited"


def _run(mesh_copy, seconds=3, trace=0, fault=None, seed=3_000_000_029):
    code = DRIVE.format(repo=REPO, seed=seed, seconds=seconds,
                        trace=bool(trace), bj=mesh_copy["bench_json"],
                        dr=mesh_copy["data_root"], fault=fault)
    proc = subprocess.Popen([sys.executable, "-c", code], cwd=REPO,
                            env=dict(os.environ, JAX_PLATFORMS="cpu"),
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)
    rc, out, err = gate._finish(proc)
    assert rc == 0, err[-3000:]
    gate._assert_nothing_left(err)
    return gate._last_json(out), err


SPAN_PHASES = ("body_read", "admission", "object", "commit", "stream",
               "device_h2d", "device_call", "device_wait")

# what cell 1 reported when this cell was added; it reports them all
SHARED = ("goodput_mibps", "op_p95_ms.put", "setup_s",
          "device_idle_share.put", "codec_roofline.put",
          "dispatches_per_op.put", "op_p50_ms.put", "retraces_per_op.put",
          *(f"{p}_ms_per_op.put" for p in SPAN_PHASES))


def test_the_real_cell_is_the_one_chip_deployment_on_the_mesh():
    cell = load_cell(CELL)
    assert cell.chips == 4 and cell.engine == "mesh"
    assert (cell.k, cell.m, cell.drives, cell.block_size) == (12, 4, 16,
                                                              gate.MIB)
    one = load_cell("n16dev1-put10m")
    assert cell.traffic == one.traffic
    # the deployment differs by the engine and the number of chips alone
    env, env1 = cell.config["env"], one.config["env"]
    assert set(env) == set(env1)
    assert {k for k in env if env[k] != env1[k]} == {"MTPU_ENCODE_ENGINE"}
    dep, dep1 = cell.config["deployment"], one.config["deployment"]
    assert {k for k in dep if dep[k] != dep1.get(k)} == \
        {"engine", "chips", "mesh_shape"}
    assert cell.config["reduced"] == one.config["reduced"] == \
        ["run_data_scale"]
    assert set(one.config["guarantees"]) < set(cell.config["guarantees"])
    # it reports what cell 1 reports, and the mesh's own metrics besides
    mine = {m["name"] for m in cell.end_to_end + cell.per_layer}
    theirs = {m["name"] for m in one.end_to_end + one.per_layer}
    assert set(SHARED) <= mine & theirs
    assert {m for m in mine if m.endswith(".mesh")} and not \
        {m for m in theirs if m.endswith(".mesh")}


def test_the_cell_and_its_metrics_are_entries_files_and_known_readers():
    """Written so that a later cell or metric, appended as this one was,
    does not break it: this file is one of the benchmark's own."""
    bench = _bench()
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 4 and entry["traffic"] == "put10m"
    assert entry["config"] == CONFIG
    assert CONFIG in [c["name"] for c in bench["configs"]]
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= len(bench["workloads"]) // 2 or len(four) == 1
    mesh = [m for m in bench["per_layer"] if m["name"].endswith(".mesh")]
    assert {m["name"] for m in mesh} <= set(MESH_METRICS) and mesh
    for m in mesh:
        assert m["workloads"][0] == CELL and m["moves"] == "goodput_mibps"
        path = os.path.join(REPO, "benchmark", "layer_metrics",
                            m["name"] + ".json")
        with open(path) as f:
            doc = json.load(f)
        assert doc["reader"] in readers.READERS and doc["what"], path
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in SHARED and "workloads" in m:
            assert m["workloads"][:2] == ["n16dev1-put10m", CELL], m


def test_the_span_metrics_keep_their_files_readers_and_first_cells():
    """What `test_layer_spans.py` holds of PR 27's 25 metrics (a data
    file each, a known reader, the family's own cell, the span's `op`),
    without pinning them to the end of `per_layer` or to one cell: that
    test cannot pass once a metric or a cell is appended (PERF.md §7)."""
    bench = _bench()
    names = {f"{phase}_ms_per_op.{family}"
             for family, phases in (
                 ("put", SPAN_PHASES), ("ops", SPAN_PHASES),
                 ("heal", [p for p in SPAN_PHASES
                           if p not in ("body_read", "admission")]))
             for phase in phases} | {
        f"retraces_per_op.{family}" for family in ("put", "ops", "heal")}
    assert len(names) == 25
    # these 25 by name: a later PR may add others under either pattern
    spans = [m for m in bench["per_layer"] if m["name"] in names]
    assert {m["name"] for m in spans} == names
    cells = {"put": "n16dev1-put10m", "ops": "n4dev1-put1m",
             "heal": "n16dev1-heal2"}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in spans:
        family = m["name"].rsplit(".", 1)[1]
        assert m["workloads"][0] == cells[family], m
        assert m["moves"] in e2e and m["source"] == "program_counter", m
        path = os.path.join(REPO, "benchmark", "layer_metrics",
                            m["name"] + ".json")
        with open(path) as f:
            doc = json.load(f)
        assert doc["reader"] in readers.READERS and doc["what"], path
        if doc["reader"] == "counter_ratio":
            op = "heal_object" if family == "heal" else "put_object"
            assert f'op="{op}"' in doc["pattern"]
            assert f'op="{op}"' in doc["over"] and doc["scale"] == 1000


def test_tiny_mesh_cell_untraced_line(mesh_copy):
    line, err = _run(mesh_copy)
    assert set(line) == gate.RESULT_KEYS and list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"goodput_mibps", "op_p95_ms.put",
                                    "setup_s"}
    for m in line["metrics"].values():
        assert math.isfinite(m["value"]) and m["value"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 4
    assert all(v == 0 and lim == 0 for v, lim in line["checks"].values())
    assert "'devices': '4'" in err, err[-2000:]      # the server's own word


def test_tiny_mesh_cell_traced_line_reads_the_mesh(mesh_copy):
    line, _ = _run(mesh_copy, trace=1)
    assert line["correct"] is True and line["failed"] == 0
    got = {k: v["value"] for k, v in line["metrics"].items()}
    for v in got.values():
        assert math.isfinite(v) and v >= 0, got
    # every .put layer metric that reads a counter or a span prints
    want = {m["name"] for m in _bench()["per_layer"]
            if m["name"] in SHARED + MESH_METRICS
            and m["source"] != "device_trace"}
    assert want <= set(got), want - set(got)
    # the mesh did the work, the one-chip engine none of it
    assert got["mesh_dispatches_per_op"] == 1.0
    assert got.get("device_dispatches_per_op", 0.0) == 0.0
    assert got["dispatches_per_op.put"] == 1.0
    # the window's PUTs run on the program the warm-up traced
    assert got["retraces_per_op.put"] == 0.0
    # two blocks a PUT (the real cell's tail), padded to the one compiled
    # batch of 8 rows, whose parity and digests cross the lane axis
    assert got["padded_blocks_per_op.mesh"] == 6.0
    assert got["collective_bytes_per_op.mesh"] == 8 * 4 * SHARD + 8 * 16 * 32
    # the H2D of the padded batch, the call and the wait have spans
    for p in ("h2d", "call", "wait"):
        assert got[f"device_{p}_ms_per_op.put"] > 0, p
    # no device plane on the CPU: the shares are left out, never 0
    for name in ("codec_roofline.put", "device_idle_share.put"):
        assert name not in got


@pytest.mark.parametrize("fault", ["parity_flip", "half_batch"])
def test_a_fault_under_the_mesh_codec_fails_the_run(mesh_copy, fault):
    line, _ = _run(mesh_copy, seconds=4, fault=fault)
    assert line["correct"] is False
    value, limit = line["checks"]["parity_bytes_differ"]
    assert value > limit == 0
