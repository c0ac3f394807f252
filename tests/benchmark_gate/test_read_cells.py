"""The read side's cell (ISSUE 36): `n12dev1-get10m` on the deployment
`node12-ec8p4-dev1`, through the harness on the CPU. (`n16dev1-mixed`,
the issue's second cell, was taken out after the driver's check found
its runs too wide apart for `ops_per_s`'s bound: PERF.md section 7.)

    JAX_PLATFORMS=cpu python -m pytest tests/benchmark_gate/test_read_cells.py -q

A tiny copy of the mix runs through `run_cell(..., require_platform=
"cpu")` on the 8+4 configuration file itself (12 drives, 1 MiB blocks,
131,072-byte shards; three blocks an object, so the pipelined GET driver
runs) and has to come out `correct`, with `ops_per_s` in the untraced
line and every new per-layer metric that has no device source in the
traced one. The arithmetic the cell rests on is pinned beside it: the
preload against the clients and the shard rotations, the new counters
against what a read has to verify, and why one wiped drive is the same
work for every seed where two were not.

The copy, and the way a run is driven, are `test_benchmark.py`'s. The
guarantees these runs hold are stated in
`benchmark/configs/node12-ec8p4-dev1.json`, which names this file;
`tests/test_ec8p4_degraded_get.py` holds them for every single drive and
every pair. Nothing is timed against a limit, every run's child keeps its
compiled programs in a directory that is this file's alone (ROADMAP D12:
the workers of a run share `<checkout>/.jax_cache` otherwise), and the
harness draws each run a free port from the kernel."""

from __future__ import annotations

import json
import math
import os
import re
import shutil
import zlib

import pytest
import test_benchmark as gate

from benchmark.harness import reference
from benchmark.harness.spec import load_cell

MIB = 1 << 20
SIZE = 3 * MIB
CELL = "n12dev1-get10m"
TINY = "tiny-dget"


def _bench() -> dict:
    with open(os.path.join(gate.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _tiny_mix(mix: dict) -> dict:
    """The mix as it is, but for its scale: 3 MiB objects, 2 clients, a
    preload of 48."""
    tiny = json.loads(json.dumps(mix))
    tiny["clients"] = 2
    for op in tiny["ops"]:
        if "size" in op:
            op["size"] = SIZE
    tiny["preload"] = {"objects": 48, "size": SIZE, "clients": 2}
    tiny.update(payload_pool=2, warmup_ops_per_client=1, check_sample=3)
    return tiny


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """A copy of benchmark/ and BENCHMARK.json to which the tiny cell
    is added, on `node12-ec8p4-dev1`'s own file; every metric that lists
    the read cell lists its tiny copy too."""
    top = tmp_path_factory.mktemp("read-cells")
    mp = pytest.MonkeyPatch()
    mp.setenv("JAX_COMPILATION_CACHE_DIR", os.path.join(top, "jax_cache"))
    data = os.path.join(top, "benchmark")
    shutil.copytree(os.path.join(gate.REPO, "benchmark"), data,
                    ignore=shutil.ignore_patterns("__pycache__", ".work"))
    before = gate._tree_hashes(data)
    bench = _bench()
    traffic = {w["name"]: w["traffic"] for w in bench["workloads"]}
    with open(os.path.join(data, "traffic", traffic[CELL] + ".json")) as f:
        mix = _tiny_mix(json.load(f))
    with open(os.path.join(data, "traffic", TINY + ".json"), "w") as f:
        json.dump(mix, f)
    bench["workloads"].append({
        "name": TINY, "config": "node12-ec8p4-dev1", "traffic": TINY,
        "chips": 1, "why": "a test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append(TINY)
    bj = os.path.join(top, "BENCHMARK.json")
    with open(bj, "w") as f:
        json.dump(bench, f)
    yield {"bench_json": bj, "data_root": data}
    mp.undo()
    after = gate._tree_hashes(data)
    assert {k: v for k, v in after.items() if k in before} == before, \
        "a file of the benchmark that was there was edited"


def _line(copy, cell: str, trace: int) -> tuple[dict, str]:
    rc, out, err = gate._finish(gate._drive(
        copy, cell, seed=3_500_000_017 + trace, seconds=4, trace=trace))
    assert rc == 0, err[-3000:]
    line = gate._last_json(out)
    assert line["correct"] is True, err[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0, err[-3000:]
    assert all(v == 0 and lim == 0 for v, lim in line["checks"].values())
    assert {"answers_wrong", "never_answered", "readback_bytes_differ",
            "nothing_compared", "layout_faults", "data_bytes_differ",
            "parity_bytes_differ", "digest_bytes_differ"} <= set(
                line["checks"])
    gate._assert_nothing_left(err)
    return {k: v["value"] for k, v in line["metrics"].items()}, err


def _host_metrics() -> set[str]:
    """The new per-layer metrics of the read cell that a CPU run can
    read: those that list it and have no device source."""
    return {m["name"] for m in _bench()["per_layer"]
            if CELL in m.get("workloads", ())
            and m["source"] != "device_trace"}


def test_an_untraced_run_is_correct_and_reports_ops_per_s(copy):
    got, _ = _line(copy, TINY, 0)
    assert set(got) == {"ops_per_s", "setup_s"}
    assert got["ops_per_s"] > 0 and got["setup_s"] > 0


def test_a_traced_degraded_get_run_reports_the_get_family(copy):
    want = _host_metrics()
    assert want == {
        "op_p50_ms.get", "op_p95_ms.get", "object_ms_per_req.get",
        "stream_ms_per_req.get", "device_call_ms_per_req.get",
        "device_wait_ms_per_req.get", "verified_bytes_per_op.get",
        "reconstructed_blocks_per_op.get", "dispatches_per_op.get"}
    got, err = _line(copy, TINY, 1)
    assert want <= set(got), want - set(got)
    for name in want:
        assert math.isfinite(got[name]) and got[name] >= 0, (name, got)
    # every byte read was verified: 8 shards of every block, or more
    # (a reader that failed over reads a ninth)
    assert got["verified_bytes_per_op.get"] >= 8 * 3 * 131072 == SIZE
    # one of twelve drives is wiped: two objects in three have a data
    # shard on it, and their three blocks are one reader batch and one
    # dispatch (PR 37: a block a round trip before), so at most a
    # dispatch for every three rebuilt blocks, a tail or a failed-over
    # read allowed for
    assert 0 < got["reconstructed_blocks_per_op.get"] <= 3
    assert 0 < got["dispatches_per_op.get"] <= \
        got["reconstructed_blocks_per_op.get"] / 2
    assert got["device_call_ms_per_req.get"] > 0
    assert got["object_ms_per_req.get"] >= got["stream_ms_per_req.get"] > 0
    assert got["op_p95_ms.get"] >= got["op_p50_ms.get"] > 0
    # no device plane on the CPU: its two metrics are left out
    assert not {"codec_roofline.get", "device_idle_share.get"} & set(got)
    assert re.search(r"wiped the bucket on drives \[\d+\] ", err)


# --- the arithmetic the cell rests on -----------------------------------------


# the fastest client of the builders' chip runs (PERF.md section 4: 75 to
# 84 since PR 37, no run passed 110) read this many of its 120 keys in a
# 30 s window, and the run this many GETs
FASTEST_CLIENT_GETS = 84
FASTEST_RUN_GETS = 534


def test_the_preload_divides_by_clients_and_rotations():
    cell = load_cell("n12dev1-get10m")
    t, dep = cell.traffic, cell.config["deployment"]
    n, clients = t["preload"]["objects"], t["clients"]
    assert (cell.drives, cell.k, cell.m) == (12, 8, 4)
    assert dep["shard_size"] == reference.shard_size(cell.block_size, 8) \
        == 131072
    assert n == 960 == 8 * 120 == 12 * 80
    assert n % clients == 0 and n % cell.drives == 0
    assert t["preload"] == {"objects": 960, "size": 10 * cell.block_size,
                            "clients": 8}
    # a client's share of the keys (`preloaded[c::clients]`), each read
    # at most once: no share runs out at 1.25 times the fastest client
    # seen, and the why's ceiling is the share over the GETs' weight
    gets = next(op for op in t["ops"] if op["op"] == "GET")
    assert gets == {"op": "GET", "weight": 9, "keys": "each_once"}
    assert [op for op in t["ops"] if op["op"] == "PUT"] == [
        {"op": "PUT", "weight": 1, "size": 10 * MIB}]
    assert t["wipe_drives"] == 1 and t["kind"] == "closed_loop"
    assert (t["payload_pool"], t["warmup_ops_per_client"],
            t["check_sample"]) == (16, 2, 8)
    share = n // clients
    assert 1.25 * FASTEST_CLIENT_GETS <= share == 120
    assert 1.25 * FASTEST_RUN_GETS <= n
    assert clients * share / 0.9 / 30 > 32
    # the keys fall on all twelve shard rotations, evenly enough that
    # every client reads every rotation
    turns = _turns(n)
    for c in range(clients):
        assert set(turns[c::clients]) == set(range(12))


def _turns(n: int) -> list[int]:
    """The turn of the ring of twelve drives that places each preloaded
    object's shards (`object.metadata.hash_order`)."""
    from benchmark.harness.traffic import BUCKET, preload_key

    return [zlib.crc32(f"{BUCKET}/{preload_key(i)}".encode()) % 12
            for i in range(n)]


def _rotations_that_rebuild(wiped: tuple[int, ...]) -> int:
    """Of the twelve turns of the ring, those in which a drive of
    `wiped` (1-based) holds a data shard, placed as the program places
    them."""
    from minio_tpu.object.metadata import hash_order

    seen = {}
    for i in range(4096):
        key = f"k{i}"
        seen.setdefault(zlib.crc32(key.encode()) % 12, hash_order(key, 12))
        if len(seen) == 12:
            break
    assert len(seen) == 12
    # distribution[d - 1] is the shard (1-based) that drive d holds
    return sum(any(dist[d - 1] <= 8 for d in wiped)
               for dist in seen.values())


def test_every_seed_does_the_same_work_with_one_wiped_drive():
    """Whichever drive `draw_wiped` returns, it holds a data shard in 8
    of the 12 rotations: 10 * 8 / 12 = 6.67 blocks rebuilt a GET for
    every seed."""
    from benchmark.harness.traffic import draw_wiped

    drawn = set()
    for seed in range(3_600_000_000, 3_600_000_200):
        (d,) = draw_wiped(reference.rng_for(seed, 1), 12, 1)
        drawn.add(d)
        assert _rotations_that_rebuild((d,)) == 8
    assert drawn == set(range(1, 13))


def test_what_is_left_of_the_seed_is_the_crc_of_960_consecutive_names():
    """Over the cell's own 960 keys the twelve rotations do not come 80
    times each (crc32 of consecutive names: 67 to 96), so a drive holds a
    data shard of 602 to 666 of them and not of 640: 6.27 to 6.94 blocks
    rebuilt a GET by the drive drawn, a range of 0.67 where two wiped
    drives had 2.5 (7.5 to 10)."""
    from minio_tpu.object.metadata import hash_order
    from benchmark.harness.traffic import BUCKET, preload_key

    turns = _turns(960)
    counts = [turns.count(r) for r in range(12)]
    assert (min(counts), max(counts)) == (67, 96)
    orders = [hash_order(f"{BUCKET}/{preload_key(i)}", 12)
              for i in range(960)]
    lose_data = [sum(o[d - 1] <= 8 for o in orders) for d in range(1, 13)]
    assert (min(lose_data), max(lose_data)) == (602, 666)
    assert sum(lose_data) == 960 * 8


@pytest.mark.parametrize("distance,rotations", [(1, 9), (2, 10), (3, 11),
                                                (4, 12), (5, 12)])
def test_two_wiped_drives_did_work_by_their_distance(distance, rotations):
    """The control that pins the reason: with two wiped drives (ISSUE
    35's traffic) the rotations that lose a data shard depend on how far
    apart the two lie on the ring, 7.5 to 10 rebuilt blocks a GET by the
    seed's draw."""
    for first in range(1, 13):
        pair = (first, (first - 1 + distance) % 12 + 1)
        assert _rotations_that_rebuild(pair) == rotations


def test_the_cell_reports_ops_per_s_and_lists_its_metrics():
    bench = _bench()
    by_name = {w["name"]: w for w in bench["workloads"]}
    assert by_name[CELL]["config"] == "node12-ec8p4-dev1"
    assert by_name[CELL]["chips"] == 1
    cell = load_cell(CELL)
    assert {m["name"] for m in cell.end_to_end} == {"ops_per_s", "setup_s"}
    assert len(cell.per_layer) == 11
    for m in cell.per_layer:
        assert m["moves"] == "ops_per_s", m["name"]
        assert m["name"].endswith(".get") and m["workloads"] == [CELL]
    # the patterns the accepted metrics read do not see the new series
    from benchmark.harness.client import dispatch_count

    page = {'mtpu_mtpu_codec_dispatch_total{codec="dense-gf8",'
            'engine="device"}': 3.0,
            'mtpu_codec_dispatch_kind_total{engine="device",'
            'kind="apply"}': 5.0}
    assert dispatch_count(page, "device") == 3.0
    with open(os.path.join(gate.REPO, "benchmark", "layer_metrics",
                           "dispatches_per_op.put.json")) as f:
        old = re.compile(json.load(f)["pattern"])
    with open(os.path.join(gate.REPO, "benchmark", "layer_metrics",
                           "dispatches_per_op.get.json")) as f:
        new = re.compile(json.load(f)["pattern"])
    assert [bool(old.search(k)) for k in page] == [True, False]
    assert [bool(new.search(k)) for k in page] == [False, True]
