"""A deployment of several nodes (ISSUE 38), through the harness on the
CPU: a copy of the benchmark to which a configuration of 2 nodes x 2
drives (2+2, `chips: 2`, so one CPU device a node) and a short
`put1m`-shaped mix are ADDED, driven through `run_cell`. The harness has
to start two server children that form one cluster under one run marker,
deal its clients over them, add up their counters, see both nodes'
dispatch counters move, read every sampled object back through the node
that did not acknowledge it, and leave no process, no root and no open
port of either node, on every exit path. For one node nothing may
change: the child's command line and, seed for seed, the requests sent.

    JAX_PLATFORMS=cpu python -m pytest tests/benchmark_gate/test_cluster.py -q

The copy, and the way a run is driven, are `test_benchmark.py`'s. Nothing
is timed against a limit; every test that starts servers has a time limit
of its own; every server child runs on the CPU and keeps its compiled
programs in a directory that is this file's alone.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import time

import pytest
import test_benchmark as gate

from benchmark.harness import child as childmod
from benchmark.harness import client as cl
from benchmark.harness.spec import SpecError, load_cell
from benchmark.harness.traffic import Load

MIB = gate.MIB
CELL = "tiny-2x2-put"
LIMIT_S = 120                       # of one run; the file stays under 60 s

# the harness's Python entry, every server child on one CPU device (the
# suite's own environment asks for eight)
DRIVE = """
import sys
sys.path.insert(0, {repo!r})
from benchmark.harness.runner import run_cell
sys.exit(run_cell({wl!r}, {seed}, {seconds}, {trace}, bench_json={bj!r},
                  data_root={dr!r}, require_platform="cpu", fault={fault!r},
                  extra_env={{"JAX_PLATFORMS": "cpu", "XLA_FLAGS":
                      "--xla_force_host_platform_device_count=1"}}))
"""


def _drive(copy, seed=7, seconds=3, trace=0, fault=None):
    code = DRIVE.format(repo=gate.REPO, wl=CELL, seed=seed, seconds=seconds,
                        trace=bool(trace), bj=copy["bench_json"],
                        dr=copy["data_root"], fault=fault)
    return subprocess.Popen([sys.executable, "-c", code], cwd=gate.REPO,
                            env=dict(os.environ, JAX_PLATFORMS="cpu"),
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """benchmark/ and BENCHMARK.json with one more configuration
    (`node4-ec2p2-dev1`'s file with `nodes: 2`), its cell on two chips,
    and three more whose nodes do not divide their drives or chips."""
    top = tmp_path_factory.mktemp("cluster-copy")
    mp = pytest.MonkeyPatch()
    mp.setenv("JAX_COMPILATION_CACHE_DIR", os.path.join(top, "jax_cache"))
    data = os.path.join(top, "benchmark")
    shutil.copytree(os.path.join(gate.REPO, "benchmark"), data,
                    ignore=shutil.ignore_patterns("__pycache__", ".work"))
    before = gate._tree_hashes(data)
    with open(os.path.join(gate.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(data, "configs", "node4-ec2p2-dev1.json")) as f:
        cfg = json.load(f)
    for name, nodes in (("tiny-2x2", 2), ("tiny-3x", 3), ("tiny-1x4", 1)):
        cfg["name"], cfg["deployment"]["nodes"] = name, nodes
        if name == "tiny-1x4":
            del cfg["deployment"]["nodes"]      # absent reads as one
        with open(os.path.join(data, "configs", name + ".json"), "w") as f:
            json.dump(cfg, f)
        bench["configs"].append({"name": name, "source": "a test",
                                 "file": f"benchmark/configs/{name}.json",
                                 "reduced": [], "why": "a test"})
    with open(os.path.join(data, "traffic", "tinyput4.json"), "w") as f:
        json.dump({"kind": "closed_loop", "clients": 4,
                   "ops": [{"op": "PUT", "weight": 1, "size": MIB}],
                   "payload_pool": 2, "warmup_ops_per_client": 1,
                   "check_sample": 4}, f)
    cells = {CELL: ("tiny-2x2", 2), "two-nodes-one-chip": ("tiny-2x2", 1),
             "three-nodes-four-drives": ("tiny-3x", 3),
             "one-node": ("tiny-1x4", 1)}
    for name, (config, chips) in cells.items():
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": "tinyput4", "chips": chips,
                                   "why": "a test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and (m["name"] == "ops_per_s"
                                 or m["name"].endswith(".ops")):
            m["workloads"] += list(cells)
    bj = os.path.join(top, "BENCHMARK.json")
    with open(bj, "w") as f:
        json.dump(bench, f)
    yield {"bench_json": bj, "data_root": data}
    mp.undo()
    after = gate._tree_hashes(data)
    assert {k: v for k, v in after.items() if k in before} == before, \
        "a file of the benchmark that was there was edited"


def _facts(err: str) -> tuple[str, str, list[int]]:
    """marker, root and every port of every node, from the run's first
    line."""
    line = next(ln for ln in err.splitlines() if "run marker=" in ln)
    words = dict(w.split("=", 1) for w in line.split() if "=" in w)
    return (words["marker"], words["root"],
            [int(p) for p in words["ports"].split(",")])


def _assert_nothing_left(err: str) -> None:
    marker, root, ports = _facts(err)
    assert len(ports) == 8          # two nodes: S3 and three planes each
    assert childmod.carriers(marker) == {}
    assert not os.path.exists(root)
    assert not [p for p in ports if childmod.port_open(p)]
    assert "left behind: no process carries" in err


def _said(err: str, what: str) -> list[str]:
    """What the run said after `what`, line by line."""
    return [ln.split(what, 1)[1] for ln in err.splitlines() if what in ln]


# --- whole runs of the two-node deployment -----------------------------------


@pytest.fixture(scope="module")
def runs(copy):
    """One untraced and one traced run of the cluster's cell, side by
    side: what the tests below read."""
    procs = [_drive(copy, seed=3_800_000_011 + t, trace=t) for t in (0, 1)]
    return [gate._finish(p, timeout=LIMIT_S) for p in procs]


def test_a_whole_run_prints_the_contracts_line_and_leaves_nothing(runs):
    rc, out, err = runs[0]
    assert rc == 0, err[-3000:]
    line = gate._last_json(out)
    assert set(line) == gate.RESULT_KEYS and list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"ops_per_s", "setup_s"}
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # the count is the cluster's: one CPU device a node
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 2
    assert all(v == 0 and lim == 0 for v, lim in line["checks"].values())
    _assert_nothing_left(err)


def test_two_children_one_marker_one_endpoint_list(runs):
    _, _, err = runs[0]
    marker, root, ports = _facts(err)
    s3a, sa, _, _, s3b, sb, _, _ = ports
    assert ports == [s3a, sa, sa + 1, sa + 2, s3b, sb, sb + 1, sb + 2]
    assert len(set(ports)) == 8
    # one endpoint list: a URL a drive of the flat d1 .. d4, the first two
    # behind node 1's storage plane, the others behind node 2's
    (eps,) = _said(err, "endpoints, the same on every node: ")
    assert eps.split() == [f"http://127.0.0.1:{sa}{root}/d1",
                           f"http://127.0.0.1:{sa}{root}/d2",
                           f"http://127.0.0.1:{sb}{root}/d3",
                           f"http://127.0.0.1:{sb}{root}/d4"]
    a, b = _said(err, "] node 1: --"), _said(err, "] node 2: --")
    assert a[0].split()[:4] == ["storage-address", f"127.0.0.1:{sa}",
                                "--port", str(s3a)]
    assert b[0].split()[:4] == ["storage-address", f"127.0.0.1:{sb}",
                                "--port", str(s3b)]
    # a chip each, were there chips
    assert "TPU_VISIBLE_DEVICES=0" in a[0] and "TPU_VISIBLE_DEVICES=1" in b[0]
    assert "server up after" in err and ", 2 nodes" in err


def test_both_nodes_dispatched_and_the_line_adds_them_up(runs):
    rc, out, err = runs[1]
    assert rc == 0, err[-3000:]
    line = gate._last_json(out)
    assert line["correct"] is True, err[-3000:]
    moved = [int(n) for n in re.findall(
        r"node \d+: (\d+) device dispatches in the window", err)]
    assert len(moved) == 2 and min(moved) > 0
    got = {k: v["value"] for k, v in line["metrics"].items()}
    # one block and one dispatch a 1 MiB PUT on whichever node took it,
    # so the sums read per operation as one node's do; requests in
    # flight at the close were counted by the counters too
    assert got["dispatches_per_op.ops"] == pytest.approx(
        sum(moved) / line["attempted"])
    assert got["dispatches_per_op.ops"] == pytest.approx(1.0)
    for name in ("object_ms_per_op.ops", "commit_ms_per_op.ops",
                 "stream_ms_per_op.ops", "device_call_ms_per_op.ops"):
        assert got[name] > 0, name
    # every node's devices are said; no device plane on the CPU
    assert len(re.findall(r"node \d+: devices ids=\[0\]", err)) == 2
    assert not {"codec_roofline.ops", "device_idle_share.ops"} & set(got)
    _assert_nothing_left(err)


def test_sampled_objects_are_read_back_through_the_other_node(runs):
    _, out, err = runs[0]
    note = next(ln for ln in err.splitlines()
                if "each read back through the node after" in ln)
    pairs = re.findall(r"w/c(\d\d)/\d{6} (\d)->(\d)", note)
    assert len(pairs) == 4
    for client, wrote, read in pairs:
        # client c talks to node c mod 2; its PUTs are read from the other
        assert int(wrote) == int(client) % 2 + 1
        assert int(read) == 3 - int(wrote)
    assert gate._last_json(out)["checks"]["readback_bytes_differ"] == [0, 0]


@pytest.mark.parametrize("how", ["parity_flip", "sigterm"])
def test_a_fault_fails_the_run_and_a_signal_leaves_nothing(copy, how):
    if how == "parity_flip":
        rc, out, err = gate._finish(_drive(copy, fault=how), timeout=LIMIT_S)
        assert rc == 0, err[-3000:]
        line = gate._last_json(out)
        assert line["correct"] is False
        value, limit = line["checks"]["parity_bytes_differ"]
        assert value > limit == 0
        _assert_nothing_left(err)
        return
    proc = _drive(copy, seconds=60)
    seen = ""
    deadline = time.monotonic() + LIMIT_S
    while "server up after" not in seen:
        assert time.monotonic() < deadline and proc.poll() is None, seen
        seen += proc.stderr.readline()
    time.sleep(4)                                   # into the window
    marker, _, _ = _facts(seen)
    assert len([c for c in childmod.carriers(marker).values()
                if "serve_child.py" in c]) == 2
    proc.send_signal(signal.SIGTERM)
    rc, out, err = gate._finish(proc, timeout=LIMIT_S)
    assert rc != 0 and out == ""
    _assert_nothing_left(seen + err)


# --- what a configuration may state, and what one node keeps -------------------


@pytest.mark.parametrize("cell,what", [
    ("two-nodes-one-chip", "1 chips"), ("three-nodes-four-drives",
                                        "4 drives")])
def test_nodes_have_to_divide_drives_and_chips(copy, cell, what):
    with pytest.raises(SpecError, match=f"do not divide the {what}"):
        load_cell(cell, copy["bench_json"], copy["data_root"])
    assert load_cell(CELL, copy["bench_json"], copy["data_root"]).nodes == 2
    assert load_cell("one-node", copy["bench_json"],
                     copy["data_root"]).nodes == 1
    for w in ("n16dev1-put10m", "n16mesh4-put10m", "n12dev1-get10m"):
        assert load_cell(w).nodes == 1


class _Popen:
    """Stands in for `subprocess.Popen`: starts nothing, keeps what it was
    asked to start. Its pid is above any the kernel gives, so a signal to
    its group finds nobody."""

    pid = (1 << 22) + 1
    started: list = []
    returncode = None

    def __init__(self, argv, **kw):
        self.started.append((argv, kw))

    def poll(self):
        return self.returncode

    def wait(self, timeout=None):
        return self.returncode


def test_one_node_is_started_by_todays_command_line(tmp_path, monkeypatch):
    started = _Popen.started = []
    monkeypatch.setattr(childmod.subprocess, "Popen", _Popen)
    root = str(tmp_path)
    one = childmod.Cluster(root, 16, 1, 4, {"A": "b"})
    (argv, kw), = started
    assert argv[1:] == [os.path.join(childmod.HARNESS_DIR, "serve_child.py"),
                        "server", f"{root}/d{{1...16}}", "--port",
                        str(one.children[0].port)]
    assert one.hosts == [f"127.0.0.1:{one.children[0].port}"]
    assert one.ports == [one.children[0].port]
    assert os.path.basename(one.children[0].log_path) == "server.log"
    assert os.path.basename(one.children[0].ctl_path) == "ctl.fifo"
    # no setting of the machine's chips: one node takes the host whole
    assert not [k for k in kw["env"] if k.startswith("TPU_")]
    assert kw["env"]["A"] == "b" and kw["env"][childmod.MARKER] == one.marker
    # four nodes: a chip each, by the table beside peaks.json
    del started[:]
    os.mkdir(tmp_path / "x")
    four = childmod.Cluster(str(tmp_path / "x"), 16, 4, 4, {})
    assert len(started) == 4
    assert [kw["env"]["TPU_VISIBLE_DEVICES"] for _, kw in started] == \
        ["0", "1", "2", "3"]
    assert len({kw["env"][childmod.MARKER] for _, kw in started}) == 1
    eps = [w for w in started[0][0] if w.startswith("http://")]
    assert len(eps) == 16 and all(
        [w for w in argv if w.startswith("http://")] == eps
        for argv, _ in started)
    assert [os.path.basename(c.log_path) for c in four.children] == \
        [f"server.n{i}.log" for i in (1, 2, 3, 4)]
    assert childmod.chip_share_env(0, 1, 4) == {}
    with pytest.raises(KeyError, match="2 chip"):   # tried, did not start
        childmod.chip_share_env(1, 2, 4)


def test_a_cluster_that_lost_a_bind_starts_again_on_fresh_ports(
        tmp_path, monkeypatch):
    """The ports are drawn free and bound later, by other processes: a
    node that finds one taken goes down, and the whole cluster starts
    again (a format may have been written), three times at most."""
    class Lost(_Popen):
        returncode = 1

        def __init__(self, argv, **kw):
            super().__init__(argv, **kw)
            kw["stdout"].write(b"OSError: [Errno 98] Address already in "
                               b"use\n")
            kw["stdout"].flush()

    started = _Popen.started = []
    monkeypatch.setattr(childmod.subprocess, "Popen", Lost)
    root = str(tmp_path)
    two = childmod.Cluster(root, 4, 2, 2, {})
    assert two.exited() == "the server of node 1 exited with 1"
    first = two.ports
    os.makedirs(os.path.join(root, "d1", "bench"))   # as a node leaves it
    said: list[str] = []
    assert two.lost_a_bind() and two.start_again(said.append)
    assert "starts again on fresh ports" in said[0]
    assert "Address already in use" in said[0] and "node 2" in said[0]
    assert len(started) == 4 and not set(first) & set(two.ports)
    assert not os.path.exists(os.path.join(root, "d1"))
    assert two.start_again() and not two.start_again()
    assert two.starts == childmod.Cluster.ATTEMPTS == 3
    # a node that went down over anything else ends the run
    monkeypatch.setattr(childmod.Child, "log_tail", lambda self, n=0: "boom")
    assert not two.lost_a_bind()
    two.stop()


# --- the device line and the trace of several nodes, which no CPU run makes ----


def _doc(files, ids=(0,), peak=100, platform="tpu"):
    return {"platform": platform, "kind": "TPU v5 lite", "count": len(ids),
            "ids": list(ids), "coords": [[0, 0, 0, 0]] * len(ids),
            "chip_files": list(files),
            "memory_peak_bytes": peak,
            "memory_peak_bytes_per_device": [peak] * len(ids)}


@pytest.mark.parametrize("docs,fails", [
    ([_doc(["/dev/accel0"], peak=5), _doc(["/dev/accel1"], peak=9)], None),
    ([_doc(["/dev/accel0"]), _doc(["/dev/accel0"])], "both hold /dev/accel0"),
    ([_doc(["/dev/accel0"]), _doc([])], "cannot be shown"),
    ([_doc(["/dev/accel0", "/dev/accel1"], ids=(0, 1)),
      _doc(["/dev/accel2"])], "sees 2 device"),
    ([_doc(["/dev/accel0"]), _doc(["/dev/accel1"], platform="cpu")],
     "reports"),
])
def test_every_node_holds_a_chip_of_its_own(copy, docs, fails):
    """Each process numbers its chips from 0, so the device files it
    holds open say which they are (my chip runs, PR 38)."""
    from benchmark.harness import runner

    cell = load_cell(CELL, copy["bench_json"], copy["data_root"])
    info = {"platform": "tpu", "device_kind": "TPU v5 lite", "devices": "1"}
    if fails:
        with pytest.raises(runner.RunFailed, match=fails):
            runner._devices(cell, docs, info)
        return
    assert runner._devices(cell, docs, info) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 2,
        "memory_peak_bytes": 9, "memory_peak_bytes_per_device": [5, 9]}
    # one node: what its JAX says, as before
    one = load_cell("one-node", copy["bench_json"], copy["data_root"])
    assert runner._devices(one, [_doc([], peak=7)], info)["count"] == 1


def test_the_nodes_traces_are_one_devices_list(monkeypatch):
    """A process traces its own chips alone and calls the first one
    TPU:0: the union keeps them apart by node, a node whose slice held
    no operation counts as idle, and only a slice that no node's device
    ran in fails the run."""
    from benchmark.harness import runner

    def summary(busy):
        return {"window_s": 0.2, "devices": [
            {"name": "/device:TPU:0", "busy_s": busy, "execution_s": 0.004,
             "module_events": 2, "op_events": 9, "device_ops": [["a", busy]],
             "idle_gaps": []}]} if busy else {"devices": []}

    by_dir = {"t/n1": summary(0.02), "t/n2": summary(0.0),
              "t/n3": summary(0.06), "t/n4": summary(0.04)}

    class Reducer:
        returncode = 0

        def __init__(self, argv, **kw):
            self.doc = by_dir[argv[2]]

        def communicate(self, timeout=None):
            return json.dumps(self.doc), ""

    monkeypatch.setattr(runner.subprocess, "Popen", Reducer)
    got = runner._reduce_traces(sorted(by_dir), "TPU v5 lite", 0.2, "m")
    assert [d["name"] for d in got["devices"]] == [
        "n1/device:TPU:0", "n3/device:TPU:0", "n4/device:TPU:0"]
    assert got["window_s"] == 0.2 and got["devices_per_process"] == 1
    assert got["busy_s_mean"] == pytest.approx((0.02 + 0.06 + 0.04) / 4)
    # one node: the reducer's own summary, names as the trace has them
    one = runner._reduce_traces(["t/n3"], "TPU v5 lite", 0.2, "m")
    assert one["devices"][0]["name"] == "/device:TPU:0"
    assert one["busy_s_mean"] == pytest.approx(0.06)
    with pytest.raises(runner.RunFailed, match="no operation of the device"):
        runner._reduce_traces(["t/n2", "t/n2"], "TPU v5 lite", 0.2, "m")


# What one seed sends to one host, recorded on the tree before ISSUE 38
# (PR 37's, whose `Load` took one host): a digest of the set-up's requests
# (method, path, md5 of the body) and of every client's first 40 requests
# of a window (op kind, key, index of the body), and one of the payloads.
POOL = "55386061f250eba2c7c6687e7b5694450da61ac6ac6454afe873fc165cbcc3a2"
RECORDED = {
    "put10m": ("17db014c8e6f2f74d306a8b0262e769d6fa49f2b7447ffde6b4e0121e2106ab5",
               POOL),
    "dget10m": ("226d81d6a874f45fadd2d02cfb606c9c8a5903270b1b8c6f6a8d9ba7e4ce6ca7",
                POOL),
}


class _Stub:
    """An S3 connection that answers every request rightly and at once,
    and keeps what it was asked."""

    def __init__(self, load, seen):
        self.load, self.seen = load, seen

    def close(self):
        pass

    def request(self, method, path, query=None, headers=None, body=b"",
                payload_hash=None):
        self.seen.append((method, path, hashlib.md5(body).hexdigest()))
        if method == "PUT":
            return 200, {"ETag": hashlib.md5(body).hexdigest()}, b""
        key = path.split("/", 2)[2]
        _, size, bi = next(p for p in self.load.preloaded if p[0] == key)
        return 200, {}, self.load.pool(size)[bi].data


def _sent(mix: str, seed: int, host, per_client: int = 40):
    """-> (digest of what `Load` sends against a stub, digest of its
    payloads). Sizes are cut to 4 KiB: neither a key nor a draw reads
    one."""
    from benchmark.harness import traffic

    cell = load_cell({"put10m": "n16dev1-put10m",
                      "dget10m": "n12dev1-get10m"}[mix])
    t = json.loads(json.dumps(cell.traffic))
    for op in t["ops"]:
        if "size" in op:
            op["size"] = 4096
    load = Load(t, seed, host, "/nonexistent", cell.drives, lambda m: None)
    seen: list = []
    old = traffic.S3
    traffic.S3 = lambda host: _Stub(load, seen)
    try:
        if "preload" in t:              # its set-up wipes a drive: left out
            load.preloaded = [(traffic.preload_key(i), 4096, i % 16)
                              for i in range(int(t["preload"]["objects"]))]
        else:
            load.setup()
        # the bucket and the first PUT alone, in order; then the fan-out
        sent = [seen[:2] + sorted(seen[2:])]
        win = load.run_window(0.4)
    finally:
        traffic.S3 = old
    assert all(o.ok for o in win.ops)
    by_client: dict[int, list] = {}
    for o in sorted(win.ops, key=lambda o: (o.client, o.sent)):
        by_client.setdefault(o.client, []).append((o.kind, o.key, o.body))
    assert len(by_client) == t["clients"]
    assert min(map(len, by_client.values())) >= per_client
    sent += [by_client[c][:per_client] for c in sorted(by_client)]
    pool = hashlib.sha256(b"".join(
        b.data for size in sorted(load.pools)
        for b in load.pools[size])).hexdigest()
    return (hashlib.sha256(json.dumps(sent).encode()).hexdigest(), pool,
            win)


@pytest.mark.parametrize("mix", sorted(RECORDED))
def test_one_host_sends_what_it_sent_before(mix):
    plan, pool, win = _sent(mix, 3_800_000_123, ["127.0.0.1:1"])
    assert (plan, pool) == RECORDED[mix]
    assert {o.node for o in win.ops} == {0}


def test_clients_and_jobs_are_dealt_over_the_nodes():
    """Client c of a window talks to node c mod N, and so does job j of
    a set-up fan-out; keys, bodies and their order are one host's."""
    from benchmark.harness import traffic

    hosts = [f"127.0.0.1:{n}" for n in (1, 2, 3, 4)]
    one, _, _ = _sent("put10m", 3_800_000_123, hosts[:1])
    four, _, win = _sent("put10m", 3_800_000_123, hosts)
    assert {(o.client, o.node) for o in win.ops} == {
        (c, c % 4) for c in range(8)}
    # four nodes send one PUT alone more to each node after the first
    assert one != four
    cell = load_cell("n16dev1-put10m")
    t = {**cell.traffic, "ops": [{"op": "PUT", "size": 4096}]}
    load = Load(t, 5, hosts, "/nonexistent", 16, lambda m: None)
    asked: list = []

    class Conn(_Stub):
        def __init__(self, host):
            super().__init__(load, asked)
            self.host = host

        def request(self, method, path, **kw):
            asked.append((self.host, path))
            return super().request(method, path, **kw)

    old = traffic.S3
    traffic.S3 = Conn
    try:
        load.setup()
    finally:
        traffic.S3 = old
    by_path = {a[1]: a[0] for a in asked if isinstance(a[0], str)
               and a[0].startswith("127")}
    assert by_path["/bench"] == hosts[0]
    assert [by_path[f"/bench/warm/first-4096{s}"]
            for s in ("", "-n2", "-n3", "-n4")] == hosts
    jobs = [f"/bench/warm/c{c:02d}-{r}-4096"
            for r in range(2) for c in range(8)]
    assert [by_path[p] for p in jobs] == [hosts[j % 4] for j in range(16)]
