"""One run of one cell: start the deployment's server children, set up,
measure for `--seconds`, check what the window produced, stop everything,
print one JSON line.

This is the client process. It never imports jax: the children that own
the chips are the servers (`serve_child.py`, one a node of the
deployment), and the traces they write are read by another child, pinned
to the CPU, once the servers have gone.

A deployment of several nodes is one system under test: the counters a
metric reads are the sums of the nodes' scrapes, the device line counts
every node's devices and gives the fullest one's memory, every node
traces its own chips over the same slice, and the forced engine's
dispatch counter has to move on every node, since every node takes
requests.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

from . import check as checking
from . import client as cl
from . import readers, roofline
from .child import Cluster, LeftBehind, become_subreaper
from .spec import CHECKOUT, HARNESS_DIR, Cell, load_cell
from .traffic import Load, Window

DEADLINE_S = 340                    # the contract allows 360
T_START = time.monotonic()


class Stop(BaseException):
    """A signal or the run's own deadline ends the run."""


class RunFailed(Exception):
    """The run cannot give a result; it prints none."""


def say(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T_START:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def _on_signal(signum, frame):
    raise Stop(f"signal {signal.Signals(signum).name}")


def trace_cue_at(traffic: dict, t0: float, seconds: float) -> float:
    """When the traced slice is cued: at the mix's `trace_cue_share` of
    the window, the middle where the mix names none. A mix whose work may
    end before the close (a heal's backlog) cues it early, where a
    sequence several times faster still holds the device."""
    return t0 + seconds * float(traffic.get("trace_cue_share", 0.5))


def _wait_file(path: str, timeout: float, what: str) -> dict:
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if os.path.exists(path):
            with open(path) as f:
                doc = json.load(f)
            if "error" in doc:
                raise RunFailed(f"{what}: {doc['error']}")
            return doc
        time.sleep(0.02)
    raise RunFailed(f"{what}: the child did not answer in {timeout:.0f}s")


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             bench_json: str | None = None, data_root: str | None = None,
             require_platform: str = "tpu", fault: str | None = None,
             extra_env: dict | None = None, out=None) -> int:
    """The harness's Python entry. The command line always requires a TPU
    and plants no fault; tests hand in `require_platform="cpu"` with
    JAX_PLATFORMS=cpu in `extra_env`, and a `fault` to see `correct` come
    out false."""
    t_start = time.monotonic()
    cell = load_cell(workload, bench_json, data_root)
    old = {s: signal.signal(s, _on_signal)
           for s in (signal.SIGTERM, signal.SIGINT, signal.SIGALRM)}
    signal.alarm(DEADLINE_S)
    become_subreaper()
    tmpfs = "/dev/shm" if os.access("/dev/shm", os.W_OK) else None
    root = tempfile.mkdtemp(prefix="mtpu-bench-", dir=tmpfs)
    env = dict(os.environ)
    env.update(cell.config["env"])
    env.update(extra_env or {})
    if not env.get("JAX_COMPILATION_CACHE_DIR"):
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CHECKOUT,
                                                        ".jax_cache")
    cluster = None
    result = checks = None
    problem: BaseException | None = None
    try:
        # from this thread, which outlives the children (see Child)
        cluster = Cluster(root, cell.drives, cell.nodes, cell.chips, env,
                          fault)
        result, checks = _measure(cell, cluster, root, seed, float(seconds),
                                  trace, require_platform, t_start)
    except BaseException as exc:  # noqa: BLE001 - cleaned up, then reported
        problem = exc
    finally:
        # every exit path comes through here, and nothing cuts it short
        signal.alarm(0)
        for s in old:
            signal.signal(s, signal.SIG_IGN)
        left, log_tail = _leave_nothing(cluster, root)
        for s, h in old.items():
            signal.signal(s, h)
    if problem is not None:
        if log_tail and not isinstance(problem, Stop):
            say("--- server log ---\n" + log_tail)
        say(f"FAILED: {type(problem).__name__}: {problem}")
        return 1
    if left:
        say(f"FAILED: {left}")
        return 1
    if "jax" in sys.modules:
        say("FAILED: the client process imported jax")
        return 1
    for note in checks.notes:
        say(note)
    for line in checks.lines():
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), file=out or sys.stdout, flush=True)
    return 0


def _leave_nothing(cluster: Cluster | None, root: str) -> tuple[str, str]:
    """-> (what is left, or '', the end of every node's log)."""
    left = log_tail = ""
    if cluster is not None:
        log_tail = cluster.log_tails()
        try:
            cluster.stop(say)
            ports = cluster.ports
            say(f"left behind: no process carries MTPU_BENCH_RUN="
                f"{cluster.marker} (scan of /proc), port "
                + (f"{ports[0]} refuses" if len(ports) == 1 else
                   f"{' '.join(map(str, ports))} refuse") + " connections")
        except LeftBehind as exc:
            left = str(exc)
    shutil.rmtree(root, ignore_errors=True)
    if os.path.exists(root):
        left = left or f"{root} could not be removed"
    else:
        say(f"left behind: {root} removed")
    return left, log_tail


def _say_run(cell: Cell, cluster: Cluster, root: str, seed: int) -> None:
    """The run's first line: what a watcher needs to find what it left.
    Of several nodes, the endpoint list that every one was given, and what
    each was given alone."""
    say(f"run marker={cluster.marker} root={root} "
        f"port={cluster.children[0].port} cell={cell.name} seed={seed}"
        + ("" if cell.nodes == 1 else
           f" nodes={cell.nodes} ports={','.join(map(str, cluster.ports))}"))
    if cell.nodes > 1:
        say("endpoints, the same on every node: " + " ".join(
            w for w in cluster.children[0].argv if "://" in w))
        for i, c in enumerate(cluster.children):
            say(f"node {i + 1}: " + " ".join(
                [w for w in c.argv[3:] if "://" not in w]
                + [f"{k}={v}" for k, v in sorted(c.share.items())]))


def _wait_ready(cell: Cell, cluster: Cluster, root: str, seed: int) -> float:
    """Seconds until every node answered, one deadline for all. A cluster
    in which a node lost the race for a port starts again on fresh ones."""
    t0 = time.monotonic()
    while True:
        try:
            for c in cluster.children:
                cl.wait_ready(c.host, cluster.exited,
                              max(1.0, 300 - (time.monotonic() - t0)))
            return time.monotonic() - t0
        except OSError as exc:
            if not (cluster.lost_a_bind() and cluster.start_again(say)):
                raise RunFailed(str(exc)) from None
            _say_run(cell, cluster, root, seed)


def _scrape(conns: list) -> list[dict[str, float]]:
    """Every node's samples, node by node."""
    return [cl.counters(s3.metrics()) for s3 in conns]


def _measure(cell: Cell, cluster: Cluster, root: str, seed: int,
             seconds: float, trace: bool, require_platform: str,
             t_start: float):
    """Set-up, the window, the check. -> (the result line, the checks)."""
    _say_run(cell, cluster, root, seed)
    load = Load(cell.traffic, seed, cluster.hosts, root, cell.drives, say)
    load.make_payloads()                       # while the servers start
    ready = _wait_ready(cell, cluster, root, seed)
    load.hosts = cluster.hosts                 # anew, had it to start again
    nodes = cluster.children
    conns = [cl.S3(c.host) for c in nodes]
    infos = [cl.backend_info(s3.metrics()) for s3 in conns]
    info = infos[0]
    say(f"server up after {ready:.1f}s on {info}"
        + ("" if len(nodes) == 1 else f", {len(nodes)} nodes"))
    if info["platform"] != require_platform:
        raise RunFailed(
            f"the server runs on platform={info['platform']} "
            f"device_kind={info['device_kind']!r}; this benchmark measures "
            f"on {require_platform} and never falls back")
    for i, other in enumerate(infos):
        if (other["platform"], other["device_kind"]) != (
                info["platform"], info["device_kind"]):
            raise RunFailed(f"node {i + 1} runs on {other}, node 1 on {info}")
    if sum(int(i["devices"]) for i in infos) < cell.chips:
        raise RunFailed(f"{[i['devices'] for i in infos]} device(s) found, "
                        f"the cell asks for {cell.chips}")
    load.setup()
    before_by_node = _scrape(conns)
    before = cl.add_up(before_by_node)

    # The traced slice: cued from here at the mix's share of the window
    # (`trace_cue_at`: the middle, unless the mix says otherwise), timed
    # and traced in the child, which takes a slice again where the device
    # did nothing in it, as long as the slice ends before the close. The
    # tracer then writes the one that holds the device (a minute for half
    # a million events) in the server's process while the window goes on:
    # a traced run's latencies are those answered before the cue.
    slices = [m["reader"] for m in cell.per_layer
              if "trace_slice_s" in m["reader"]]
    # Every chip writes its own events into its process's trace, so the
    # slice is cut by the chips of a process.
    per_node = cell.chips // cell.nodes
    slice_s = max((float(r["trace_slice_s"]) for r in slices),
                  default=0.0) / per_node if trace else 0.0
    tags = [c.tag for c in nodes]
    trace_dirs = [os.path.join(root, "trace" + t.replace(".", "/"))
                  for t in tags]
    done_paths = [os.path.join(root, f"trace_done{t}.json") for t in tags]

    def cue_trace(t0: float):
        def work():
            time.sleep(max(0.0, trace_cue_at(cell.traffic, t0, seconds)
                           - time.monotonic()))
            until = t0 + seconds - 0.5 + time.time() - time.monotonic()
            # every node at the same instant, each into a directory of
            # its own
            cluster.cue(lambda i: f"trace {trace_dirs[i]} {slice_s} "
                                  f"{until} {done_paths[i]}")
        if slice_s > 0:
            threading.Thread(target=work, daemon=True,
                             name="trace-cue").start()

    win = load.run_window(seconds, cue_trace)
    setup_s = win.t0 - t_start
    after_by_node = _scrape(conns)
    after = cl.add_up(after_by_node)
    for s3 in conns:
        s3.close()
    failed = [o for o in win.ops if not o.ok]
    say(f"window closed: {len(win.ops)} requests, {len(failed)} failed"
        + (f"; heal polls {win.heal_polls[-1]}" if win.heal_polls else ""))
    if win.heal_polls:
        _say_heal_sequence(win, seconds)
    for o in failed[:5]:
        say(f"  {o.kind} {o.key}: {o.error or o.wrong}")
    if win.generator_late_s:
        late = [readers.quantile(win.generator_late_s, q) * 1e3
                for q in (0.5, 0.95)]
        say(f"the generator sent its requests {late[0]:.1f} ms (median) and "
            f"{late[1]:.1f} ms (95th percentile) after they were due")

    dev_paths = [os.path.join(root, f"device{t}.json") for t in tags]
    cluster.cue(lambda i: f"device {dev_paths[i]}")
    span = None
    if slice_s > 0:
        # the children's wall clock, on this process's monotonic one
        off = time.time() - time.monotonic()
        for i, path in enumerate(done_paths):
            done = _wait_file(path, 200, f"trace cue{tags[i]}")
            took = (done["start"] - off, done["stop"] - off)
            span = span or took              # node 1's stands for the run
            say(f"traced{tags[i]} {took[1] - took[0]:.3f}s starting "
                f"{took[0] - win.t0:+.2f}s after the window opened and "
                f"ending {win.t0 + seconds - took[1]:+.2f}s before the "
                f"close, attempt {done['attempts']}; written in "
                f"{done['written'] - done['stop']:.1f}s")
    device = _devices(cell, [_wait_file(p, 60, f"device cue{tags[i]}")
                             for i, p in enumerate(dev_paths)], info)
    for i, (b, a) in enumerate(zip(before_by_node, after_by_node)):
        moved = (cl.dispatch_count(a, cell.engine)
                 - cl.dispatch_count(b, cell.engine))
        if len(nodes) > 1:
            say(f"node {i + 1}: {moved:.0f} {cell.engine} dispatches in the "
                "window")
        # a heal is node 1's work alone: its admin calls go there
        if moved <= 0 and (i == 0 or load.kind != "heal"):
            raise RunFailed(
                f"the {cell.engine} dispatch counter did not move in the "
                "window" + (f" on node {i + 1}" if len(nodes) > 1 else "")
                + ": the device did none of the work")

    checks = checking.check_window(cell, load, win, seed, cluster.hosts,
                                   root)
    cluster.stop(say)                          # the chips are free again

    ev = readers.Evidence(cell=cell, window=win, setup_s=setup_s,
                          before=before, after=after,
                          device_kind=device["kind"],
                          traced_from=trace_cue_at(cell.traffic, win.t0,
                                                   seconds)
                          if span else None)
    dev_line = {k: device[k] for k in ("platform", "kind", "count",
                                       "memory_peak_bytes")}
    breakdown = None
    if span and device["platform"] == "tpu":
        ev.trace = _reduce_traces(trace_dirs, device["kind"],
                                  span[1] - span[0], cluster.marker)
        full = max(ev.trace["devices"], key=lambda d: d["busy_s"])
        dev_line["busy_s"] = ev.trace["busy_s_mean"]
        dev_line["window_s"] = ev.trace["window_s"]
        breakdown = {"device_ops": full["device_ops"],
                     "idle_gaps": full["idle_gaps"]}
    metrics = {}
    for entry in cell.per_layer if trace else cell.end_to_end:
        value = readers.read(ev, entry["reader"])
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    result = {"correct": checks.correct, **_attempted(win, failed),
              "metrics": metrics, "device": dev_line}
    if breakdown:
        result["breakdown"] = breakdown
    result["checks"] = checks.rows             # comes last
    return result, checks


def _devices(cell: Cell, docs: list[dict], info: dict) -> dict:
    """What the nodes' JAX says of their devices, as one device line's
    worth: the count is the total, the peak the fullest device's. Every
    node of several has to hold its share of the cell's chips and, on a
    TPU, chips that no other node holds: shown by the device files each
    process has open, since each numbers its chips from 0."""
    held: dict[str, int] = {}
    for doc in docs:
        if doc["platform"] != info["platform"]:
            raise RunFailed(f"JAX in the child reports {doc}, the server's "
                            f"metrics {info}")
    for i, doc in enumerate(docs if len(docs) > 1 else []):
        say(f"node {i + 1}: devices ids={doc['ids']} coords={doc['coords']} "
            f"chip files {doc['chip_files']} peak bytes "
            f"{doc['memory_peak_bytes_per_device']}")
        share = cell.chips // cell.nodes
        if doc["count"] != share or len(doc["ids"]) != share:
            raise RunFailed(
                f"node {i + 1} sees {doc['count']} device(s) "
                f"({len(doc['ids'])} its own), its share of the cell's "
                f"{cell.chips} chips is {share}")
        if doc["platform"] != "tpu":
            continue
        if len(doc["chip_files"]) != share:
            raise RunFailed(
                f"node {i + 1} holds the device files {doc['chip_files']} "
                f"open, not {share}: which chip it runs on cannot be shown")
        for path in doc["chip_files"]:
            if path in held:
                raise RunFailed(
                    f"nodes {held[path] + 1} and {i + 1} both hold {path}: "
                    "a chip belongs to one process")
            held[path] = i
    peaks = [p for d in docs for p in d["memory_peak_bytes_per_device"]]
    return {"platform": docs[0]["platform"], "kind": docs[0]["kind"],
            "count": sum(d["count"] for d in docs),
            "memory_peak_bytes": max(peaks),
            "memory_peak_bytes_per_device": peaks}


def _say_heal_sequence(win: Window, seconds: float) -> None:
    """How long the backlog lasted, and whether a slow run was slow all
    along or stood still somewhere: the polls, by steps of 5 s."""
    polls = [p for p in win.heal_polls if p[0] <= win.end + 1e-6]
    say(f"heal sequence: {polls[-1][1]} objects reported healed in "
        f"{win.end - win.t0:.2f}s of the window's {seconds:.0f}s ("
        + ("the backlog ran dry" if win.end < win.t0 + seconds - 1e-3
           else "still running at the close") + ")")
    steps = [max((n for t, n, _ in polls if t <= win.t0 + s), default=0)
             for s in range(0, int(seconds) + 5, 5)]
    rises = [t for (t, n, _), (_, m, _) in zip(polls[1:], polls) if n > m]
    waits = [b - a for a, b in zip([win.t0] + rises, rises)]
    say("heal sequence: objects healed in each 5 s "
        f"{[b - a for a, b in zip(steps, steps[1:])]}; the longest wait "
        f"for a result {max(waits, default=0):.2f}s")


def _attempted(win: Window, failed: list) -> dict:
    if win.heal_polls:
        _, healed, heal_failed = win.heal_polls[-1]
        return {"attempted": int(healed + heal_failed),
                "failed": int(heal_failed)}
    return {"attempted": len(win.ops), "failed": len(failed)}


def _reduce_traces(trace_dirs: list[str], device_kind: str, slice_s: float,
                   marker: str) -> dict:
    """The summary of every node's trace, as one: each made by a child of
    its own that is pinned to the CPU and carries the run's marker; the
    devices are the union over the nodes (a process traces its own chips
    alone), the window the longest. A node whose slices all fell between
    two dispatches holds no device's operation; the run fails only where
    none does."""
    names = roofline.peaks_for(device_kind)["trace"]
    merged: dict = {"devices": [], "window_s": 0.0,
                    "devices_per_process": 0}
    silent = 0
    reducers = [subprocess.Popen(                   # side by side
        [sys.executable, os.path.join(HARNESS_DIR, "trace_reduce.py"),
         trace_dir, json.dumps(names), str(slice_s)],
        env=dict(os.environ, JAX_PLATFORMS="cpu", MTPU_BENCH_RUN=marker),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for trace_dir in trace_dirs]
    for i, proc in enumerate(reducers):
        node = f"node {i + 1} " if len(trace_dirs) > 1 else ""
        out, err = proc.communicate(timeout=200)
        if proc.returncode != 0:
            raise RunFailed(f"trace reduction failed:\n{err[-2000:]}")
        for line in err.splitlines()[-40:]:
            say(f"trace_reduce: {node}{line[:300]}")
        summary = json.loads(out.strip().splitlines()[-1])
        if not summary.get("devices"):
            say(f"trace {node}holds no operation of a device")
            silent += 1
            continue
        for d in summary["devices"]:
            say(f"trace {node}{d['name']}: busy {d['busy_s']:.4f}s of "
                f"{summary['window_s']:.4f}s, {d['module_events']} module "
                f"and {d['op_events']} op events")
            if node:                    # every process calls its chip 0
                d["name"] = f"n{i + 1}{d['name']}"
        merged["devices"] += summary["devices"]
        merged["window_s"] = max(merged["window_s"], summary["window_s"])
        merged["devices_per_process"] = max(merged["devices_per_process"],
                                            len(summary["devices"]))
    if not merged["devices"]:
        raise RunFailed("the traced slice holds no operation of the device: "
                        "no busy time to report")
    # a node without a device's operation in its slice was idle all of it
    busy = ([d["busy_s"] for d in merged["devices"]]
            + [0.0] * silent * merged["devices_per_process"])
    merged["busy_s_mean"] = sum(busy) / len(busy)
    return merged


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description="one run of one cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        return run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace))
    except Exception as exc:  # noqa: BLE001 - no result line, code 1
        say(f"FAILED: {type(exc).__name__}: {exc}")
        return 1
