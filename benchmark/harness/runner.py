"""One run of one cell: start the server child, set up, measure for
`--seconds`, check what the window produced, stop everything, print one
JSON line.

This is the client process. It never imports jax: the one child that owns
the chip is the server (`serve_child.py`), and the trace it writes is read
by a second child, pinned to the CPU, once the server has gone.
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
from .child import Child, LeftBehind, become_subreaper
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
    child = None
    result = checks = None
    problem: BaseException | None = None
    try:
        # from this thread, which outlives the child (see Child)
        child = Child(root, cell.drives, env, fault)
        say(f"run marker={child.marker} root={root} port={child.port} "
            f"cell={cell.name} seed={seed}")
        result, checks = _measure(cell, child, root, seed, float(seconds),
                                  trace, require_platform, t_start)
    except BaseException as exc:  # noqa: BLE001 - cleaned up, then reported
        problem = exc
    finally:
        # every exit path comes through here, and nothing cuts it short
        signal.alarm(0)
        for s in old:
            signal.signal(s, signal.SIG_IGN)
        left, log_tail = _leave_nothing(child, root)
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


def _leave_nothing(child: Child | None, root: str) -> tuple[str, str]:
    """-> (what is left, or '', the end of the server's log)."""
    left = log_tail = ""
    if child is not None:
        log_tail = child.log_tail()
        try:
            child.stop(say)
            say(f"left behind: no process carries MTPU_BENCH_RUN="
                f"{child.marker} (scan of /proc), port {child.port} "
                "refuses connections")
        except LeftBehind as exc:
            left = str(exc)
    shutil.rmtree(root, ignore_errors=True)
    if os.path.exists(root):
        left = left or f"{root} could not be removed"
    else:
        say(f"left behind: {root} removed")
    return left, log_tail


def _measure(cell: Cell, child: Child, root: str, seed: int, seconds: float,
             trace: bool, require_platform: str, t_start: float):
    """Set-up, the window, the check. -> (the result line, the checks)."""
    load = Load(cell.traffic, seed, child.host, root, cell.drives, say)
    load.make_payloads()                       # while the server starts
    try:
        ready = cl.wait_ready(child.host, child.proc, 300)
    except OSError as exc:
        raise RunFailed(str(exc)) from None
    s3 = cl.S3(child.host)
    info = cl.backend_info(s3.metrics())
    say(f"server up after {ready:.1f}s on {info}")
    if info["platform"] != require_platform:
        raise RunFailed(
            f"the server runs on platform={info['platform']} "
            f"device_kind={info['device_kind']!r}; this benchmark measures "
            f"on {require_platform} and never falls back")
    if int(info["devices"]) < cell.chips:
        raise RunFailed(f"{info['devices']} device(s) found, the cell asks "
                        f"for {cell.chips}")
    load.setup()
    before = cl.counters(s3.metrics())

    # The traced slice: cued from here at the mix's share of the window
    # (`trace_cue_at`: the middle, unless the mix says otherwise), timed
    # and traced in the child, which takes a slice again where the device
    # did nothing in it, as long as the slice ends before the close. The
    # tracer then writes the one that holds the device (a minute for half
    # a million events) in the server's process while the window goes on:
    # a traced run's latencies are those answered before the cue.
    slices = [m["reader"] for m in cell.per_layer
              if "trace_slice_s" in m["reader"]]
    # Every chip writes its own events, so the slice is cut by their number.
    slice_s = max((float(r["trace_slice_s"]) for r in slices),
                  default=0.0) / cell.chips if trace else 0.0
    trace_dir = os.path.join(root, "trace")
    done_path = os.path.join(root, "trace_done.json")

    def cue_trace(t0: float):
        def work():
            time.sleep(max(0.0, trace_cue_at(cell.traffic, t0, seconds)
                           - time.monotonic()))
            until = t0 + seconds - 0.5 + time.time() - time.monotonic()
            child.cue(f"trace {trace_dir} {slice_s} {until} {done_path}")
        if slice_s > 0:
            threading.Thread(target=work, daemon=True,
                             name="trace-cue").start()

    win = load.run_window(seconds, cue_trace)
    setup_s = win.t0 - t_start
    after = cl.counters(s3.metrics())
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

    dev_path = os.path.join(root, "device.json")
    child.cue(f"device {dev_path}")
    span = None
    if slice_s > 0:
        done = _wait_file(done_path, 200, "trace cue")
        # the child's wall clock, on this process's monotonic one
        off = time.time() - time.monotonic()
        span = (done["start"] - off, done["stop"] - off)
        say(f"traced {span[1] - span[0]:.3f}s starting "
            f"{span[0] - win.t0:+.2f}s after the window opened and ending "
            f"{win.t0 + seconds - span[1]:+.2f}s before the close, attempt "
            f"{done['attempts']}; written in "
            f"{done['written'] - done['stop']:.1f}s")
    device = _wait_file(dev_path, 60, "device cue")
    if device["platform"] != info["platform"]:
        raise RunFailed(f"JAX in the child reports {device}, the server's "
                        f"metrics {info}")
    moved = (cl.dispatch_count(after, cell.engine)
             - cl.dispatch_count(before, cell.engine))
    if moved <= 0:
        raise RunFailed(f"the {cell.engine} dispatch counter did not move "
                        "in the window: the device did none of the work")

    checks = checking.check_window(cell, load, win, seed, child.host, root)
    child.stop(say)                            # the chip is free again

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
        ev.trace = _reduce_trace(trace_dir, device["kind"],
                                 span[1] - span[0], child.marker)
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


def _reduce_trace(trace_dir: str, device_kind: str, slice_s: float,
                  marker: str) -> dict:
    """The trace's summary, made by a child of its own that is pinned to
    the CPU and carries the run's marker."""
    names = roofline.peaks_for(device_kind)["trace"]
    r = subprocess.run(
        [sys.executable, os.path.join(HARNESS_DIR, "trace_reduce.py"),
         trace_dir, json.dumps(names), str(slice_s)],
        env=dict(os.environ, JAX_PLATFORMS="cpu", MTPU_BENCH_RUN=marker),
        capture_output=True, text=True, timeout=200)
    if r.returncode != 0:
        raise RunFailed(f"trace reduction failed:\n{r.stderr[-2000:]}")
    for line in r.stderr.splitlines()[-40:]:
        say(f"trace_reduce: {line[:300]}")
    summary = json.loads(r.stdout.strip().splitlines()[-1])
    if not summary.get("devices"):
        raise RunFailed("the traced slice holds no operation of the device: "
                        "no busy time to report")
    for d in summary["devices"]:
        say(f"trace {d['name']}: busy {d['busy_s']:.4f}s of "
            f"{summary['window_s']:.4f}s, {d['module_events']} module and "
            f"{d['op_events']} op events")
    return summary


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
