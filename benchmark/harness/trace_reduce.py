"""From a profiler trace to numbers: busy union, idle share, the device
operations that took most time, the longest idle gaps.

The arithmetic works on plain tuples `(name, start_ns, duration_ns)`, so
that it is checked on a hand-built trace. `load_xplane` turns the
`.xplane.pb` that `jax.profiler` wrote into that form with jaxlib's
reader; it runs in a process of its own, pinned to the CPU, after the
server child has gone (this module's `__main__`), so the benchmark's
client process never imports jax.

Busy time comes from the module-level line of a device's plane (one event
per execution of a compiled program); the op-level line, which holds some
hundred thousand events per dispatch of the digest scan, is only summed by
name for `breakdown`.
"""

from __future__ import annotations

import glob
import json
import os
import sys

Event = tuple[str, int, int]          # name, start_ns, duration_ns


def busy_union_ns(events: list[Event]) -> int:
    """Nanoseconds covered by at least one of the events."""
    total, end = 0, None
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if end is None or start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def idle_share(busy_s: float, window_s: float) -> float | None:
    """1 - busy/window as a share of 1; None where there is no window."""
    if window_s <= 0:
        return None
    return 1.0 - busy_s / window_s


def execution_ns(modules: list[Event]) -> float | None:
    """Device time of one execution of a compiled program: the mean over
    the module events that are at least half as long as the longest. A
    trace clips the executions that straddle its ends, and those pieces
    are shorter than the whole ones; a program's own executions differ by
    a few percent."""
    if not modules:
        return None
    longest = max(dur for _, _, dur in modules)
    whole = [dur for _, _, dur in modules if 2 * dur >= longest]
    return sum(whole) / len(whole)


def top_ops(sums_ns: dict[str, int], n: int = 10) -> list[list]:
    """Summed by the whole name the trace prints (an HLO instruction, a
    kilobyte long for a scan), labelled by its first 96 characters."""
    rows = sorted(sums_ns.items(), key=lambda kv: -kv[1])[:n]
    return [[name[:96], ns / 1e9] for name, ns in rows]


def gaps(events: list[Event], start_ns: int, stop_ns: int) -> list[tuple[int,
                                                                          int]]:
    """(start, duration) of every stretch of [start_ns, stop_ns] that no
    event covers."""
    out, end = [], start_ns
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if start > end:
            out.append((end, min(start, stop_ns) - end))
        end = max(end, start + dur)
        if end >= stop_ns:
            break
    if end < stop_ns:
        out.append((end, stop_ns - end))
    return [g for g in out if g[1] > 0]


def label_gap(gap: tuple[int, int], host_events: list[Event]) -> str:
    """What the host was doing in the gap: the host span that covers most
    of it. Until the program carries spans on the profiler's clock this
    is whatever the runtime itself traces, or nothing."""
    g0, g1 = gap[0], gap[0] + gap[1]
    best, best_ns = "unattributed", 0
    for name, start, dur in host_events:
        ov = min(g1, start + dur) - max(g0, start)
        if ov > best_ns:
            best, best_ns = name, ov
    return best if best_ns * 2 >= gap[1] else "unattributed"


def longest_gaps(events: list[Event], start_ns: int, stop_ns: int,
                 host_events: list[Event], n: int = 10) -> list[list]:
    """The idle time by what the host was doing, longest first: gaps with
    one label are summed."""
    sums: dict[str, int] = {}
    for gap in gaps(events, start_ns, stop_ns):
        label = label_gap(gap, host_events)
        sums[label] = sums.get(label, 0) + gap[1]
    rows = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in rows]


def reduce_trace(trace: dict, names: dict, slice_s: float = 0.0) -> dict:
    """trace: {"planes": [{"name", "lines": [{"name", "events": [Event]} |
    {"name", "sums_ns": {...}, "count": n}]}]}. names: the device's entry
    of peaks.json under "trace". -> the summary the readers take."""
    devices, host_events = [], []
    for plane in trace["planes"]:
        if plane["name"].startswith(names["plane_prefix"]):
            continue
        for line in plane["lines"]:
            host_events += [e for e in line.get("events", [])
                            if e[2] >= 100_000]
    for plane in trace["planes"]:
        if not plane["name"].startswith(names["plane_prefix"]):
            continue
        modules, op_sums, n_ops = [], {}, 0
        for line in plane["lines"]:
            if line["name"] == names["module_line"]:
                modules = list(line["events"])
            elif line["name"] == names["op_line"]:
                if "sums_ns" in line:
                    op_sums, n_ops = line["sums_ns"], line["count"]
                else:
                    for name, _, dur in line["events"]:
                        op_sums[name] = op_sums.get(name, 0) + dur
                    n_ops = len(line["events"])
        if not modules:
            continue
        first = min(e[1] for e in modules)
        last = max(e[1] + e[2] for e in modules)
        devices.append({"name": plane["name"], "modules": modules,
                        "first": first, "last": last,
                        "busy_ns": busy_union_ns(modules),
                        "op_sums": op_sums, "op_events": n_ops,
                        "module_events": len(modules)})
    if not devices:
        return {"devices": []}
    first = min(d["first"] for d in devices)
    last = max(d["last"] for d in devices)
    window_ns = max(last - first, int(slice_s * 1e9))
    stop_ns = first + window_ns
    out = {"window_s": window_ns / 1e9, "devices": []}
    for d in devices:
        out["devices"].append({
            "name": d["name"], "busy_s": d["busy_ns"] / 1e9,
            "execution_s": execution_ns(d["modules"]) / 1e9,
            "module_events": d["module_events"], "op_events": d["op_events"],
            "device_ops": top_ops(d["op_sums"] or _module_sums(d["modules"])),
            "idle_gaps": longest_gaps(d["modules"], first, stop_ns,
                                      host_events)})
    fullest = max(out["devices"], key=lambda d: d["busy_s"])
    out["fullest"] = fullest["name"]
    out["busy_s_mean"] = (sum(d["busy_s"] for d in out["devices"])
                          / len(out["devices"]))
    return out


def _module_sums(modules: list[Event]) -> dict[str, int]:
    sums: dict[str, int] = {}
    for name, _, dur in modules:
        sums[name] = sums.get(name, 0) + dur
    return sums


def load_xplane(path: str, names: dict) -> dict:
    """Read an .xplane.pb with jaxlib's reader. The op-level line is summed
    by name as it is read; it is too long to keep."""
    try:
        from jaxlib._profile_data import ProfileData
    except ImportError:
        from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        is_dev = plane.name.startswith(names["plane_prefix"])
        lines = []
        for line in plane.lines:
            if is_dev and line.name == names["op_line"]:
                sums: dict[str, int] = {}
                count = 0
                for ev in line.events:
                    sums[ev.name] = sums.get(ev.name, 0) + int(ev.duration_ns)
                    count += 1
                lines.append({"name": line.name, "sums_ns": sums,
                              "count": count})
            elif is_dev and line.name != names["module_line"]:
                continue
            else:
                lines.append({"name": line.name, "events": [
                    (ev.name, int(ev.start_ns), int(ev.duration_ns))
                    for ev in line.events
                    if is_dev or ev.duration_ns >= 100_000]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def main(argv: list[str]) -> int:
    """trace_reduce.py <trace dir> <names as JSON> <slice seconds>: prints
    the summary as one JSON line."""
    trace_dir, names, slice_s = argv[0], json.loads(argv[1]), float(argv[2])
    path = find_xplane(trace_dir)
    if path is None:
        print(json.dumps({"devices": [], "error": "no .xplane.pb"}))
        return 0
    trace = load_xplane(path, names)
    for plane in trace["planes"]:       # what the trace holds, for a reader
        print(f"plane {plane['name']}: " + ", ".join(
            f"{ln['name']} ({ln.get('count', len(ln.get('events', [])))})"
            for ln in plane["lines"]), file=sys.stderr)
    for plane in trace["planes"]:       # the programs' executions, in order
        for ln in plane["lines"]:
            if (plane["name"].startswith(names["plane_prefix"])
                    and ln["name"] == names["module_line"]):
                first = min((e[1] for e in ln["events"]), default=0)
                for name, start, dur in sorted(ln["events"],
                                               key=lambda e: e[1])[:40]:
                    print(f"module {plane['name']} +{(start - first) / 1e6:9.3f}"
                          f" ms {dur / 1e6:9.3f} ms {name[:80]}",
                          file=sys.stderr)
    summary = reduce_trace(trace, names, slice_s)
    summary["xplane_bytes"] = os.path.getsize(path)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
