"""The small set of generic readers that metrics are made of. A metric is
a data file (`end_to_end/<name>.json` or `layer_metrics/<name>.json`) that
names one reader and gives its parameters; a later PR adds a metric over a
new counter, span or trace pattern by adding a file.

A reader takes the run's `Evidence` and its parameters and returns the
value, or None where it finds nothing to read: the harness then leaves the
metric out of the line. It never returns 0 for a share of a roofline.

A request rate is all the work answered in the window over all its
seconds, and a quantile is over every request of the window that was
answered rightly; nothing is a median of chunks.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, field

from . import roofline, trace_reduce
from .traffic import Window


@dataclass
class Evidence:
    """What one run left for the readers."""

    cell: object
    window: Window
    setup_s: float
    before: dict[str, float] = field(default_factory=dict)   # counters
    after: dict[str, float] = field(default_factory=dict)
    trace: dict | None = None          # trace_reduce's summary
    traced_from: float | None = None   # when the tracer was started
    device_kind: str = ""
    peaks_path: str | None = None


def quantile(values: list[float], q: float) -> float | None:
    """Nearest rank: the smallest value with at least q of the sample at
    or below it."""
    if not values:
        return None
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q * len(ordered)) - 1)]


def _kinds(params: dict):
    return set(params["ops"]) if params.get("ops") else None


def _done_in_window(ev: Evidence, params: dict):
    kinds = _kinds(params)
    return [o for o in ev.window.in_window()
            if not kinds or o.kind in kinds]


def _window_s(ev: Evidence) -> float:
    return ev.window.end - ev.window.t0


def bytes_per_s(ev: Evidence, p: dict):
    """User bytes of requests answered in the window / window seconds."""
    if ev.window.heal_polls:
        return None
    done = _done_in_window(ev, p)
    return sum(o.size for o in done) / _window_s(ev) / p.get("scale", 1)


def ops_per_s(ev: Evidence, p: dict):
    if ev.window.heal_polls:
        return None
    return len(_done_in_window(ev, p)) / _window_s(ev)


def latency_quantile_ms(ev: Evidence, p: dict):
    """Over every request sent in the window and answered rightly, those
    answered after the close too; from when it was due. In a run that
    traces, over those answered before the tracer was started: it writes
    its file in the server's process, which slows the requests after."""
    kinds = _kinds(p)
    lat = [o.latency * 1e3 for o in ev.window.ops
           if o.ok and (not kinds or o.kind in kinds)
           and (ev.traced_from is None or o.done <= ev.traced_from)]
    return quantile(lat, float(p["q"]))


def healed_in_window(ev: Evidence) -> tuple[float, float] | None:
    """(objects healed in the window, its seconds). The seconds run from
    the heal request to the window's last reading: the close, or the
    sequence's end where the backlog ran dry first. The objects are those
    whose result arrived by then, and of the one in work at the close the
    share of its time that lay in the window: from the result before it to
    the close, over from that result to its own, which the run waits for.
    One that never comes is credited nothing."""
    polls = ev.window.heal_polls
    end = ev.window.end
    inside = [p for p in polls if p[0] <= end + 1e-6]
    if not inside:
        return None
    n = inside[-1][1]
    # a result arrived with the first reading that showed its count
    t_n = next(t for t, count, _ in inside if count == n)
    t_next = next((t for t, count, _ in polls if t > end and count > n),
                  None)
    share = 0.0 if t_next is None else (end - t_n) / (t_next - t_n)
    return n + share, end - ev.window.t0


def heal_bytes_per_s(ev: Evidence, p: dict):
    """User bytes healed in the window / the window's seconds: all the
    work over all the time, so a sequence that stalls reads lower. See
    `healed_in_window` for the object in work at the close."""
    got = healed_in_window(ev)
    if got is None or got[0] <= 0 or got[1] <= 0:
        return None
    return got[0] * ev.window.heal_object_size / got[1] / p.get("scale", 1)


def setup_s(ev: Evidence, p: dict):
    return ev.setup_s


def _delta(ev: Evidence, pattern: str) -> float | None:
    rx = re.compile(pattern)
    hit = [k for k in ev.after if rx.search(k)]
    if not hit:
        return None
    return sum(ev.after[k] - ev.before.get(k, 0.0) for k in hit)


def _ops_done(ev: Evidence, p: dict) -> float:
    """Operations the window finished (healed objects for a heal mix),
    those in flight at the close counted in: the counters saw them."""
    if ev.window.heal_polls:
        return float(ev.window.heal_polls[-1][1])
    kinds = _kinds(p)
    return float(len([o for o in ev.window.ops
                      if o.ok and (not kinds or o.kind in kinds)]))


def counter_delta_per_op(ev: Evidence, p: dict):
    d, n = _delta(ev, p["pattern"]), _ops_done(ev, p)
    return None if d is None or n <= 0 else d / n


def counter_delta_per_s(ev: Evidence, p: dict):
    d = _delta(ev, p["pattern"])
    return None if d is None else d / _window_s(ev)


def counter_ratio(ev: Evidence, p: dict):
    num, den = _delta(ev, p["pattern"]), _delta(ev, p["over"])
    if num is None or not den:
        return None
    return num / den * p.get("scale", 1)


def _fullest(ev: Evidence) -> dict | None:
    if not ev.trace or not ev.trace.get("devices"):
        return None
    return max(ev.trace["devices"], key=lambda d: d["busy_s"])


def idle_share_pct(ev: Evidence, p: dict):
    """1 - busy/slice on the fullest device, from the trace."""
    dev = _fullest(ev)
    if dev is None:
        return None
    share = trace_reduce.idle_share(dev["busy_s"], ev.trace["window_s"])
    return None if share is None else 100.0 * share


def trace_busy_pct(ev: Evidence, p: dict):
    """Device seconds of the operations whose name matches `pattern`, as a
    share of the device's busy time."""
    dev = _fullest(ev)
    if dev is None or dev["busy_s"] <= 0:
        return None
    rx = re.compile(p["pattern"])
    hit = [s for name, s in dev["device_ops"] if rx.search(name)]
    return 100.0 * sum(hit) / dev["busy_s"] if hit else None


# a slice that the device's executions fill to this share has no gaps:
# the device is saturated, and an execution may be longer than the slice
SATURATED = 0.95


def roofline_share_pct(ev: Evidence, p: dict):
    """Least time the chip needs for the erasure work of one dispatch /
    the device time of one dispatch.

    The work is counted from the client's side, so it reads the same
    whatever engine or kernel does it: user bytes of the operations the
    run finished / the rise of the dispatch counter (`dispatches`) over
    the same operations. The device time comes from the traced slice. A
    slice with gaps holds whole executions, and a dispatch takes what one
    of them takes (`trace_reduce.execution_ns`), however many of them the
    slice happened to catch. A slice without gaps may hold only pieces of
    executions longer than itself; the device is then busy that share of
    the whole window, and a dispatch takes those seconds / the dispatches
    of the operations finished in the window."""
    dev = _fullest(ev)
    if dev is None or dev["busy_s"] <= 0:
        return None
    dispatches = _delta(ev, p["dispatches"])
    ops = _ops_done(ev, p)
    if not dispatches or ops <= 0:
        return None
    if ev.window.heal_polls:
        nbytes = ops * ev.window.heal_object_size
    else:
        kinds = _kinds(p)
        nbytes = sum(o.size for o in ev.window.ops
                     if o.ok and (not kinds or o.kind in kinds))
    busy_share = dev["busy_s"] / ev.trace["window_s"]
    if busy_share >= SATURATED:
        in_window = (healed_in_window(ev)[0] if ev.window.heal_polls
                     else len(_done_in_window(ev, p)))
        if in_window <= 0:
            return None
        per_dispatch_s = (busy_share * _window_s(ev) / in_window
                          * ops / dispatches)
    else:
        per_dispatch_s = dev["execution_s"]
    cell = ev.cell
    outputs = cell.m if p["work"] == "encode" else int(p["outputs"])
    work = roofline.coding_work(nbytes / dispatches, cell.k, outputs,
                                cell.block_size)
    peaks = roofline.peaks_for(ev.device_kind, ev.peaks_path)
    least, _ = roofline.least_seconds(work, peaks)
    # every chip of the process that dispatched works on each dispatch
    # (all the cell's, where the deployment is one node); the fullest sets
    # the share
    chips = ev.trace.get("devices_per_process") or len(ev.trace["devices"])
    return 100.0 * least / chips / per_dispatch_s


READERS = {f.__name__: f for f in (
    bytes_per_s, ops_per_s, latency_quantile_ms, heal_bytes_per_s, setup_s,
    counter_delta_per_op, counter_delta_per_s, counter_ratio,
    idle_share_pct, trace_busy_pct, roofline_share_pct)}


def read(ev: Evidence, reader: dict):
    name = reader["reader"]
    if name not in READERS:
        raise KeyError(f"no reader {name!r}; there are {sorted(READERS)}")
    return READERS[name](ev, reader)
