"""The per-layer metrics that read the span plane (ISSUE 27), through the
harness on the CPU: a traced run over a copy of the data root reports
every new `.ops` and `.heal` metric as a finite number, and an
operation's phases add up under the object layer's span.

    JAX_PLATFORMS=cpu python -m pytest tests/benchmark_gate/test_layer_spans.py -q

The copy, the cells added to it and the way a run is driven are
`test_benchmark.py`'s: its fixture appends `tiny-put` to every `.ops`
metric's cells and `tiny-heal` to every `.heal` metric's. Nothing is
timed against a limit."""

from __future__ import annotations

import json
import math
import os

import test_benchmark as gate
from test_benchmark import copy  # noqa: F401 - the module's fixture

PHASES = ("body_read", "admission", "object", "commit", "stream",
          "device_h2d", "device_call", "device_wait")


def _new_metrics(family: str) -> set[str]:
    with open(os.path.join(gate.REPO, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    names = {m["name"] for m in per_layer
             if m["name"].endswith("." + family)
             and ("_ms_per_op." in m["name"]
                  or m["name"].startswith("retraces_per_op."))}
    assert names, family
    return names


def _traced_line(copy, cell: str, seconds: int) -> dict:  # noqa: F811
    rc, out, err = gate._finish(gate._drive(copy, cell, seconds=seconds,
                                            trace=1))
    assert rc == 0, err[-3000:]
    line = gate._last_json(out)
    assert line["correct"] is True and line["failed"] == 0, err[-3000:]
    gate._assert_nothing_left(err)
    return {k: v["value"] for k, v in line["metrics"].items()}


def test_a_traced_put_run_reports_every_ops_phase(copy):  # noqa: F811
    want = _new_metrics("ops")
    assert want == ({f"{p}_ms_per_op.ops" for p in PHASES}
                    | {"retraces_per_op.ops"})
    got = _traced_line(copy, "tiny-put", 3)
    assert want <= set(got), want - set(got)
    for name in want:
        assert math.isfinite(got[name]) and got[name] >= 0, (name, got)
    # every PUT reads its body, passes the governor, streams and commits
    for p in ("body_read", "object", "commit", "stream", "device_call",
              "device_wait"):
        assert got[f"{p}_ms_per_op.ops"] > 0, p
    # the phases are children of the object layer's span: they add up
    # under it, and the remainder is its self time
    parts = sum(got[f"{p}_ms_per_op.ops"]
                for p in ("admission", "stream", "commit"))
    assert got["object_ms_per_op.ops"] >= parts, got
    # the device phases lie inside the stream
    device = sum(got[f"device_{p}_ms_per_op.ops"]
                 for p in ("h2d", "call", "wait"))
    assert got["stream_ms_per_op.ops"] >= device, got
    # the window's PUTs run on functions the warm-up traced
    assert got["retraces_per_op.ops"] == 0, got
    # the metrics that time the same layers from outside stay
    assert got["dispatches_per_op.ops"] == 1.0
    assert got["op_p50_ms.ops"] >= got["object_ms_per_op.ops"] * 0.5


def test_a_traced_heal_run_reports_every_heal_phase(copy):  # noqa: F811
    want = _new_metrics("heal")
    assert want == ({f"{p}_ms_per_op.heal" for p in PHASES[2:]}
                    | {"retraces_per_op.heal"})
    got = _traced_line(copy, "tiny-heal", 8)
    assert want <= set(got), want - set(got)
    for name in want:
        assert math.isfinite(got[name]) and got[name] >= 0, (name, got)
    parts = got["stream_ms_per_op.heal"] + got["commit_ms_per_op.heal"]
    assert got["object_ms_per_op.heal"] >= parts > 0, got
    assert got["device_call_ms_per_op.heal"] > 0, got
    assert got["dispatches_per_op.heal"] == 1.0, got
    # the warm-up healed an object of every rotation: the window's heals
    # run on functions it traced
    assert got["retraces_per_op.heal"] == 0, got
