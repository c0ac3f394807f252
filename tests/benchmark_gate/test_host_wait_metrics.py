"""The per-layer metrics of the host's waits, and the proof that
`span_seconds`'s new `label` moves no metric the benchmark had.

The scrapes are rendered by the program's own span plane and registry from
one set of records, once as they render now (an `rpc` and a `fanout`
span's series keep a bounded label) and once with the labels taken out
again, as a program without them renders the same records. Every
per-layer metric that was there reads the same on both; each new one reads
its hand-computed value on the first and nothing on the second.

    JAX_PLATFORMS=cpu python -m pytest tests/benchmark_gate/test_host_wait_metrics.py -q
"""

from __future__ import annotations

import json
import os
import re

import pytest
import test_benchmark as gate

from benchmark.harness import client as cl
from benchmark.harness import readers

METRICS = os.path.join(gate.REPO, "benchmark", "layer_metrics")

# this file's metrics; the first five are entries of BENCHMARK.json, the
# last three data files that no cell lists yet (PERF.md section 7)
NEW = ("interp_wait_ms.put", "interp_wait_ms.ops", "interp_wait_ms.heal",
       "drive_lock_ms_per_put.put", "drive_lock_ms_per_put.ops",
       "interp_wait_ms.get", "rpc_create_file_ms_per_op.cluster",
       "shard_read_wait_ms_per_req.get")
OLD = sorted(name[:-len(".json")] for name in os.listdir(METRICS)
             if name.endswith(".json") and name[:-len(".json")] not in NEW)

PUTS, GETS, HEALS = 4, 3, 2
# a PUT's and a GET's spans: (kind, label, seconds), each a binary fraction
PUT_SPANS = [
    ("body-read", "", 0.03125), ("admission", "put", 0.0009765625),
    ("lock", "write", 0.0078125), ("object", "put", 0.25),
    ("stream", "batched_pipelined", 0.125), ("stage", "put/md5", 0.03125),
    ("stage-wait", "put/pack", 0.015625), ("device-h2d", "enc", 0.0078125),
    ("device-call", "enc", 0.00390625), ("device-wait", "enc", 0.001953125),
    ("fanout", "quorum-wait", 0.0625), ("fanout", "straggler-detach #3", 0.0),
    ("fanout", "all", 0.015625), ("commit", "", 0.046875),
    ("disk", "rename_data:d1", 0.0078125),
    ("rpc", "storage:create_file", 0.125), ("rpc", "storage:create_file", 0.0625),
    ("rpc", "storage:rename_data", 0.03125), ("rpc", "lock:lock", 0.001953125),
    ("rpc", "peer:no_such_method", 0.5),
]
GET_SPANS = [
    ("object", "get", 0.1875), ("stream", "fused", 0.125),
    ("fanout", "all", 0.03125), ("fanout", "shard-read-wait", 0.0390625),
    ("fanout", "shard-read-wait", 0.0234375), ("fanout", "hedge #9", 0.0),
    ("device-h2d", "rec", 0.00390625), ("device-call", "rec", 0.0009765625),
    ("device-wait", "rec", 0.001953125), ("disk", "read_version:d3", 0.0078125),
    ("readtier", "fallback", 0.0009765625),
]
HEAL_SPANS = [("object", "heal", 0.0625), ("stream", "heal_fused", 0.03125),
              ("device-h2d", "rec", 0.0009765625),
              ("device-call", "rec", 0.001953125),
              ("device-wait", "rec", 0.0009765625),
              ("fanout", "all", 0.015625), ("commit", "", 0.00390625)]
PROBE = [0.0001220703125, 0.00048828125, 0.0029296875]      # seconds late
LOCK_WAITS = {"rename_data": 0.375, "write_metadata": 0.0625}
# the counters other metrics read, as the program names them
OTHER = {
    'mtpu_mtpu_codec_dispatch_total{codec="dense-gf8",engine="device"}': 9,
    'mtpu_mtpu_codec_dispatch_total{codec="dense-gf8",engine="mesh"}': 9,
    'mtpu_codec_dispatch_kind_total{engine="device",kind="reconstruct"}': 4,
    'mtpu_codec_trace_total{codec="dense-gf8",engine="device"}': 1,
    'mtpu_bitrot_verified_bytes_total{path="get"}': 3 * 10 * gate.MIB,
    'mtpu_get_reconstructed_blocks_total': 20,
    'mtpu_mesh_padded_blocks_total': 24, 'mtpu_mesh_retraces_total': 0,
    'mtpu_mesh_collective_bytes_total': 4096,
    **{f'mtpu_rpc_{name}{{plane="{plane}"}}': n
       for name, n in (("calls_total", 40), ("sent_bytes_total", 1 << 20),
                       ("served_seconds_total", 0.5))
       for plane in ("storage", "lock")},
}


def _render() -> str:
    """The span plane's page after the records above: spans through
    `spans.record` under a root of each op, the probe's and the drive
    lock's series as the program raises them."""
    from minio_tpu.observability import spans
    from minio_tpu.observability.metrics import Metrics

    reg = Metrics()
    saved = set(spans._rpc_labels)
    spans.set_metrics(reg)
    try:
        for method in ("create_file", "rename_data"):
            spans.name_rpc("storage", method)
        spans.name_rpc("lock", "lock")
        for op, n, tree in (("put_object", PUTS, PUT_SPANS),
                            ("get_object", GETS, GET_SPANS),
                            ("heal_object", HEALS, HEAL_SPANS)):
            for _ in range(n):
                with spans.request_trace(op, background=op == "heal_object"):
                    for kind, label, s in tree:
                        spans.record(kind, label, int(s * 1e9))
    finally:
        spans.set_metrics(None)
        spans._rpc_labels.clear()
        spans._rpc_labels.update(saved)
        spans.reset()
    # the probe was stopped above; its samples and the lock's waits are
    # written as the probe and `LocalStorage._take_lock` write them
    with reg._mu:
        reg._hists.pop("interp_wait_seconds", None)
    for late in PROBE:
        reg.observe("interp_wait_seconds", late)
    for op, s in LOCK_WAITS.items():
        reg.inc("drive_lock_wait_seconds_total", s, op=op)
        reg.inc("drive_lock_waits_total", op=op)
    reg.inc("drive_lock_wait_seconds_total", 0.0, op="delete_version")
    return reg.render_prometheus() + "".join(
        f"{k} {v}\n" for k, v in OTHER.items())


def _unlabelled(page: dict[str, float]) -> dict[str, float]:
    """The same records as a program renders them whose `span_seconds`
    has `kind` and `op` alone, and that has neither the probe nor the
    drive lock's counters."""
    out: dict[str, float] = {}
    for key, val in page.items():
        if "interp_wait_seconds" in key or "drive_lock_" in key:
            continue
        key = re.sub(r',?label="[^"]*"', "", key).replace("{,", "{")
        out[key] = out.get(key, 0.0) + val
    return out


@pytest.fixture(scope="module")
def pages():
    page = cl.counters(_render())
    assert any('label="quorum-wait"' in k for k in page)
    # two nodes' scrapes added up, as the runner adds them
    return {"labelled": cl.add_up([page, page]),
            "parent": cl.add_up([_unlabelled(page)] * 2)}


def _evidence(after: dict[str, float]) -> readers.Evidence:
    ev = gate._evidence([gate._op(101.0 + i, 102.0 + i) for i in range(PUTS)])
    ev.after = after
    return ev


def _doc(name: str) -> dict:
    with open(os.path.join(METRICS, name + ".json")) as f:
        return json.load(f)


def test_the_labels_leave_the_keys_of_every_other_kind_alone(pages):
    labelled = {k for k in pages["labelled"] if "label=" in k}
    assert labelled and all(re.search(r'kind="(rpc|fanout)"', k)
                            for k in labelled)
    # the label sorts between kind and op; an unknown method reads other
    assert ('mtpu_span_seconds_sum{kind="rpc",label="other",'
            'op="put_object"}') in pages["labelled"]
    assert ('mtpu_span_seconds_count{kind="fanout",label="hedge",'
            'op="get_object"}') in pages["labelled"]


@pytest.mark.parametrize("name", OLD)
def test_a_metric_that_was_there_reads_the_same_with_labels(pages, name):
    doc = _doc(name)
    got = readers.read(_evidence(pages["labelled"]), doc)
    want = readers.read(_evidence(pages["parent"]), doc)
    assert got == want, (name, got, want)
    if "span_seconds" in doc.get("pattern", ""):
        assert got is not None, name


@pytest.mark.parametrize("name,want", [
    *((f"interp_wait_ms.{fam}", 1000 * sum(PROBE) / len(PROBE))
      for fam in ("put", "ops", "heal", "get")),
    *((f"drive_lock_ms_per_put.{fam}",
       1000 * 2 * sum(LOCK_WAITS.values()) / (2 * PUTS))
      for fam in ("put", "ops")),
    ("rpc_create_file_ms_per_op.cluster", 1000 * (0.125 + 0.0625)),
    ("shard_read_wait_ms_per_req.get", 1000 * (0.0390625 + 0.0234375)),
])
def test_each_new_metric_reads_its_value_and_nothing_on_the_parent(
        pages, name, want):
    doc = _doc(name)
    assert doc["reader"] == "counter_ratio" and doc["what"], name
    assert readers.read(_evidence(pages["labelled"]), doc) == \
        pytest.approx(want)
    assert readers.read(_evidence(pages["parent"]), doc) is None


def test_the_new_entries_are_appended_and_name_their_layers():
    with open(os.path.join(gate.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    names = [m["name"] for m in bench["per_layer"]]
    listed = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert set(NEW[:5]) <= {m["name"] for m in listed}
    # after every entry that was there
    assert min(names.index(m["name"]) for m in listed) > \
        names.index("lock_ms_per_op.cluster")
    for m in listed:
        assert m["source"] == "program_counter" and m["moves"] in e2e, m
        assert m["workloads"] and set(m["workloads"]) <= cells, m
        assert m["layer"] == ("interpreter" if m["name"].startswith("interp")
                              else "drive lock"), m
