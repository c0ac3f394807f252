"""What a run is made of, found by name.

`BENCHMARK.json` names cells, configurations and metrics. Whatever belongs
to one configuration, one traffic mix or one per-layer metric is a file of
its own under the benchmark's data root:

    configs/<config>.json          the deployment: geometry, environment,
                                   guarantees, source, assumed, reduced;
                                   `deployment.nodes` (1 where absent) is
                                   the number of server processes that
                                   form the one cluster: each holds
                                   drives / nodes of the drives and
                                   chips / nodes of the cell's chips, so
                                   it has to divide both
    end_to_end/<metric>.json       one end-to-end metric: its reader and
                                   the reader's parameters
    traffic/<traffic>.json         the mix: loop kind, clients, ops, sizes,
                                   preload, warm-up, what to inject
    layer_metrics/<metric>.json    one per-layer metric: its reader and the
                                   reader's parameters

A later PR adds a cell by adding entries to `BENCHMARK.json` and files
here; it edits no file that is there.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass

HARNESS_DIR = os.path.dirname(os.path.abspath(__file__))
DATA_ROOT = os.path.dirname(HARNESS_DIR)            # <checkout>/benchmark
CHECKOUT = os.path.dirname(DATA_ROOT)


class SpecError(Exception):
    """BENCHMARK.json or one of the data files does not hold together."""


def _load(path: str) -> dict:
    try:
        with open(path) as f:
            doc = json.load(f)
    except FileNotFoundError:
        raise SpecError(f"{path} is missing") from None
    except ValueError as exc:
        raise SpecError(f"{path}: {exc}") from None
    if not isinstance(doc, dict):
        raise SpecError(f"{path} does not hold a JSON object")
    return doc


def _need(doc: dict, where: str, *keys: str) -> None:
    for k in keys:
        if k not in doc:
            raise SpecError(f"{where} lacks {k!r}")


@dataclass
class Cell:
    """One entry of `workloads`, with everything its run needs."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]     # BENCHMARK.json entries, "reader" added
    per_layer: list[dict]

    @property
    def k(self) -> int:
        return self.config["deployment"]["data"]

    @property
    def m(self) -> int:
        return self.config["deployment"]["parity"]

    @property
    def drives(self) -> int:
        return self.config["deployment"]["drives"]

    @property
    def nodes(self) -> int:
        """Server processes of the deployment, one cluster together."""
        return int(self.config["deployment"].get("nodes", 1))

    @property
    def block_size(self) -> int:
        return self.config["deployment"]["block_size"]

    @property
    def codec(self) -> str:
        """The codec the deployment states, '' where it leaves the choice
        to the program."""
        return self.config["env"].get("MTPU_CODEC", "")

    @property
    def engine(self) -> str:
        """Label of the dispatch counter that has to move."""
        return self.config["deployment"]["engine"]


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _with_readers(metrics: list[dict], cell: str, data_root: str,
                  folder: str) -> list[dict]:
    """The metrics this cell reports, each with its data file under
    "reader"."""
    out = []
    for m in metrics:
        if not _reports(m, cell):
            continue
        path = os.path.join(data_root, folder, m["name"] + ".json")
        reader = _load(path)
        _need(reader, path, "reader")
        out.append({**m, "reader": reader})
    return out


def load_cell(workload: str, bench_json: str | None = None,
              data_root: str | None = None) -> Cell:
    bench_json = bench_json or os.path.join(CHECKOUT, "BENCHMARK.json")
    data_root = data_root or DATA_ROOT
    bench = _load(bench_json)
    _need(bench, bench_json, "workloads", "configs", "end_to_end",
          "per_layer")
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise SpecError(f"{bench_json} names no workload {workload!r}; it "
                        f"has {[w['name'] for w in bench['workloads']]}")
    cfg_entry = next((c for c in bench["configs"]
                      if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise SpecError(f"workload {workload!r} names the configuration "
                        f"{entry['config']!r}, which configs lacks")
    # the configuration's file is where BENCHMARK.json says it is, beside
    # the data root that holds it (a test's copy keeps the relative path)
    cfg_path = os.path.join(os.path.dirname(data_root), cfg_entry["file"])
    config = _load(cfg_path)
    _need(config, cfg_path, "deployment", "env", "guarantees")
    _need(config["deployment"], cfg_path + " deployment", "drives", "data",
          "parity", "block_size", "engine")
    tr_path = os.path.join(data_root, "traffic", entry["traffic"] + ".json")
    traffic = _load(tr_path)
    _need(traffic, tr_path, "kind")
    e2e = _with_readers(bench["end_to_end"], workload, data_root,
                        "end_to_end")
    layers = _with_readers(bench["per_layer"], workload, data_root,
                           "layer_metrics")
    cell = Cell(name=workload, chips=int(entry["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layers)
    for what, n in (("drives", cell.drives), ("chips", cell.chips)):
        if cell.nodes < 1 or n % cell.nodes:
            raise SpecError(
                f"{cfg_path} states {cell.nodes} nodes, which do not divide "
                f"the {n} {what} of workload {workload!r}: every node holds "
                "the same share of the drives and chips of its own (one "
                "process a chip: four nodes on one chip are no deployment)")
    return cell
