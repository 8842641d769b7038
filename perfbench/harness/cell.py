"""Find a cell's files by name.  There is no registry: a workload entry of
`BENCHMARK.json` names a configuration and a traffic mix, and everything else
is `<path>/configs/<config>.json`, `<path>/traffic/<traffic>.json` and
`<path>/layers/<metric>.json` under the first of `paths`.  A later PR adds a
cell, a mix or a per-layer metric by adding files and entries."""

from __future__ import annotations

import json
import os


class CellError(ValueError):
    """The benchmark's own files do not describe the cell asked for."""


def _load(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise CellError(f"{path}: {e}") from e


class Cell:
    def __init__(self, benchmark_file: str, workload: str):
        self.root = os.path.dirname(os.path.abspath(benchmark_file))
        self.benchmark = _load(benchmark_file)
        by_name = {w["name"]: w for w in self.benchmark["workloads"]}
        if workload not in by_name:
            raise CellError(
                f"no workload {workload!r} in {benchmark_file}; it has {sorted(by_name)}"
            )
        self.workload = by_name[workload]
        self.name = workload
        self.chips = int(self.workload["chips"])
        self.data_dir = os.path.join(self.root, self.benchmark["paths"][0])
        config_entry = next(
            (c for c in self.benchmark["configs"] if c["name"] == self.workload["config"]),
            None,
        )
        if config_entry is None:
            raise CellError(f"workload {workload!r} names an undeclared config")
        self.config = _load(os.path.join(self.root, config_entry["file"]))
        self.traffic = _load(
            os.path.join(self.data_dir, "traffic", self.workload["traffic"] + ".json")
        )

    def _in_cell(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self) -> list:
        return [m for m in self.benchmark["end_to_end"] if self._in_cell(m)]

    def per_layer(self) -> list:
        """(declared metric, its reader file) for this cell's per-layer metrics."""
        out = []
        for m in self.benchmark["per_layer"]:
            if self._in_cell(m):
                path = os.path.join(self.data_dir, "layers", m["name"] + ".json")
                out.append((m, _load(path)))
        return out

    def scheduling(self) -> dict:
        """The deployment's `scheduling:` block (the reference's key names),
        with the traffic mix's per-round cap as the scheduling burst."""
        block = dict(self.config["scheduling"])
        cap = int(self.traffic["cap"])
        block["maximumSchedulingBurst"] = cap
        block["maximumPerQueueSchedulingBurst"] = cap
        return block
