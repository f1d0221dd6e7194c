"""`BENCHMARK.json` and the data files it names.

Everything that belongs to one configuration, one traffic mix, one
cell or one per-layer metric is a file of its own, found by its name:

    benchmarks/configs/<config>.json        the deployment as it is run
    benchmarks/traffic/<traffic>.json       the mix's parameters
    benchmarks/cells/<cell>.json            the cell's own numbers (its rate)
    benchmarks/layer_metrics/<metric>.py    the metric's reader

so a later PR adds a cell or a metric by adding files and entries and
edits nothing that is there.  A metric `x.catchup` is read by
`layer_metrics/x.py` unless `layer_metrics/x.catchup.py` exists: the
suffix only says which end-to-end metric the number moves.
"""

from __future__ import annotations

import importlib.util
import json
import os


class ManifestError(Exception):
    pass


def _load_json(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except OSError as e:
        raise ManifestError(f"cannot read {path}: {e}") from e
    except ValueError as e:
        raise ManifestError(f"{path} is not JSON: {e}") from e


class Manifest:
    def __init__(self, root: str):
        self.root = root
        self.doc = _load_json(os.path.join(root, "BENCHMARK.json"))
        self.bench_dir = os.path.join(root, "benchmarks")

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        known = ", ".join(w["name"] for w in self.doc["workloads"])
        raise ManifestError(f"no workload {name!r} in BENCHMARK.json (have: {known})")

    def config(self, cell: dict) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == cell["config"]:
                return _load_json(os.path.join(self.root, c["file"]))
        raise ManifestError(f"workload {cell['name']!r} names no known config")

    def traffic(self, cell: dict) -> dict:
        """The mix's parameters, overlaid with the cell's own."""
        mix = _load_json(
            os.path.join(self.bench_dir, "traffic", cell["traffic"] + ".json")
        )
        own = os.path.join(self.bench_dir, "cells", cell["name"] + ".json")
        if os.path.exists(own):
            mix = {**mix, **_load_json(own)}
        return mix

    def _reports(self, metric: dict, cell_name: str, family: str) -> bool:
        listed = metric.get("workloads")
        if listed is not None:
            return cell_name in listed
        if family == "end_to_end":
            return True
        # a per-layer metric without `workloads` is due in every cell
        # that reports the end-to-end metric it moves
        return any(
            e["name"] == metric["moves"] and self._reports(e, cell_name, "end_to_end")
            for e in self.doc["end_to_end"]
        )

    def metrics(self, family: str, cell_name: str) -> list:
        """The `end_to_end` or `per_layer` entries due in this cell."""
        return [m for m in self.doc[family] if self._reports(m, cell_name, family)]

    def reader(self, metric_name: str):
        """The `read(obs)` of a per-layer metric's own file."""
        d = os.path.join(self.bench_dir, "layer_metrics")
        for stem in (metric_name, metric_name.split(".", 1)[0]):
            path = os.path.join(d, stem + ".py")
            if os.path.exists(path):
                spec = importlib.util.spec_from_file_location(
                    "layer_metric_" + stem.replace(".", "_").replace("-", "_"), path
                )
                mod = importlib.util.module_from_spec(spec)
                spec.loader.exec_module(mod)
                return mod.read
        raise ManifestError(f"no reader file for per-layer metric {metric_name!r} in {d}")
