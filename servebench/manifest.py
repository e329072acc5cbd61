"""BENCHMARK.json and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by the name the
manifest gives it: a later PR adds a cell by adding files and entries,
and edits nothing that is here.

stdlib only.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class Cell:
    """One entry of `workloads`, with its files resolved."""

    def __init__(self, manifest: Dict, name: str, root: Path = ROOT):
        self.manifest, self.root = manifest, root
        entry = next((w for w in manifest["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                           + ", ".join(w["name"] for w in manifest["workloads"]))
        self.name, self.chips = name, int(entry["chips"])
        cfg = next(c for c in manifest["configs"]
                   if c["name"] == entry["config"])
        self.config_path = root / cfg["file"]
        self.config = json.loads(self.config_path.read_text())
        self.traffic_path = self._traffic_file(entry["traffic"])
        self.end_to_end = self._metrics("end_to_end")
        self.per_layer = self._metrics("per_layer")

    def _traffic_file(self, traffic: str) -> Path:
        for d in self.manifest["paths"]:
            p = self.root / d / "traffic" / (traffic + ".json")
            if p.exists():
                return p
        raise FileNotFoundError(f"no traffic file for {traffic!r}")

    def _metrics(self, group: str) -> List[Dict]:
        return [m for m in self.manifest[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def reader(self, metric: str):
        """The per-layer metric's reader: `layer_metrics/<name>.py`
        under one of the manifest's paths, with a `read(ctx)`."""
        for d in self.manifest["paths"]:
            p = self.root / d / "layer_metrics" / (metric + ".py")
            if p.exists():
                spec = importlib.util.spec_from_file_location(
                    "servebench_layer_" + metric.replace(".", "_")
                    .replace("-", "_"), p)
                mod = importlib.util.module_from_spec(spec)
                spec.loader.exec_module(mod)
                return mod.read
        raise FileNotFoundError(f"no reader for per-layer metric {metric!r}")


def load_manifest(root: Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())
