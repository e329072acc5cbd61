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


def find_under_paths(manifest: Dict, root: Path, *parts: str) -> Path:
    """`<path>/<parts...>` under the first of the manifest's `paths`
    that holds it: how a cell's traffic file, a per-layer metric's reader
    and a configuration's reference are found by name."""
    for d in manifest["paths"]:
        p = root.joinpath(d, *parts)
        if p.exists():
            return p
    raise FileNotFoundError("no " + "/".join(parts) + " under any of "
                            + ", ".join(manifest["paths"]))


def decode_width(config: Dict) -> int:
    """Positions of each live stream that one decode call carries: the
    configuration file's top-level `decode_width`, 1 where it has none
    (a token a step). A model that generates by blocks of B positions
    states B: each of its steps is a forward of B positions a stream.
    Read by the reference check (how the program is driven) and by the
    least time of a block (servebench/peaks.py)."""
    w = config.get("decode_width", 1)
    if not isinstance(w, int) or isinstance(w, bool) or w < 1:
        raise ValueError(f"`decode_width` of {config.get('name')!r} is "
                         f"{w!r}: a whole number of positions, 1 or more")
    return w


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
        return find_under_paths(self.manifest, self.root, "traffic",
                                traffic + ".json")

    def _metrics(self, group: str) -> List[Dict]:
        return [m for m in self.manifest[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def reader(self, metric: str):
        """The per-layer metric's reader: `layer_metrics/<name>.py`
        under one of the manifest's paths, with a `read(ctx)`."""
        p = find_under_paths(self.manifest, self.root, "layer_metrics",
                             metric + ".py")
        spec = importlib.util.spec_from_file_location(
            "servebench_layer_" + metric.replace(".", "_").replace("-", "_"), p)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def load_manifest(root: Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())
