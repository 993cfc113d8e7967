"""``BENCHMARK.json`` and the files it names, found by name.

A cell names a configuration and a traffic mix; each lives in a file of its
own under ``chipbench/``, and so does each per-layer metric's reader and
each cell's limits. Adding any of them takes new files and new entries in
``BENCHMARK.json``, never an edit of a file already there.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Callable, Dict, List, Mapping, Optional

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class Manifest:
    """The benchmark as ``BENCHMARK.json`` at ``root`` describes it."""

    def __init__(self, root: str):
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.data: Dict[str, Any] = json.load(f)
        self.bench_dir = os.path.join(root, self.data["paths"][0])

    def _json(self, *parts: str) -> Dict[str, Any]:
        with open(os.path.join(self.bench_dir, *parts)) as f:
            return json.load(f)

    def cell(self, name: str) -> Dict[str, Any]:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in self.data['workloads']]}")

    def config(self, name: str) -> Dict[str, Any]:
        for c in self.data["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"])) as f:
                    return json.load(f)
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> Dict[str, Any]:
        return self._json("traffic", f"{name}.json")

    def limits(self, cell: str) -> Dict[str, float]:
        return self._json("limits", f"{cell}.json")

    def reader(self, metric: str) -> Callable[[Mapping[str, Any]],
                                               Optional[float]]:
        """``read(ctx)`` of ``chipbench/metrics/<metric>.py``."""
        path = os.path.join(self.bench_dir, "metrics", f"{metric}.py")
        spec = importlib.util.spec_from_file_location(
            f"chipbench_metric_{metric.replace('.', '_').replace('-', '_')}",
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def problems(m: Manifest) -> List[str]:
    """Names that break the character rules, and entries whose files are
    missing: an empty list for a sound manifest."""
    d = m.data
    out = []
    metrics = d["end_to_end"] + d["per_layer"]
    for entry in d["configs"] + d["workloads"] + metrics:
        if not NAME.match(entry["name"]):
            out.append(f"bad name {entry['name']!r}")
    for metric in metrics:
        if not UNIT.match(metric["unit"]):
            out.append(f"bad unit {metric['unit']!r} of {metric['name']}")
        if metric["better"] not in ("lower", "higher"):
            out.append(f"bad 'better' of {metric['name']}")
    for c in d["configs"]:
        if not os.path.isfile(os.path.join(m.root, c["file"])):
            out.append(f"config file {c['file']} missing")
    configs = {c["name"] for c in d["configs"]}
    for w in d["workloads"]:
        if w["config"] not in configs:
            out.append(f"{w['name']}: unknown config {w['config']!r}")
        for part, name in (("traffic", w["traffic"] + ".json"),
                           ("limits", w["name"] + ".json")):
            if not os.path.isfile(os.path.join(m.bench_dir, part, name)):
                out.append(f"{w['name']}: {part}/{name} missing")
    for metric in d["per_layer"]:
        if not os.path.isfile(os.path.join(m.bench_dir, "metrics",
                                           metric["name"] + ".py")):
            out.append(f"reader metrics/{metric['name']}.py missing")
    return out
