"""``BENCHMARK.json`` and the files it names, found by name.

A cell ``<config>.<traffic>`` names a configuration (its ``file``), a
traffic mix (``bench/traffic/<traffic>.json``, see ``traffic.load``) and
the metrics it reports. A per-layer metric ``<base>[.<suffix>]`` is read
by ``bench/metrics/<name>.py`` if that file exists, else by
``bench/metrics/<base>.py``: a module with ``read(ctx) -> float | None``.
So a later cell, mix, configuration or metric is new files and new
entries, never an edit.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Callable, Dict, List

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def workload(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(bench: Dict, name: str, root: str = ROOT) -> Dict:
    for c in bench["configs"]:
        if c["name"] == name:
            with open(os.path.join(root, c["file"])) as f:
                return json.load(f)
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def metrics_for(bench: Dict, cell: str, trace: bool) -> List[Dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics
    with ``trace`` off, its per-layer metrics with it on."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def reader(name: str, bench_dir: str = BENCH_DIR) -> Callable:
    """The ``read`` function of metric ``name``."""
    for stem in (name, name.split(".", 1)[0]):
        path = os.path.join(bench_dir, "metrics", f"{stem}.py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {name!r} under "
                            f"{os.path.join(bench_dir, 'metrics')}")


def validate(bench: Dict) -> List[str]:
    """Names, units and cross references that break the contract."""
    bad = []
    names = ([c["name"] for c in bench["configs"]]
             + [w["name"] for w in bench["workloads"]]
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
             + [w["config"] for w in bench["workloads"]]
             + [w["traffic"] for w in bench["workloads"]]
             + [k for c in bench["configs"] for k in c["reduced"]])
    bad += [f"name {n!r}" for n in names if not NAME.match(n)]
    bad += [f"unit {m['unit']!r}" for m in bench["end_to_end"]
            + bench["per_layer"] if not UNIT.match(m["unit"])]
    cfgs = {c["name"] for c in bench["configs"]}
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    bad += [f"cell {w['name']!r} names no configuration"
            for w in bench["workloads"] if w["config"] not in cfgs]
    bad += [f"metric {m['name']!r} lists unknown cells"
            for m in bench["end_to_end"] + bench["per_layer"]
            if set(m.get("workloads", [])) - cells]
    bad += [f"metric {m['name']!r} moves {m.get('moves')!r}"
            for m in bench["per_layer"] if m.get("moves") not in e2e]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [x["name"] for x in bench[group]]
        bad += [f"duplicate {group} name {n!r}" for n in set(seen)
                if seen.count(n) > 1]
    return bad
