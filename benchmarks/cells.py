"""Finding a cell's files by name. Nothing here lists a configuration, a
traffic mix, a guest or a metric: ``BENCHMARK.json`` names them, and each is
a file of its own under one of the manifest's ``paths``:

    <file given by the configuration's entry>     the sizes, as run
    traffic/<traffic>.json                        the mix, and its guest
    guests/<guest>.py                             driver and guest
    end_to_end/<metric>.py, layer_metrics/<metric>.py   one reader each

So a later change adds a cell, a guest or a metric by adding files and
entries, and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_manifest(path: str | None = None) -> dict:
    path = path or os.path.join(ROOT, "BENCHMARK.json")
    with open(path) as f:
        return json.load(f)


def find_file(manifest: dict, relative: str) -> str:
    for base in manifest["paths"]:
        candidate = os.path.join(ROOT, base, relative)
        if os.path.isfile(candidate):
            return candidate
    raise FileNotFoundError(
        f"{relative} is under none of the benchmark's paths "
        f"{manifest['paths']}")


def load_module(manifest: dict, kind: str, name: str):
    """The module ``<kind>/<name>.py``, imported by its path (a metric's
    name may hold dots)."""
    path = find_file(manifest, os.path.join(kind, name + ".py"))
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(manifest: dict, workload: str) -> dict:
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; there are {sorted(cells)}")
    cell = dict(cells[workload])
    configs = {c["name"]: c for c in manifest["configs"]}
    entry = configs[cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        cell["config_values"] = json.load(f)
    with open(find_file(manifest, os.path.join(
            "traffic", cell["traffic"] + ".json"))) as f:
        cell["traffic_values"] = json.load(f)
    cell["guest"] = cell["traffic_values"]["guest"]
    return cell


def metrics_of(manifest: dict, kind: str, workload: str) -> list:
    """The entries of ``end_to_end`` or ``per_layer`` that this cell
    reports: those without a ``workloads`` key whose moved metric the cell
    reports (every end-to-end metric without the key), and those that list
    the cell."""
    e2e = [m for m in manifest["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if kind == "end_to_end":
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in manifest["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]
