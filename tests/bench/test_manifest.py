"""``BENCHMARK.json`` against the rules it is held to, and the harness
against its promise that a cell, a configuration, a traffic mix, a guest or
a metric is added as files and entries, with no edit to a file that is
there."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmarks import cells

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTHS = re.compile(r"(hidden|intermediate|latent|state|proj|head).*size"
                    r"|_dim$|_rank$|expansion|experts_per_tok")


@pytest.fixture(scope="module")
def manifest():
    return cells.load_manifest()


def line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_keys_names_units_and_lengths(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(manifest["command"]) <= 32
    assert all(line(w) for w in manifest["command"])
    assert 1 <= len(manifest["paths"]) <= 16
    for p in manifest["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(REPO, p))
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51

    for kind, keys, optional in (
            ("configs", {"name", "source", "file", "reduced", "why"}, set()),
            ("workloads", {"name", "config", "traffic", "chips", "why"},
             set()),
            ("end_to_end", {"name", "unit", "better", "bound", "source"},
             {"workloads"}),
            ("per_layer", {"name", "unit", "better", "source", "layer",
                           "moves"}, {"workloads"})):
        names = [e["name"] for e in manifest[kind]]
        assert len(names) == len(set(names)), f"{kind}: a name twice"
        for e in manifest[kind]:
            assert keys <= set(e) <= keys | optional, (kind, e["name"])
            assert NAME.match(e["name"]), e["name"]
    metric_names = [m["name"] for m in manifest["end_to_end"]
                    + manifest["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert line(m["layer"])
    for e in manifest["configs"] + manifest["workloads"]:
        assert line(e["why"])


def test_configurations_are_files_with_their_cuts_listed(manifest):
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in manifest["workloads"]}
    by_source = {}
    for c in manifest["configs"]:
        assert c["name"] in used, f"{c['name']} is used by no cell"
        assert line(c["source"]) and len(c["reduced"]) <= 16
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        assert PATH.match(c["file"])
        with open(os.path.join(REPO, c["file"])) as f:
            values = json.load(f)
        assert values["source"] == c["source"]
        assert values["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTHS.search(key), key
        by_source.setdefault(c["source"], []).append((c, values))
    # configurations of one source differ only in what they list as reduced
    for group in by_source.values():
        whole = [v for c, v in group if not c["reduced"]]
        for c, values in group:
            for base in whole:
                differ = {k for k in set(base) | set(values)
                          if k not in ("reduced", "assumed")
                          and base.get(k) != values.get(k)}
                assert differ <= set(c["reduced"]), (c["name"], differ)


def test_every_cell_resolves_to_its_files_by_name(manifest):
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(manifest["workloads"]) // 4)
    for w in manifest["workloads"]:
        assert w["chips"] in (1, 4)
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        cell = cells.load_cell(manifest, w["name"])
        guest = cells.load_module(manifest, "guests", cell["guest"])
        assert callable(guest.make_guest) and callable(guest.drive)
        limits = cell["traffic_values"]["check"]["limits"][cell["config"]]
        assert limits and all(isinstance(v, (int, float))
                              for v in limits.values())


def test_every_metric_has_a_reader_and_its_cells_report_what_it_moves(
        manifest):
    cell_names = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert e2e["setup_s"]["bound"] <= 0.1
    for m in manifest["end_to_end"]:
        assert set(m.get("workloads", cell_names)) <= cell_names
        assert callable(cells.load_module(manifest, "end_to_end",
                                          m["name"]).read)
    layers = set()
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        moved_in = set(e2e[m["moves"]].get("workloads", cell_names))
        assert set(m.get("workloads", moved_in)) <= moved_in, m["name"]
        assert callable(cells.load_module(manifest, "layer_metrics",
                                          m["name"]).read)
        layers.add(m["layer"])
    for name in cell_names:
        reported = {m["name"] for m in
                    cells.metrics_of(manifest, "end_to_end", name)}
        assert "setup_s" in reported and len(reported) >= 2, name
        assert cells.metrics_of(manifest, "per_layer", name), name
    # a kernel's roofline stands beside a whole-step share of the peak
    for m in manifest["per_layer"]:
        if m["name"].endswith("_roofline") or "_roofline." in m["name"]:
            assert m["unit"] == "%"
            assert any("mfu" in re.split(r"[._]", o["name"])
                       and o["moves"] == m["moves"]
                       for o in manifest["per_layer"]), m["name"]
    # PERF.md's list of layers has each layer's name, letter for letter
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert layer in perf, f"layer {layer!r} is not in PERF.md"


def test_a_cell_added_as_files_only_is_found(tmp_path):
    """In a copy of the benchmark: one new configuration, one new traffic
    mix, one new per-layer reader and their entries. No file that was there
    is edited, and the harness finds and loads all of them."""
    for rel in ("BENCHMARK.json", "benchmarks", "tests/bench"):
        src, dst = os.path.join(REPO, rel), tmp_path / rel
        if os.path.isdir(src):
            shutil.copytree(src, dst, ignore=shutil.ignore_patterns(
                "__pycache__"))
        else:
            shutil.copy(src, dst)
    with open(tmp_path / "BENCHMARK.json") as f:
        manifest = json.load(f)
    first = manifest["workloads"][0]
    with open(tmp_path / manifest["configs"][0]["file"]) as f:
        config = json.load(f)
    config["num_hidden_layers"] = 2
    config["reduced"] = ["num_hidden_layers"]
    (tmp_path / "benchmarks/configs/added.json").write_text(
        json.dumps(config))
    with open(tmp_path / f"benchmarks/traffic/{first['traffic']}.json") as f:
        traffic = json.load(f)
    traffic["check"]["limits"] = {"added": {"some_gap": 0.5}}
    (tmp_path / "benchmarks/traffic/added_mix.json").write_text(
        json.dumps(traffic))
    (tmp_path / "benchmarks/layer_metrics/added_count.py").write_text(
        "def read(record):\n    return record.get('added')\n")
    moved = next(m["name"] for m in manifest["end_to_end"]
                 if first["name"] in m.get("workloads", []))
    manifest["configs"].append({
        "name": "added", "source": config["source"],
        "file": "benchmarks/configs/added.json",
        "reduced": ["num_hidden_layers"], "why": "added as files only"})
    manifest["workloads"].append({
        "name": "added_cell", "config": "added", "traffic": "added_mix",
        "chips": 1, "why": "added as files only"})
    for m in manifest["end_to_end"]:
        if first["name"] in m.get("workloads", []):
            m["workloads"].append("added_cell")
    manifest["per_layer"].append({
        "name": "added_count", "unit": "1", "better": "higher",
        "source": "program_counter", "layer": "added", "moves": moved,
        "workloads": ["added_cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    code = (
        "import json, sys\n"
        f"sys.path.insert(0, {str(tmp_path)!r})\n"
        "from benchmarks import cells\n"
        f"assert cells.ROOT == {str(tmp_path)!r}, cells.ROOT\n"
        "m = cells.load_manifest()\n"
        "cell = cells.load_cell(m, 'added_cell')\n"
        "cells.load_module(m, 'guests', cell['guest'])\n"
        "read = cells.load_module(m, 'layer_metrics', 'added_count').read\n"
        "print(json.dumps({\n"
        "  'layers': cell['config_values']['num_hidden_layers'],\n"
        "  'limits': cell['traffic_values']['check']['limits']['added'],\n"
        "  'read': read({'added': 7}), 'none': read({}),\n"
        "  'e2e': [x['name'] for x in\n"
        "          cells.metrics_of(m, 'end_to_end', 'added_cell')],\n"
        "  'layer': [x['name'] for x in\n"
        "            cells.metrics_of(m, 'per_layer', 'added_cell')]}))\n")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120, cwd=str(tmp_path),
                       env={k: v for k, v in os.environ.items()
                            if k != "PYTHONPATH"})
    assert p.returncode == 0, p.stderr[-2000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["layers"] == 2 and got["limits"] == {"some_gap": 0.5}
    assert got["read"] == 7 and got["none"] is None
    assert "setup_s" in got["e2e"] and moved in got["e2e"]
    assert "added_count" in got["layer"]


def test_the_real_cell_without_a_tpu_exits_non_zero_naming_the_platform():
    manifest = cells.load_manifest()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, *manifest["command"][1:]),
         "--workload", manifest["workloads"][0]["name"], "--seed",
         "2147483999", "--seconds", "1", "--trace", "0"],
        env=env, cwd=REPO, timeout=300, capture_output=True, text=True)
    assert p.returncode == 3, (p.returncode, p.stderr[-2000:])
    assert "platform=cpu" in p.stderr
    assert p.stdout.strip() == "", "no result may be printed without a chip"
