"""BENCHMARK.json against the benchmark's contract, and the harness's
lookup by name: every configuration, traffic mix and per-layer metric is
a file of its own that the harness finds from its name."""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perf import deployment, run  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTHS = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|projection|head")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _text(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_size(manifest):
    assert set(manifest) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"
    }
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(manifest["run_seconds"], int) and 1 <= manifest["run_seconds"] <= 51
    cmd = manifest["command"]
    assert 1 <= len(cmd) <= 32 and all(_text(w) for w in cmd)
    assert not any(w.startswith("/") or ".." in w for w in cmd)
    paths = manifest["paths"]
    assert 1 <= len(paths) <= 16
    for p in paths:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))


def test_check_fits_the_driver_budget(manifest):
    # 2 + 14 runs per cell at run_seconds + 60 s, 180 s of compile per
    # cell, 1200 s spare: all within 43200 s at the full 24 cells
    cells = 24
    total = (2 + 14 * cells) * (manifest["run_seconds"] + 60) + cells * 180 + 1200
    assert total <= 43200


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end", "per_layer"])
def test_names_are_unique_and_well_formed(manifest, section):
    names = [e["name"] for e in manifest[section]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)


def test_configs(manifest):
    assert 1 <= len(manifest["configs"]) <= 24
    files = set()
    used = {w["config"] for w in manifest["workloads"]}
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _text(c["source"]) and _text(c["why"]) and c["name"] in used
        assert c["file"].startswith(tuple(p + "/" for p in manifest["paths"]))
        assert c["file"] not in files
        files.add(c["file"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) and not WIDTHS.search(k) for k in c["reduced"])
        cfg = deployment.load_config(os.path.join(ROOT, c["file"]))
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert len(cfg["source"]) <= 200 and cfg["guarantees"]
        assert cfg["daemon_node"] in deployment.build(cfg).index


def test_workloads(manifest):
    cells = manifest["workloads"]
    assert 1 <= len(cells) <= 24
    configs = {c["name"] for c in manifest["configs"]}
    pairs = set()
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4) and _text(w["why"])
        assert NAME.match(w["traffic"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        # the traffic mix is a data file naming a driver that exists
        with open(os.path.join(ROOT, "perf", "traffic", f"{w['traffic']}.json")) as f:
            traffic = json.load(f)
        assert os.path.isfile(os.path.join(ROOT, "perf", "drivers", f"{traffic['driver']}.py"))
    four = sum(1 for w in cells if w["chips"] == 4)
    assert four <= max(1, len(cells) // 2)


def test_metrics(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert 1 <= len(e2e) <= 16 and "setup_s" in e2e
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    assert 1 <= len(manifest["per_layer"]) <= 128
    layers = {}
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"
        }
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES and _text(m["layer"])
        assert m["moves"] in e2e
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
        # a reader of its own, found by the metric's name
        assert os.path.isfile(os.path.join(ROOT, "perf", "layer_metrics", f"{m['name']}.py"))
        assert callable(run.load_reader(m["name"]))
    assert all(len(v) == 1 for v in layers.values())


def test_every_cell_reports_setup_another_metric_and_a_layer(manifest):
    for w in manifest["workloads"]:
        e2e, layer = run.cell_metrics(manifest, w["name"])
        names = {m["name"] for m in e2e}
        assert "setup_s" in names and len(names) >= 2
        assert layer
        # every per-layer metric's `moves` is reported where it is read
        assert all(m["moves"] in names for m in layer)


def test_files_under_paths_are_named_from_name_characters(manifest):
    for p in manifest["paths"]:
        for dirpath, dirnames, filenames in os.walk(os.path.join(ROOT, p)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for f in filenames:
                rel = os.path.relpath(os.path.join(dirpath, f), ROOT)
                assert PATH.match(rel), rel
