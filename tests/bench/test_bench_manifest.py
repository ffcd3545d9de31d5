"""BENCHMARK.json is consistent with the files the harness finds by name,
so a later cell, configuration or metric is added as files and entries."""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
E2E = {m["name"]: m for m in MAN["end_to_end"]}


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric["workloads"] if "workloads" in metric else True


def test_top_level_keys_and_command():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(MAN["paths"]) <= 16
    for p in MAN["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p.split("/")
        assert (ROOT / p).is_dir()
    cmd = MAN["command"]
    assert 1 <= len(cmd) <= 32
    for word in cmd[1:]:
        assert not word.startswith("/") and ".." not in word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in MAN["paths"]), word
            assert (ROOT / word).is_file()
    assert isinstance(MAN["run_seconds"], int) and 1 <= MAN["run_seconds"] <= 51
    assert len(json.dumps(MAN)) <= 64 * 1024


def test_names_and_units():
    names = [e["name"] for key in ("configs", "workloads", "end_to_end", "per_layer") for e in MAN[key]]
    names += [w["traffic"] for w in MAN["workloads"]] + [w["config"] for w in MAN["workloads"]]
    names += [k for c in MAN["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        got = [e["name"] for e in MAN[key]]
        assert len(got) == len(set(got)), key
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for text in [c["why"] for c in MAN["configs"]] + [w["why"] for w in MAN["workloads"]] + \
            [m["layer"] for m in MAN["per_layer"]] + [c["source"] for c in MAN["configs"]]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_configs_have_files_and_drivers():
    assert 1 <= len(MAN["configs"]) <= 24
    files = [c["file"] for c in MAN["configs"]]
    assert len(files) == len(set(files))
    for c in MAN["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["source"].startswith("https://")
        assert any(c["file"].startswith(p + "/") for p in MAN["paths"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert (ROOT / "bench" / "drivers" / f"{cfg['driver']}.py").is_file()
        assert any(w["config"] == c["name"] for w in MAN["workloads"]), "every config is used"


def test_workloads_find_their_files():
    cells = MAN["workloads"]
    assert 1 <= len(cells) <= 24
    configs = {c["name"] for c in MAN["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(pairs) == len(set(pairs))
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs
        assert w["chips"] in (1, 4)
        assert (ROOT / "bench" / "workloads" / f"{w['traffic']}.json").is_file()
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)


def test_metrics_have_readers_and_move_reported_metrics():
    cells = [w["name"] for w in MAN["workloads"]]
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.25
    for m in MAN["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in MAN["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        moved = E2E[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells and _reports(moved, cell), (m["name"], cell)
        path = ROOT / "bench" / "metrics" / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location(f"bench.metrics.{m['name']}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        assert callable(module.read)
    layers = {}
    for m in MAN["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


@pytest.mark.parametrize("cell", [w["name"] for w in MAN["workloads"]])
def test_every_cell_reports_setup_another_metric_and_a_layer(cell):
    e2e = [m["name"] for m in MAN["end_to_end"] if _reports(m, cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(_reports(m, cell) for m in MAN["per_layer"] if "workloads" in m)


def test_check_budget_fits_the_full_benchmark():
    # 2 + 14 runs a cell, each run_seconds + 60, 2 x 90 s a cell to compile,
    # 1200 s spare: all of it for 24 cells within 43,200 s.
    runs = 2 + 14 * 24
    assert runs * (MAN["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
