"""BENCHMARK.json against the benchmark's contract: names, units, files,
and which cells report which metrics."""

import ast
import json
import re
from pathlib import Path

import pytest

from chipbench.conftest import REPO

M = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}
METRIC_KEYS = {"name", "unit", "better", "source"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|"
                   r"projection|head|expansion|experts_per_tok")
METRICS = M["end_to_end"] + M["per_layer"]
CELLS = {w["name"]: w for w in M["workloads"]}


def _line(s: str) -> bool:
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_sizes():
    assert set(M) == TOP
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)
    assert 1 <= len(M["paths"]) <= 16
    for p in M["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (REPO / p).is_dir()
    assert 1 <= len(M["command"]) <= 32
    for word in M["command"]:
        assert _line(word) and not word.startswith("/") and ".." not in word
    assert (REPO / M["command"][1]).is_file()
    assert M["command"][1].startswith(M["paths"][0] + "/")


def test_check_fits_the_time_limit_at_24_cells():
    rs = M["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("name", sorted(
    [m["name"] for m in METRICS] + list(CELLS)
    + [c["name"] for c in M["configs"]]))
def test_names(name):
    assert NAME.match(name), name


def test_names_unique():
    for group in (METRICS, M["workloads"], M["configs"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))


def test_configs():
    used = {w["config"] for w in M["workloads"]}
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith(M["paths"][0] + "/")
        cfg = json.loads((REPO / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key)
            assert key in cfg and key in cfg["reduced"]
        assert set(cfg["reduced"]) == set(c["reduced"])
    files = [c["file"] for c in M["configs"]]
    assert len(files) == len(set(files))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_files_exist(cell):
    w = CELLS[cell]
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["chips"] in (1, 4) and _line(w["why"])
    assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    bench = REPO / M["paths"][0]
    assert (bench / "traffic" / f"{w['traffic']}.json").is_file()
    cfg_file = {c["name"]: c["file"] for c in M["configs"]}[w["config"]]
    cfg = json.loads((REPO / cfg_file).read_text())
    assert (bench / "apps" / f"{cfg['app']}.py").is_file()
    assert (bench / "reference" / f"{cfg['app']}.py").is_file()
    for kernel in cfg["kernels"]:
        assert (bench / "work" / f"{kernel}.py").is_file()
    assert set(cfg["limits"]) == {"loops_failed", "loops_differing",
                                  "tasks_off"}


def test_pairs_once_and_four_chip_share():
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(pairs) == len(set(pairs))
    fours = sum(w["chips"] == 4 for w in M["workloads"])
    assert fours <= max(1, len(pairs) // 2)


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_entry(metric):
    m = {x["name"]: x for x in METRICS}[metric]
    assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert m["source"] in SOURCES
    assert (REPO / M["paths"][0] / "metrics" / f"{metric}.py").is_file()
    for cell in m.get("workloads", []):
        assert cell in CELLS
    if m in M["end_to_end"]:
        assert set(m) - {"workloads"} == METRIC_KEYS | {"bound"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert set(m) - {"workloads"} == METRIC_KEYS | {"layer", "moves"}
        assert _line(m["layer"])
    if "roofline" in metric or "mfu" in metric:
        assert m["unit"] == "%"


def test_every_cell_reports_setup_and_another_end_to_end_metric():
    for cell in CELLS:
        e2e = [m["name"] for m in M["end_to_end"]
               if cell in m.get("workloads", [cell])]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = [m for m in M["per_layer"]
                 if cell in m.get("workloads", [cell])]
        assert layer


def test_per_layer_metrics_move_an_end_to_end_metric_their_cells_report():
    e2e = {m["name"]: m for m in M["end_to_end"]}
    for m in M["per_layer"]:
        target = e2e[m["moves"]]
        for cell in m.get("workloads", list(CELLS)):
            assert cell in target.get("workloads", [cell])


def test_one_layer_name_per_layer():
    layers = {m["layer"] for m in M["per_layer"]}
    perf = (REPO / "PERF.md").read_text()
    for layer in layers:
        assert layer in perf, layer


@pytest.mark.parametrize("path", sorted(
    (REPO / "chipbench" / "reference").glob("*.py")), ids=lambda p: p.name)
def test_reference_imports_nothing_of_the_program(path):
    tree = ast.parse(path.read_text())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            mods = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            mods = [node.module or ""]
        else:
            continue
        assert not any(m.split(".")[0] in ("repro", "chipbench")
                       for m in mods), (path, mods)


def test_traffic_files_are_data():
    for f in (REPO / "chipbench" / "traffic").iterdir():
        assert f.suffix in (".json", ".jsonl", ".toml", ".txt", ".csv")
