"""Every cell, configuration, mix and metric of BENCHMARK.json is found by
name, and the file keeps to the benchmark's own rules."""
import importlib.util
import json
import re

import pytest

from conftest import BENCH

ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cell_names_existing_files(cell):
    configs = {c["name"]: c for c in SPEC["configs"]}
    assert cell["config"] in configs
    assert (ROOT / configs[cell["config"]]["file"]).is_file()
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    assert (BENCH / "drivers" / f"{traffic['driver']}.py").is_file()
    assert cell["chips"] == 1
    for name in (cell["name"], cell["config"], cell["traffic"]):
        assert NAME.match(name)


@pytest.mark.parametrize("config", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file(config):
    data = json.loads((ROOT / config["file"]).read_text())
    assert data["name"] == config["name"]
    for key in config["reduced"]:
        assert key in data and key in data["source_values"]
        assert data[key] != data["source_values"][key]
    assert (BENCH / "generators" / f"{data['generator']}.py").is_file()
    from datagen import base_relations, clients

    queries = [q for c in clients(data) for q in c]
    assert queries and base_relations(queries)
    for name, out_vars, guard, cond in queries:
        assert NAME.match(name) and len(out_vars) <= len(guard[1])


@pytest.mark.parametrize("metric", SPEC["per_layer"] + SPEC["end_to_end"],
                         ids=lambda m: m["name"])
def test_metric_has_reader(metric):
    path = BENCH / "metrics" / f"{metric['name']}.py"
    spec = importlib.util.spec_from_file_location("m", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert callable(mod.read)
    if "moves" in metric:
        assert metric["moves"] in {m["name"] for m in SPEC["end_to_end"]}


def test_end_to_end_bounds():
    names = [m["name"] for m in SPEC["end_to_end"]]
    assert "setup_s" in names
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_check_budget_fits():
    """A full check of 24 cells fits in 43200 s: 2 + 14 runs per cell of
    run_seconds + 60 s each, 180 s per cell to compile, 1200 s spare."""
    cells = 24
    runs = 2 + 14 * cells
    assert runs * (SPEC["run_seconds"] + 60) + cells * 2 * 90 + 1200 <= 43200
