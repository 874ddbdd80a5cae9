"""BENCHMARK.json: every cell, configuration, mix, driver, metric and limit
is found by name, and the file keeps to its own format."""
import json
import pathlib
import re

import pytest

from bench import run as bench_run

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) < 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    c = bench_run.resolve(SPEC, cell)
    w = next(w for w in SPEC["workloads"] if w["name"] == cell)
    assert c.config["name"] == w["config"]
    assert c.chips == w["chips"] == 1
    assert (bench_run.BENCH / "drivers" / f"{c.traffic['driver']}.py").is_file()
    assert c.limits and all(limit["limit"] > 0
                            for limit in c.limits.values())
    for m in c.end_to_end + c.per_layer:
        assert (bench_run.BENCH / "metrics" / f"{m['name']}.py").is_file()
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer


def test_unknown_workload_is_refused():
    with pytest.raises(bench_run.BenchError):
        bench_run.resolve(SPEC, "no.such.cell")


def test_names_units_and_bounds():
    groups = [SPEC["configs"], SPEC["workloads"], SPEC["end_to_end"]
              + SPEC["per_layer"]]
    for group in groups:
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("config", SPEC["configs"], ids=lambda c: c["name"])
def test_config_files(config):
    body = json.loads((ROOT / config["file"]).read_text())
    assert config["file"].startswith("bench/configs/")
    assert body["name"] == config["name"] and config["reduced"] == []
    assert len(body["dims"]) >= 3 and body["nnz"] > 0
    assert body["dtype"] == "float32" and "assumed" in body


@pytest.mark.parametrize("config", SPEC["configs"], ids=lambda c: c["name"])
def test_config_cpu_cut(config):
    """The CPU tests' tensor: as many modes as the configuration, no dim
    or nnz above the published one, and room in every mode for each cube
    edge the configuration's cells' mixes ask for."""
    body = json.loads((ROOT / config["file"]).read_text())
    cut = body["cpu_cut"]
    assert len(cut["dims"]) == len(body["dims"])
    assert all(0 < c <= d for c, d in zip(cut["dims"], body["dims"]))
    assert 0 < cut["nnz"] <= body["nnz"]
    for w in SPEC["workloads"]:
        if w["config"] == config["name"]:
            mix = bench_run.resolve(SPEC, w["name"]).traffic["coords"]
            assert min(cut["dims"]) >= mix.get("edge", 1), w["name"]
