"""The harness end to end on the CPU at reduced sizes: it refuses to run
off a TPU, and with the look for a chip skipped it drives every cell
through the public calls and reports what the contract asks for."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from bench import run as bench_run

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


def small_spec(tmp_path, spec=SPEC) -> dict:
    """``spec`` with every configuration cut to the CPU-sized tensor of
    its own file's ``cpu_cut`` (the traffic mixes, drivers and limits are
    the cells' own)."""
    spec = json.loads(json.dumps(spec))
    for c in spec["configs"]:
        body = json.loads((ROOT / c["file"]).read_text())
        body.update(body["cpu_cut"])
        path = tmp_path / f"{c['name']}.json"
        path.write_text(json.dumps(body))
        c["file"] = str(path)
    return spec


def _cli(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_exits_nonzero_off_a_tpu():
    p = _cli(ROOT)
    assert p.returncode != 0
    assert "not a TPU" in p.stderr
    assert not p.stdout.strip()


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark's
    files, a run with the look for a chip skipped finds no program."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    code = ("import sys; sys.path.insert(0, '.'); from bench import run; "
            f"run.run({CELLS[0]!r}, 1, 0.1, False, chip=False)")
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "No module named 'repro'" in p.stderr
    assert not p.stdout.strip()


@pytest.mark.parametrize("cell", CELLS)
def test_untraced_run_reports_end_to_end(cell, tmp_path):
    out = bench_run.run(cell, 2 ** 31 + 3, 0.5, False,
                        spec=small_spec(tmp_path), chip=False)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] >= 1
    want = {m["name"] for m in bench_run.resolve(SPEC, cell).end_to_end}
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert list(out)[-1] == "checks"
    limits = bench_run.resolve(SPEC, cell).limits
    assert set(out["checks"]) == set(limits)
    assert all(0 <= c["value"] <= c["limit"]
               for c in out["checks"].values())


def test_traced_run_reports_per_layer(tmp_path):
    cell = "uber.apr.clustered"
    out = bench_run.run(cell, 17, 0.5, True, spec=small_spec(tmp_path),
                        chip=False)
    assert out["correct"] is True
    names = {m["name"] for m in bench_run.resolve(SPEC, cell).per_layer}
    assert set(out["metrics"]) <= names and "ingest_s" in out["metrics"]
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(out)[-1] == "checks"


def test_small_spec_takes_a_configuration_added_by_a_file(tmp_path):
    """A second, five-mode configuration in its own file with its own
    cut: ``small_spec`` cuts it by that file alone, and every existing
    cell resolves as it did without it."""
    five = {"name": "five", "dims": [1605, 4198, 1631, 4209, 868131],
            "nnz": 1698825, "rank": 16, "dtype": "float32",
            "values": {"kind": "counts", "low": 1, "high": 9},
            "cpu_cut": {"dims": [16, 20, 16, 24, 40], "nnz": 20000}}
    src = tmp_path / "src"
    src.mkdir()
    (src / "five.json").write_text(json.dumps(five))
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "five", "source": "a test",
                            "file": str(src / "five.json"), "reduced": [],
                            "why": "a test"})
    (tmp_path / "one").mkdir()
    (tmp_path / "two").mkdir()
    alone = small_spec(tmp_path / "one")
    both = small_spec(tmp_path / "two", spec)
    cut = next(c for c in both["configs"] if c["name"] == "five")
    body = json.loads(pathlib.Path(cut["file"]).read_text())
    assert body["dims"] == five["cpu_cut"]["dims"]
    assert body["nnz"] == five["cpu_cut"]["nnz"]
    for cell in CELLS:
        assert bench_run.resolve(both, cell) == bench_run.resolve(alone, cell)
        config = bench_run.resolve(alone, cell).config
        assert (config["dims"], config["nnz"]) == (
            config["cpu_cut"]["dims"], config["cpu_cut"]["nnz"])
