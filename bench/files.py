"""The benchmark's files, found by name: a missing one is a `BenchError`
that names it."""
from __future__ import annotations

import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent
CHECKOUT = BENCH.parent


class BenchError(Exception):
    """A run that cannot give a result: it exits non-zero, unprinted."""


def load_module(path: pathlib.Path):
    """A benchmark file loaded by path (metric names carry dots)."""
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(CHECKOUT)}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(CHECKOUT)}")
    return json.loads(path.read_text())
