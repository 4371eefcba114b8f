"""The benchmark's tests see ``BENCHMARK.json`` with the parked cells of
``data/parked_cells.json`` added, so the reverse harness path they need
stays under test while its cell is held out of the benchmark."""

import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from rkbench import spec  # noqa: E402

_READ = spec.benchmark


def with_parked() -> dict:
    bench = _READ()
    parked = json.loads((HERE / "data" / "parked_cells.json").read_text())
    return {**bench, "workloads": bench["workloads"] + parked["workloads"],
            "per_layer": bench["per_layer"] + parked["per_layer"]}


@pytest.fixture(autouse=True)
def _parked_cells(monkeypatch):
    monkeypatch.setattr(spec, "benchmark", with_parked)
