"""Find a cell's configuration, traffic mix and metrics by name."""

from __future__ import annotations

import importlib.util
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


class SpecError(ValueError):
    """A name that no file of the benchmark defines."""


def _json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise SpecError(f"no file {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def benchmark() -> dict:
    return _json(ROOT / "BENCHMARK.json")


def config(name: str) -> dict:
    return _json(BENCH_DIR / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return _json(BENCH_DIR / "traffic" / f"{name}.json")


def reader(metric: str):
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    path = BENCH_DIR / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise SpecError(f"no reader {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(f"rkbench_metric_{metric}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _metric_in(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, bench: dict | None = None) -> dict:
    """One ``workloads`` entry with its loaded config and traffic, and the
    metrics it reports: ``end_to_end`` (trace 0) and ``per_layer``
    (trace 1), each a list of metric entries."""
    bench = benchmark() if bench is None else bench
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise SpecError(f"unknown workload {name!r}; known: "
                        f"{[w['name'] for w in bench['workloads']]}")
    w = found[0]
    return {"name": name, "chips": w["chips"],
            "config": config(w["config"]), "traffic": traffic(w["traffic"]),
            "end_to_end": [m for m in bench["end_to_end"]
                           if _metric_in(m, name)],
            "per_layer": [m for m in bench["per_layer"]
                          if _metric_in(m, name)]}
