"""The whole run on the CPU at the configurations' ``rehearse`` sizes,
past the look for a chip: a sound run comes out correct, and one whose
timed path alters an answer where it is produced comes out not correct.

The same rehearsal from the command line:
    JAX_PLATFORMS=cpu PYTHONPATH=src python3 benchmarks/chip/run.py \
        --workload netflix.reverse-head-k50 --seed 1 --seconds 2 \
        --trace 0 --rehearse
"""

import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import jax.numpy as jnp
import pytest

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[2]
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(ROOT / "src"))

from rkbench import harness, spec  # noqa: E402
from repro.engine import serving  # noqa: E402

REVERSE = "netflix.reverse-head-k50"
FORWARD = "amazon-cds.forward-k50"


def _run(workload, seed, trace=False):
    return harness.run(workload, seed, 1.0, trace,
                       t_start=time.perf_counter(), rehearse=True)


def _failed(line):
    return {c for c, v in line["checks"].items()
            if (v["value"] > v["limit"] if v["op"] == "<="
                else v["value"] < v["limit"])}


def _limited(workload):
    """The numbers the cell's limits file compares."""
    return set(json.loads((spec.BENCH_DIR / "limits" /
                           f"{workload}.json").read_text()))


def _keys_ok(line):
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("workload", [REVERSE, FORWARD])
def test_sound_run_is_correct(workload):
    line = _run(workload, 2**31 + 7)
    _keys_ok(line)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    cell = spec.cell(workload)
    assert set(line["metrics"]) == {m["name"] for m in cell["end_to_end"]}


def test_traced_run_reads_counters():
    line = _run(REVERSE, 11, trace=True)
    assert line["correct"], line["checks"]
    assert {"scan_lane_share", "tiles_per_ticket"} <= set(line["metrics"])
    assert 0 < line["metrics"]["scan_lane_share"]["value"] <= 100
    assert line["metrics"]["tiles_per_ticket"]["value"] > 0
    assert "busy_s" in line["device"] and "window_s" in line["device"]


def test_altered_reverse_answer_is_not_correct(monkeypatch):
    flush = serving.ReverseServer._flush_batch

    def altered(self, group, k, **kw):
        out = flush(self, group, k, **kw)
        return [out[0]._replace(predictions=jnp.zeros_like(
            out[0].predictions))] + out[1:]
    monkeypatch.setattr(serving.ReverseServer, "_flush_batch", altered)
    line = _run(REVERSE, 12)
    assert not line["correct"]
    assert _failed(line) & _limited(REVERSE), line["checks"]


def test_altered_forward_answer_is_not_correct(monkeypatch):
    flush = serving.RetrievalServer._flush_batch

    def altered(self, group, k, **kw):
        out = flush(self, group, k, **kw)
        return [out[0]._replace(ids=out[0].ids[::-1])] + out[1:]
    monkeypatch.setattr(serving.RetrievalServer, "_flush_batch", altered)
    line = _run(FORWARD, 13)
    assert not line["correct"]
    assert _failed(line) & _limited(FORWARD), line["checks"]


def _cli(cwd, *extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", FORWARD,
         "--seed", "1", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_without_chip_prints_no_result():
    p = _cli(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no chip" in p.stderr


def test_cli_without_program_prints_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE.parent, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path, "--rehearse")
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert not any(json.loads(l).get("correct") for l in
                   p.stdout.splitlines() if l.startswith("{"))
