"""CPU tests of the span reduction (``rkbench/spans.py``) and of the
readers that read the program's spans and counters
(``rkbench/span_readers.py``)."""

import gzip
import json
import pathlib
import shutil
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[2] / "src"))

from rkbench import harness, span_readers, spans, xtrace  # noqa: E402

FORWARD = "amazon-cds.forward-k50"
TABLE = ("rk.submit", "rk.form", "rk.flush", "rk.flush.pad",
         "rk.flush.launch", "rk.flush.split", "rk.resolve")


def _unzip(name, tmp_path):
    path = tmp_path / name.removesuffix(".gz")
    path.write_bytes(gzip.decompress((HERE / "data" / name).read_bytes()))
    return path


def _stats(completed, batches, queue_wait_s=None, linger_s=0.0):
    base = {"completed": completed, "batches": batches}
    if queue_wait_s is not None:
        base.update(queue_wait_s=queue_wait_s, linger_s=linger_s)
    return SimpleNamespace(**base)


def test_runs_and_overlap():
    s, e = spans.runs([5.0, 0.0, 2.0, 20.0], [8.0, 3.0, 4.0, 25.0])
    assert s.tolist() == [0.0, 5.0, 20.0] and e.tolist() == [4.0, 8.0, 25.0]
    a = ([0.0, 10.0], [4.0, 12.0])
    b = ([3.0, 11.0, 30.0], [11.5, 20.0, 31.0])
    # [3, 4) and [10, 12)
    assert spans.overlap(a, b) == 1.0 + 2.0
    assert spans.overlap(a, ([], [])) == 0.0


@pytest.mark.parametrize("name,want", [
    ("%sort.10 = (s32[64512]{0:T(1024)}) sort(s32[64512] %a), "
     "dimensions={0}", "sort.10"),
    ("%hamming_scores.5 = s32[1,64512]{1,0} custom-call(u32[1,4] %b)",
     "hamming_scores.5"),
    ("copy-start.5 = (f32[1,100]) copy-start(f32[1,100] %c)",
     "copy-start.5"),
    ("jit_broadcast_in_dim(3470333007927857990)", None)])
def test_instruction_of_a_device_op(name, want):
    assert spans.instruction(name) == want


def test_queue_wait_reads_the_counter_delta():
    ctx = SimpleNamespace(stats0=_stats(100, 30, 1.0, 0.2),
                          stats1=_stats(612, 130, 6.12, 0.7))
    assert span_readers.queue_wait_ms(ctx) == pytest.approx(10.0)
    # a program without the counter, and a window with no answer
    old = SimpleNamespace(stats0=_stats(100, 30), stats1=_stats(612, 130))
    assert span_readers.queue_wait_ms(old) is None
    idle = SimpleNamespace(stats0=_stats(5, 2, 1.0), stats1=_stats(5, 2, 1.0))
    assert span_readers.queue_wait_ms(idle) is None


def _synthetic(n_devices=1, scope_s=None, flush=True):
    s = np.array([0.0, 10e6, 20e6])
    sp = {"rk.flush": (s, s + np.array([2e6, 3e6, 4e6]))} if flush else {}
    return spans.Spans(window_s=1.0, spans=sp, launches=87.0,
                       scope_s=scope_s or {}, idle_by_span={},
                       n_devices=n_devices)


def test_span_readers_on_a_synthetic_reduction(monkeypatch):
    red = _synthetic(scope_s={"kmips.select": 0.05, "kmips.scan": 0.01})
    monkeypatch.setattr(spans, "current", lambda: red)
    ctx = SimpleNamespace(tickets=200)
    assert span_readers.flush_host_ms_per_batch(ctx) == pytest.approx(3.0)
    assert span_readers.launches_per_batch(ctx) == pytest.approx(29.0)
    assert span_readers.select_ms_per_ticket(ctx) == pytest.approx(0.25)
    assert span_readers.select_ms_per_ticket(
        SimpleNamespace(tickets=0)) is None


@pytest.mark.parametrize("red", [None, _synthetic(flush=False),
                                 _synthetic(n_devices=0)])
def test_span_readers_find_nothing_to_read(monkeypatch, red):
    monkeypatch.setattr(spans, "current", lambda: red)
    ctx = SimpleNamespace(tickets=200)
    assert span_readers.select_ms_per_ticket(ctx) is None
    assert span_readers.launches_per_batch(ctx) is None
    if red is None or "rk.flush" not in red.spans:
        assert span_readers.flush_host_ms_per_batch(ctx) is None


def test_a_trace_without_program_spans_reads_nothing(tmp_path):
    """The older chip trace (``forward_trace.xplane.pb.gz``), from a
    program with no spans and no scope map: the reduction finds the
    launches and nothing else, and the existing reduction is untouched
    by the new one."""
    path = _unzip("forward_trace.xplane.pb.gz", tmp_path)
    red = spans.reduce(str(path), None)
    assert red.n_devices == 1 and red.spans == {} and red.scope_s == {}
    assert red.launches > 0
    base = xtrace.reduce(str(path), kernels=("hamming_scores",))
    assert red.window_s == pytest.approx(base.window_s)


def _chip_spans(tmp_path):
    path = _unzip("forward_spans_trace.xplane.pb.gz", tmp_path)
    scopes = {(m, i): s for m, i, s in json.loads(
        (HERE / "data" / "forward_spans_scopes.json").read_text())}
    return path, scopes


def test_reduction_of_a_chip_trace_with_spans(tmp_path):
    """One traced second of the forward cell on a TPU v5e lite, with the
    program's op-scope map from the same process
    (``tests/record_spans_trace.py``): every span of the serving path is
    on the host plane, the selection's device time is found and within
    the device's busy time, and the launches per flush are counted."""
    path, scopes = _chip_spans(tmp_path)
    red = spans.reduce(str(path), scopes)
    base = xtrace.reduce(str(path), kernels=("hamming_scores",))
    assert red.n_devices == 1
    assert set(TABLE) <= set(red.spans), sorted(red.spans)
    n_flush = red.spans["rk.flush"][0].size
    assert n_flush > 0 and red.launches >= n_flush
    assert {"kmips.select", "kmips.scan", "kmips.rerank"} <= set(red.scope_s)
    assert 0 < red.scope_s["kmips.select"] <= base.busy_s
    assert sum(red.scope_s.values()) <= base.busy_s + 1e-9
    idle = base.window_s - base.busy_s
    assert all(0 <= v <= idle + 1e-9 for v in red.idle_by_span.values())
    assert red.idle_by_span["rk.flush"] > 0
    for name in TABLE:
        s, e = red.spans[name]
        assert np.all(e >= s)


def test_readers_find_the_trace_the_harness_took(tmp_path, monkeypatch):
    """``spans.current`` reads the newest trace under the harness's
    ``TRACE_DIR``, as during a traced run, reduces it once, and the four
    readers come out within the window's bounds."""
    path, scopes = _chip_spans(tmp_path)
    trace_dir = tmp_path / "trace" / "plugins" / "profile" / "run"
    trace_dir.mkdir(parents=True)
    shutil.copy(path, trace_dir / "host.xplane.pb")
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    monkeypatch.setattr(spans, "program_scopes", lambda: scopes)
    monkeypatch.setattr(spans, "_CACHE", {})
    base = xtrace.reduce(str(path), kernels=("hamming_scores",))
    tickets = len(base.kernels["hamming_scores"])
    ctx = SimpleNamespace(tickets=tickets)
    select = span_readers.select_ms_per_ticket(ctx)
    assert 0 < select <= 1e3 * base.busy_s / tickets
    assert span_readers.launches_per_batch(ctx) >= 1
    assert 0 < span_readers.flush_host_ms_per_batch(ctx) < 1e3
    assert spans.current() is spans.current()
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "none")
    assert spans.current() is None


def test_traced_forward_rehearsal_reads_spans_and_counters(tmp_path,
                                                          monkeypatch):
    """A traced run on the CPU at the rehearsal size: the host spans and
    the counters are read; the device metrics are left out, there being
    no device plane. (Its own trace directory, so that it cannot meet
    another traced test's.)"""
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    line = harness.run(FORWARD, 2**31 + 21, 1.0, True,
                       t_start=time.perf_counter(), rehearse=True)
    assert line["correct"], line["checks"]
    got = line["metrics"]
    assert got["queue_wait_ms"]["value"] > 0
    assert got["flush_host_ms_per_batch"]["value"] > 0
    assert "launches_per_batch" not in got
    assert "select_ms_per_ticket" not in got
