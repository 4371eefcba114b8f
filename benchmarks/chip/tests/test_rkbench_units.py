"""CPU tests of the benchmark's arithmetic and of finding parts by name."""

import gzip
import pathlib
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from rkbench import harness, readers, schedule, spec, xtrace  # noqa: E402


def test_poisson_count_fixed_and_inside_window():
    mix = {"arrivals": "poisson", "rate": 7.0}
    a = schedule.arrivals(mix, 30.0, seed=5)
    b = schedule.arrivals(mix, 30.0, seed=2**33 + 5)
    assert a.size == b.size == 210
    assert np.all(np.diff(a) >= 0) and a[0] >= 0 and a[-1] < 30.0
    assert not np.array_equal(a, b)
    assert np.array_equal(a, schedule.arrivals(mix, 30.0, seed=5))


def test_unknown_arrivals_rejected():
    with pytest.raises(ValueError):
        schedule.arrivals({"arrivals": "bursty", "rate": 1.0}, 1.0, 0)


def test_unknown_pool_rejected():
    with pytest.raises(ValueError):
        harness.traffic_queries({"pool": {"kind": "catalog"}}, None, None,
                                4, 0)


def test_positions_cover_pool_evenly():
    pos = schedule.positions(128, 256, seed=9)
    assert np.bincount(pos, minlength=128).tolist() == [2] * 128
    few = np.sort(schedule.positions(17770, 10, seed=3))
    assert np.all(np.diff(few) >= 1776) and few.max() < 17770


@pytest.mark.parametrize("q,want", [(0.0, 1), (0.5, 51), (0.95, 95),
                                    (1.0, 100)])
def test_nearest_rank_percentile(q, want):
    # rank round(q * (n - 1)), halves up: the median of 1..100 is the 51st
    vals = list(range(100, 0, -1))
    assert schedule.pct(vals, q) == want


def test_every_named_part_loads():
    bench = spec.benchmark()
    for w in bench["workloads"]:
        c = spec.cell(w["name"], bench)
        assert c["traffic"]["direction"] in ("reverse", "forward")
        assert {m["name"] for m in c["end_to_end"]} >= {"setup_s"}
        assert c["per_layer"]
        limits = spec.BENCH_DIR / "limits" / f"{w['name']}.json"
        assert limits.is_file()
    for m in bench["per_layer"]:
        assert callable(spec.reader(m["name"]))
    for c in bench["configs"]:
        assert (spec.ROOT / c["file"]).is_file()
        assert spec.config(c["name"])["name"] == c["name"]


def test_unknown_names_are_errors():
    with pytest.raises(spec.SpecError):
        spec.cell("netflix.no-such-mix")
    with pytest.raises(spec.SpecError):
        spec.reader("no_such_metric")


def test_missing_chip_is_an_error():
    with pytest.raises(harness.NoChip):
        harness.device_info(1, rehearse=False)


def test_union_and_gaps():
    s = np.array([0.0, 5.0, 2.0, 20.0])
    e = np.array([3.0, 8.0, 4.0, 25.0])
    assert xtrace.union_seconds(s, e) == 4.0 + 3.0 + 5.0
    gs, ge = xtrace.gaps(s, e, -1.0, 30.0)
    assert list(zip(gs, ge)) == [(-1.0, 0.0), (4.0, 5.0), (8.0, 20.0),
                                 (25.0, 30.0)]
    assert xtrace.union_seconds(np.array([]), np.array([])) == 0.0


def test_reduction_of_a_chip_trace(tmp_path):
    """``xtrace`` on one second of the forward cell traced on a TPU v5e
    lite (``run.py --workload amazon-cds.forward-k50 --seconds 1 --trace
    1 --keep-trace <file>``, gzipped): the window and the device plane are
    found, no share passes the whole, and the scan kernel's events are
    there for the readers."""
    raw = (HERE / "data" / "forward_trace.xplane.pb.gz").read_bytes()
    path = tmp_path / "chip.xplane.pb"
    path.write_bytes(gzip.decompress(raw))
    red = xtrace.reduce(str(path), kernels=("hamming_scores",))
    assert red.n_devices == 1
    assert 0 < red.busy_s <= red.window_s
    assert all(0 < sec <= red.window_s for sec in red.op_seconds.values())
    events = red.kernels["hamming_scores"]
    assert events and sum(sec for sec, _ in events) <= red.busy_s
    idle = sum(sec for _, sec in red.idle_gaps)
    assert 0 < idle <= red.window_s - red.busy_s + 1e-9
    ctx = harness.SimpleNamespace(trace=red, tickets=len(events))
    assert 0 < readers.device_idle_share(ctx) < 100
    assert readers.hamming_scores_ms_per_ticket(ctx) > 0
    out = xtrace.breakdown(red)
    assert 0 < len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10


@pytest.mark.parametrize("r0,want_knee,want_bad", [(0.5, 3.0, 4.0),
                                                    (16.0, 3.0, 4.0),
                                                    (2.0, 3.0, 4.0)])
def test_sweep_doubles_or_halves_then_bisects(monkeypatch, r0, want_knee,
                                              want_bad):
    # a server that holds any rate up to 3.2 tickets/s
    def fake_drive(gw, tenant, mix, rows, sched, seconds, trace, compiles):
        assert len(rows) == sched.size == round(mix["rate"] * seconds)
        return harness.SimpleNamespace(
            overloaded=mix["rate"] > 3.2, lat_ms=[1.0], in_window=sched.size,
            served_per_s=mix["rate"])
    monkeypatch.setattr(harness, "drive", fake_drive)
    monkeypatch.setattr(harness, "traffic_queries",
                        lambda mix, items, users, n, seed: list(range(n)))
    out = harness.run_sweep(None, "t", {"rate": r0}, None, None, 10.0, 1,
                            r0, 0.0, {"window": False, "n": 0})
    assert out["knee"] == want_knee and out["first_overloaded"] == want_bad
    assert out["rate"] == pytest.approx(0.8 * want_knee)
