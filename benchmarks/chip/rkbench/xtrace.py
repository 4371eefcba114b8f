"""Reduce a profiler trace (``.xplane.pb``) to device busy time, per-op
device time and the longest device idle gaps, named by host span.

Read with ``jax.profiler.ProfileData``. The traced window is the host span
named ``bench.window`` that the harness opens around its arrivals; device
and host events share the profiler's clock. Device operations are the
events of each TPU plane's ``XLA Ops`` line; busy time is the union of
their intervals inside the window, averaged over the device planes.
"""

from __future__ import annotations

import collections
import glob
import os
from typing import NamedTuple

import numpy as np

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
TOP = 10
ATTRIBUTED_GAPS = 2000


class Reduced(NamedTuple):
    window_s: float            # length of the traced window
    busy_s: float              # union of device-op time, mean over devices
    op_seconds: dict           # op name -> device seconds inside the window
    kernels: dict              # kernel name -> [(seconds, {stat: value})]
    idle_gaps: list            # [(host span name, seconds)], longest first
    n_devices: int


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _stats(event) -> dict:
    out = {}
    for name, value in event.stats:
        out[name] = value
    return out


def union_seconds(starts: np.ndarray, ends: np.ndarray) -> float:
    """Length of the union of [start, end) intervals (any units)."""
    if starts.size == 0:
        return 0.0
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    # a new run starts where an interval begins after every earlier end
    new = np.concatenate([[True], s[1:] > e[:-1]])
    run_id = np.cumsum(new) - 1
    run_start = s[new]
    run_end = np.zeros(run_start.size)
    np.maximum.at(run_end, run_id, e)
    return float(np.sum(run_end - run_start))


def gaps(starts: np.ndarray, ends: np.ndarray, lo: float, hi: float):
    """The [start, end) intervals of [lo, hi) that no interval covers."""
    if starts.size == 0:
        return np.array([lo]), np.array([hi])
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    g_start = np.concatenate([[lo], e])
    g_end = np.concatenate([s, [hi]])
    g_start = np.clip(g_start, lo, hi)
    g_end = np.clip(g_end, lo, hi)
    keep = g_end > g_start
    return g_start[keep], g_end[keep]


def _name_gaps(g_start, g_end, host) -> list:
    """Attribute the longest gaps to the innermost host event live at
    each gap's midpoint; seconds summed per name, largest first."""
    if g_start.size == 0:
        return []
    h_start, h_end, h_name = host
    longest = np.argsort(g_start - g_end)[:ATTRIBUTED_GAPS]
    total = collections.Counter()
    for i in longest:
        mid = 0.5 * (g_start[i] + g_end[i])
        live = np.nonzero((h_start <= mid) & (h_end > mid))[0]
        if live.size:
            j = live[np.argmin(h_end[live] - h_start[live])]
            name = h_name[j]
        else:
            name = "(no host span)"
        total[name] += (g_end[i] - g_start[i]) * 1e-9
    return total.most_common(TOP)


def _kernel_of(event, kernels) -> str | None:
    """Which of ``kernels`` the device event runs: its name, or a kernel
    name inside one of its text stats (a Pallas call's HLO carries
    ``kernel_name``)."""
    for k in kernels:
        if k in event.name:
            return k
    for _, value in event.stats:
        if isinstance(value, str):
            for k in kernels:
                if k in value:
                    return k
    return None


def reduce(path: str, kernels=()) -> Reduced:
    """Reduce the trace at ``path``; ``kernels`` names the device ops whose
    individual events (duration and stats) are kept."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    window = None
    host_s, host_e, host_n = [], [], []
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.end_ns)
                    else:
                        host_s.append(ev.start_ns)
                        host_e.append(ev.end_ns)
                        host_n.append(ev.name)
        elif plane.name.startswith("/device:TPU:"):
            devices.append(plane)
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    lo, hi = window
    host = (np.asarray(host_s), np.asarray(host_e), host_n)
    op_ns = collections.Counter()
    kept = {k: [] for k in kernels}
    busy, idle = [], collections.Counter()
    which: dict = {}            # op name -> kernel name or None
    for plane in devices:
        starts, ends = [], []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
                if e <= s:
                    continue
                starts.append(s)
                ends.append(e)
                op_ns[ev.name] += e - s
                if ev.name not in which:
                    which[ev.name] = _kernel_of(ev, kernels)
                if which[ev.name] is not None:
                    kept[which[ev.name]].append(((e - s) * 1e-9,
                                                 _stats(ev)))
        starts, ends = np.asarray(starts), np.asarray(ends)
        busy.append(union_seconds(starts, ends) * 1e-9)
        for name, sec in _name_gaps(*gaps(starts, ends, lo, hi), host):
            idle[name] += sec / len(devices)
    return Reduced(window_s=(hi - lo) * 1e-9,
                   busy_s=float(np.mean(busy)) if busy else 0.0,
                   op_seconds={k: v * 1e-9 / max(len(devices), 1)
                               for k, v in op_ns.items()},
                   kernels=kept,
                   idle_gaps=idle.most_common(TOP),
                   n_devices=len(devices))


def breakdown(red: Reduced) -> dict:
    """The result line's ``breakdown``: the device ops that took most
    time, and the idle gaps by what the host was doing."""
    ops = sorted(red.op_seconds.items(), key=lambda kv: -kv[1])[:TOP]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in red.idle_gaps]}
