"""Reduce the traced window's profile to the serving path's own spans and
launches: the ``rk.*`` host spans the runtime and servers open, the
``XLA Modules`` launches on each device plane, device-op time by the
``kmips.*`` stage the program's ``serving.op_scopes()`` puts each
instruction in, and the device-idle time while each span is open.

``xtrace`` and its ``Reduced`` stay as they are; the readers of
``metrics/`` reach this reduction through ``current()``, which finds the
trace the harness has just taken under ``harness.TRACE_DIR`` (it is there
while the readers run) and reduces it once. Against a program that opens
no such spans, or has no ``op_scopes``, every field is simply empty.
"""

from __future__ import annotations

import collections
import os
import re
import sys
from typing import NamedTuple

import numpy as np

from . import harness, xtrace

SPAN_PREFIX = "rk."
MODULES_LINE = "XLA Modules"
_INSTR = re.compile(r"^%?([^\s=]+) =")


class Spans(NamedTuple):
    window_s: float        # length of the traced window
    spans: dict            # host span name -> (start_ns, end_ns) arrays,
    #                        every span of that name starting in the window
    launches: float        # module launches starting in the window, mean
    #                        over device planes
    scope_s: dict          # kmips.* scope -> union of its device-op time
    #                        in the window, mean over device planes
    idle_by_span: dict     # rk.* span name -> device-idle seconds while one
    #                        is open, mean over device planes
    n_devices: int


def runs(starts, ends):
    """Disjoint, sorted [start, end) runs covering the union of the
    intervals."""
    starts, ends = np.asarray(starts, float), np.asarray(ends, float)
    if starts.size == 0:
        return np.zeros(0), np.zeros(0)
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    new = np.concatenate([[True], s[1:] > e[:-1]])
    last = np.concatenate([new[1:], [True]])
    return s[new], e[last]


def overlap(a, b) -> float:
    """Length of the intersection of two unions of intervals, each given
    as (starts, ends)."""
    (a_s, a_e), (b_s, b_e) = runs(*a), runs(*b)
    total, i, j = 0.0, 0, 0
    while i < a_s.size and j < b_s.size:
        lo, hi = max(a_s[i], b_s[j]), min(a_e[i], b_e[j])
        if hi > lo:
            total += hi - lo
        if a_e[i] < b_e[j]:
            i += 1
        else:
            j += 1
    return total


def instruction(op_name: str) -> str | None:
    """The instruction name of a device op event (``%sort.10 = ...``)."""
    m = _INSTR.match(op_name)
    return m.group(1) if m else None


def _module(name: str) -> str:
    """``jit__scan(1420...)`` -> ``jit__scan``."""
    return name.split("(", 1)[0]


def reduce(path: str, scopes: dict | None = None) -> Spans:
    """Reduce the trace at ``path``; ``scopes`` is the program's
    ``{(module, instruction): scope}`` map (None: no scope times)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    window, host = None, collections.defaultdict(lambda: ([], []))
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == xtrace.WINDOW_SPAN:
                        window = (ev.start_ns, ev.end_ns)
                    elif ev.name.startswith(SPAN_PREFIX):
                        host[ev.name][0].append(ev.start_ns)
                        host[ev.name][1].append(ev.end_ns)
        elif plane.name.startswith("/device:TPU:"):
            devices.append(plane)
    if window is None:
        raise ValueError(f"no {xtrace.WINDOW_SPAN!r} span in {path}")
    lo, hi = window
    spans = {}
    for name, (s, e) in host.items():
        s, e = np.asarray(s, float), np.asarray(e, float)
        keep = (s >= lo) & (s < hi)
        if keep.any():
            spans[name] = (s[keep], e[keep])
    launches, scope_ns = [], collections.Counter()
    idle = collections.Counter()
    scopes = scopes or {}
    for plane in devices:
        lines = {line.name: list(line.events) for line in plane.lines}
        mods = sorted((ev.start_ns, ev.end_ns, _module(ev.name))
                      for ev in lines.get(MODULES_LINE, []))
        launches.append(sum(1 for s, _, _ in mods if lo <= s < hi))
        m_start = np.asarray([m[0] for m in mods], float)
        op_s, op_e = [], []
        by_scope = collections.defaultdict(lambda: ([], []))
        for ev in lines.get(xtrace.OPS_LINE, []):
            s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
            if e <= s:
                continue
            op_s.append(s)
            op_e.append(e)
            i = int(np.searchsorted(m_start, ev.start_ns, "right")) - 1
            if i < 0 or mods[i][1] < ev.start_ns:
                continue
            scope = scopes.get((mods[i][2], instruction(ev.name)))
            if scope is not None:
                by_scope[scope][0].append(s)
                by_scope[scope][1].append(e)
        for scope, (s, e) in by_scope.items():
            scope_ns[scope] += xtrace.union_seconds(np.asarray(s, float),
                                                    np.asarray(e, float))
        free = xtrace.gaps(np.asarray(op_s, float), np.asarray(op_e, float),
                           float(lo), float(hi))
        for name, (s, e) in spans.items():
            idle[name] += overlap((np.clip(s, lo, hi), np.clip(e, lo, hi)),
                                  free)
    n = max(len(devices), 1)
    return Spans(window_s=(hi - lo) * 1e-9, spans=spans,
                 launches=float(np.mean(launches)) if launches else 0.0,
                 scope_s={k: v * 1e-9 / n for k, v in scope_ns.items()},
                 idle_by_span={k: v * 1e-9 / n for k, v in idle.items()},
                 n_devices=len(devices))


def program_scopes() -> dict | None:
    """The running program's ``serving.op_scopes()``, or None where the
    program has none."""
    serving = sys.modules.get("repro.engine.serving")
    read = getattr(serving, "op_scopes", None)
    return read() if read is not None else None


def log_summary(red: Spans) -> None:
    log = harness.log
    for name in sorted(red.spans):
        s, e = red.spans[name]
        d = (e - s) * 1e-6
        log(f"span {name}: {s.size} in the window, mean {d.mean():.4f} ms, "
            f"p50 {np.median(d):.4f} ms, total {d.sum() * 1e-3:.4f} s")
    log(f"launches: {red.launches:.1f} module launches in the window; "
        f"device time by scope: " + ", ".join(
            f"{k} {v:.6f} s" for k, v in sorted(red.scope_s.items())))
    log("idle by program span: " + ", ".join(
        f"{k} {v:.6f} s" for k, v in sorted(red.idle_by_span.items(),
                                            key=lambda kv: -kv[1])))


_CACHE: dict = {}


def current() -> Spans | None:
    """The reduction of the trace the harness took in this run (reduced
    once, its summary logged), or None when there is no trace."""
    try:
        path = xtrace.newest_xplane(str(harness.TRACE_DIR))
    except FileNotFoundError:
        return None
    st = os.stat(path)
    key = (path, st.st_mtime_ns, st.st_size)
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = reduce(path, program_scopes())
        log_summary(_CACHE[key])
    return _CACHE[key]
