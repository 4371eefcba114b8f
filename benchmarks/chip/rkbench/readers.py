"""The arithmetic of the per-layer metrics. Each metric's own reader,
``metrics/<name>.py``, names one function here; a function that finds
nothing to read returns None and the metric is left out of the line.

``ctx`` carries what a traced run gathered: the tenant's ``RuntimeStats``
at the window's start and end (``stats0``, ``stats1``), the answers, every
ticket's latency (``lat_ms``), the reduced device trace (``trace``,
``xtrace.Reduced``) and the tickets completed inside the traced window.
"""

from __future__ import annotations

import numpy as np

from . import schedule

def batch_occupancy(ctx):
    """% of micro-batch slots filled: completed / (batches x batch size),
    from ``RuntimeStats`` (engine/runtime.py)."""
    batches = ctx.stats1.batches - ctx.stats0.batches
    if batches <= 0:
        return None
    done = ctx.stats1.completed - ctx.stats0.completed
    return 100.0 * done / (batches * ctx.batch_size)


def tail_p95_ms(ctx):
    """95th percentile (nearest rank) of every offered ticket's latency
    from its intended arrival, in the traced run: the tail that spreads
    too widely from run to run to carry an end-to-end bound."""
    if not ctx.lat_ms:
        return None
    return schedule.pct(ctx.lat_ms, .95)


def device_idle_share(ctx):
    """% of the traced window in which no operation ran on the device."""
    red = ctx.trace
    if red is None or red.n_devices == 0 or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)


def _events(ctx, kernel):
    return ctx.trace.kernels.get(kernel, []) if ctx.trace is not None else []


def hamming_scores_ms_per_ticket(ctx):
    """Device ms of the ``hamming_scores`` kernel (kernels/hamming_scan.py)
    in the traced window, over the tickets completed in it."""
    events = _events(ctx, "hamming_scores")
    if not events or ctx.tickets == 0:
        return None
    return 1e3 * sum(sec for sec, _ in events) / ctx.tickets


def scan_lane_share(ctx):
    """% of the users each reverse query starts with that the plan leaves
    to the execute phase's scan, summed over the pruning funnels
    (engine.PruningFunnel) of the window's micro-batches."""
    if ctx.direction != "reverse":
        return None
    funnels = {id(a["funnel"]): a["funnel"] for a in ctx.answers.values()}
    total = sum(f.users_total for f in funnels.values())
    if total == 0:
        return None
    return 100.0 * sum(f.scan_lanes for f in funnels.values()) / total


def tiles_per_ticket(ctx):
    """Execute-phase tile visits per reverse ticket: the answered tickets'
    ``QueryStats.tiles_scanned`` (core/sah.py) over their number."""
    if ctx.direction != "reverse" or not ctx.answers:
        return None
    tiles = sum(int(np.asarray(a["tiles"])) for a in ctx.answers.values())
    return tiles / len(ctx.answers)
