"""The arithmetic of the per-layer metrics that read the program's own
spans and counters (``spans.py``, ``RuntimeStats.queue_wait_s``). As in
``readers.py``, each ``metrics/<name>.py`` names one function here, and a
function that finds nothing to read — a program without these spans or
counters, or a trace with no device plane — returns None.
"""

from __future__ import annotations

from . import harness, spans

FLUSH = "rk.flush"
SELECT = "kmips.select"


def queue_wait_ms(ctx):
    """Mean host ms a ticket waited from admission to its batch's
    formation, linger included: Δ``RuntimeStats.queue_wait_s`` /
    Δ``completed`` over the traced run."""
    w0 = getattr(ctx.stats0, "queue_wait_s", None)
    w1 = getattr(ctx.stats1, "queue_wait_s", None)
    done = ctx.stats1.completed - ctx.stats0.completed
    if w0 is None or w1 is None or done <= 0:
        return None
    batches = ctx.stats1.batches - ctx.stats0.batches
    linger = ctx.stats1.linger_s - ctx.stats0.linger_s
    wait_ms = 1e3 * (w1 - w0) / done
    harness.log(f"waits: queue {wait_ms:.4f} ms per ticket over {done} "
                f"tickets; linger {1e3 * linger / max(batches, 1):.4f} ms "
                f"per batch over {batches} batches")
    return wait_ms


def _flushes(red):
    if red is None or FLUSH not in red.spans:
        return None
    return red.spans[FLUSH]


def flush_host_ms_per_batch(ctx):
    """Mean host ms of the ``rk.flush`` spans (one micro-batch's stack,
    pad, launch and split) that start in the traced window."""
    f = _flushes(spans.current())
    if f is None:
        return None
    start, end = f
    return float((end - start).mean()) * 1e-6


def launches_per_batch(ctx):
    """Device program launches (``XLA Modules`` events) in the traced
    window over the ``rk.flush`` spans in it."""
    red = spans.current()
    f = _flushes(red)
    if f is None or red.n_devices == 0:
        return None
    return red.launches / f[0].size


def select_ms_per_ticket(ctx):
    """Device ms attributed to the ``kmips.select`` stage (the top-n_cand
    over the Hamming scores) in the traced window, over the tickets
    completed in it."""
    red = spans.current()
    if red is None or SELECT not in red.scope_s or ctx.tickets == 0:
        return None
    return 1e3 * red.scope_s[SELECT] / ctx.tickets
