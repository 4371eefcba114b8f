"""One run of one cell: corpus, build, one tenant, warmup, an open-loop
window through ``ServingGateway.submit``, counters, answer check, result.

Every step reads its parameters from the cell's configuration and traffic
files (``spec.py``); nothing here names a cell.
"""

from __future__ import annotations

import contextlib
import gc
import json
import os
import queue
import shutil
import sys
import threading
import time
from types import SimpleNamespace

import numpy as np

from . import compare, corpus, reference, schedule, spec, xtrace

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
WAIT_AFTER_WINDOW_S = 60.0
TRACE_DIR = spec.ROOT / ".bench_trace"
TRACE_SECONDS = 5.0


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_info(chips: int, rehearse: bool) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"device: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    if not rehearse and (info["platform"] != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {info['platform']} device(s)")
    return info


def enable_compile_cache() -> str:
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` if set, else
    at the checkout's fixed ``.jax_cache``; every program is kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        spec.ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def sizes(cfg: dict, rehearse: bool) -> dict:
    out = {k: cfg[k] for k in ("n_items", "m_users", "d")}
    if rehearse:
        out.update(cfg["rehearse"])
    return out


def engine_config(cfg: dict, direction: str):
    from repro import get_config
    knobs = dict(cfg["engine"])
    ec = get_config(knobs.pop("preset")).replace(**knobs)
    if direction == "forward":
        ec = ec.replace(n_cand=cfg["forward_n_cand"])
    return ec


def traffic_queries(mix: dict, items, users, n: int, seed: int):
    """The (n, d) query rows of a run, drawn from the mix's pool."""
    import jax.numpy as jnp
    pool = mix["pool"]
    if pool["kind"] == "users":
        source, order = users, np.arange(users.shape[0])
    elif pool["kind"] == "head":
        source = items
        order = np.asarray(jnp.argsort(-jnp.linalg.norm(items, axis=-1)))
        order = order[:pool["size"]]
    else:
        raise ValueError(f"pool kind must be head|users, got "
                         f"{pool['kind']!r}")
    ids = order[schedule.positions(order.size, n, seed)]
    return source[jnp.asarray(ids)]


class Collector(threading.Thread):
    """Takes each ticket's answer to the host as it resolves, in arrival
    order, and stamps when the client holds it."""

    def __init__(self, direction: str, deadline: float, annotate):
        super().__init__(name="bench-collector", daemon=True)
        self.direction, self.deadline, self.annotate = (direction, deadline,
                                                        annotate)
        self.inbox: queue.Queue = queue.Queue()
        self.done, self.answers, self.errors = {}, {}, {}

    def run(self) -> None:
        while True:
            item = self.inbox.get()
            if item is None:
                return
            i, ticket = item
            try:
                with self.annotate("bench.result"):
                    r = ticket.result(
                        timeout=max(0.0, self.deadline - time.perf_counter()))
                    self.answers[i] = take_answer(self.direction, r)
                self.done[i] = time.perf_counter()
            except BaseException as e:  # noqa: BLE001 — a missing answer
                self.errors[i] = repr(e)


def profiler_options():
    """Device ops and host annotations; no Python call tracing, which
    slows the host it measures and fills the trace."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def warm_profiler() -> None:
    """Start and stop the profiler once in set-up: its first start holds
    the host for seconds, which inside the window would stall arrivals."""
    import jax
    t0 = time.perf_counter()
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=profiler_options())
    jax.profiler.stop_trace()
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    log(f"profiler warm start and stop: {time.perf_counter() - t0:.3f} s")


class TracedSpan:
    """The profiler traces the last ``TRACE_SECONDS`` of the window (all
    of a shorter one), marked by the host span ``bench.window``. The
    generator calls ``until`` before each arrival; the trace is written
    by ``finish``, once every answer is in, because writing it holds the
    host for seconds."""

    def __init__(self, on: bool, t_w0: float, seconds: float):
        self.start = t_w0 + seconds - min(seconds, TRACE_SECONDS)
        self.end = t_w0 + seconds
        self.todo = [self.start, self.end] if on else []
        self.mark = None

    def until(self, t: float) -> None:
        """Open or close the traced window at every switch due by ``t``."""
        import jax
        while self.todo and self.todo[0] <= t:
            time.sleep(max(0.0, self.todo.pop(0) - time.perf_counter()))
            if self.mark is None:
                shutil.rmtree(TRACE_DIR, ignore_errors=True)
                t0 = time.perf_counter()
                jax.profiler.start_trace(str(TRACE_DIR),
                                         profiler_options=profiler_options())
                self.mark = jax.profiler.TraceAnnotation("bench.window")
                self.mark.__enter__()
                log(f"trace start: {time.perf_counter() - t0:.3f} s")
            else:
                self.mark.__exit__(None, None, None)

    def finish(self) -> None:
        import jax
        if self.mark is not None:
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            log(f"trace written: {time.perf_counter() - t0:.3f} s")


def take_answer(direction: str, r) -> dict:
    if direction == "reverse":
        return {"bits": reference.pack(r.predictions),
                "tiles": r.stats.tiles_scanned, "funnel": r.funnel}
    return {"ids": np.asarray(r.ids), "vals": np.asarray(r.values)}


def per_layer(cell: dict, ctx) -> dict:
    out = {}
    for m in cell["per_layer"]:
        v = spec.reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, rehearse: bool = False, control: bool = False,
        sweep: float | None = None, keep_trace: str | None = None) -> dict:
    """One run; returns the result line (the contract's keys, ``checks``
    last). ``control`` puts the lower-precision reference in the program's
    place and skips build and window; ``sweep`` finds the knee from that
    rate (``run_sweep``) and returns what it found instead; ``keep_trace``
    is a file the traced window's ``.xplane.pb`` is copied to."""
    cell = spec.cell(workload)
    cfg, mix = cell["config"], cell["traffic"]
    device = device_info(cell["chips"], rehearse)
    import jax
    import jax.numpy as jnp
    log(f"compile cache: {enable_compile_cache()}")
    sys.path.insert(0, str(spec.ROOT / "src"))
    from repro import IndexArtifact
    from repro.engine import ServingGateway

    direction, k = mix["direction"], mix["k"]
    shape = sizes(cfg, rehearse)
    # a configuration with a ``data_seed`` serves one corpus and index to
    # every run, and the run's seed draws only its queries and arrivals
    k_data, k_build = jax.random.split(corpus.root_key(
        cfg.get("data_seed", seed)))
    items, users = corpus.make(k_data, **shape, **cfg["corpus"])
    jax.block_until_ready(users)
    log(f"corpus: {shape['n_items']} items x {shape['m_users']} users, "
        f"d={shape['d']}, {time.perf_counter() - t_start:.3f} s")
    sched = schedule.arrivals(mix, seconds, seed)
    n = sched.size
    qs = traffic_queries(mix, items, users, n, seed)
    qs_host = np.asarray(qs)
    if control:
        return run_control(cell, direction, k, items, users, qs, qs_host,
                           device)

    ec = engine_config(cfg, direction)
    t0 = time.perf_counter()
    art = IndexArtifact.build(items, users if direction == "reverse"
                              else None, k_build, config=ec)
    if direction == "forward":
        jax.block_until_ready(art.kmips_index)
    build_s = time.perf_counter() - t0
    timings = art.build_timings
    log(f"build: {build_s:.3f} s"
        + (f"; {timings.format()}" if timings is not None else ""))

    gw = ServingGateway(pool_workers=1)
    tenant = "tenant"
    gw.register(tenant, art, mode=direction)
    t0 = time.perf_counter()
    cells = gw.warmup(ks=(k,))
    warmup_s = time.perf_counter() - t0
    # every group size once, so eager padding and slicing compile here
    prime = (users[:1] if direction == "forward" else
             items[jnp.argsort(jnp.linalg.norm(items, axis=-1))[:1]])
    t0 = time.perf_counter()
    for g in range(1, ec.serve_batch_size + 1):
        for t in gw.submit(tenant, jnp.repeat(prime, g, axis=0), k=k):
            take_answer(direction, t.result())
    prime_s = time.perf_counter() - t0
    rows = [qs[i] for i in range(n)]
    jax.block_until_ready(rows)
    log(f"warmup: {cells} programs {warmup_s:.3f} s; priming "
        f"{prime_s:.3f} s")

    compiles = {"window": False, "n": 0}

    def on_event(event, duration, **_):
        if event == BACKEND_COMPILE and compiles["window"]:
            compiles["n"] += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)

    if trace:
        warm_profiler()
    if sweep is not None:
        return run_sweep(gw, tenant, mix, items, users, seconds, seed,
                         sweep, t_start, compiles)
    setup_s = time.perf_counter() - t_start
    w = drive(gw, tenant, mix, rows, sched, seconds, trace, compiles)
    coll, lat_ms, in_window = w.coll, w.lat_ms, w.in_window
    stats0, stats1, span = w.stats0, w.stats1, w.span
    traces_after = gw.stats().traces_after_warmup
    mem = jax.local_devices()[0].memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    device["memory_peak_bytes"] = peak
    missing = n - len(coll.answers)
    log(f"traces_after_warmup={traces_after} "
        f"compiles_in_window={compiles['n']}")
    for i, err in sorted(coll.errors.items())[:3]:
        log(f"ticket {i} failed: {err}")

    metrics = {}
    if not trace:
        values = {"ticket_p50_ms": schedule.pct(lat_ms, .50),
                  "tickets_per_s": w.served_per_s,
                  "hbm_peak_gb": peak / 1e9,
                  "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}
        breakdown = None
    else:
        xplane = xtrace.newest_xplane(str(TRACE_DIR))
        if keep_trace:
            shutil.copyfile(xplane, keep_trace)
        red = xtrace.reduce(xplane, kernels=("hamming_scores",))
        ctx = SimpleNamespace(
            direction=direction, batch_size=ec.serve_batch_size,
            stats0=stats0, stats1=stats1, answers=coll.answers, trace=red,
            lat_ms=lat_ms,
            tickets=sum(1 for t in coll.done.values()
                                     if span.start <= t <= span.end))
        metrics = per_layer(cell, ctx)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        breakdown = xtrace.breakdown(red)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    # the reference runs once the program's state is gone; tickets still
    # queued past the wait are already counted missing
    gw.close(drain=False)
    del gw, art, rows
    gc.collect()
    t0 = time.perf_counter()
    answers = coll.answers
    done = sorted(answers)
    limits = json.loads((spec.BENCH_DIR / "limits" /
                         f"{workload}.json").read_text())
    if not done:
        nums = compare.NOTHING[direction]
    elif direction == "reverse":
        nums = compare.reverse_numbers(
            items, users, k, ec.tie_eps, qs_host[done],
            [answers[i]["bits"] for i in done])
    else:
        nums = compare.forward_numbers(
            items, k, qs_host[done],
            np.stack([answers[i]["ids"] for i in done]),
            np.stack([answers[i]["vals"] for i in done]))
    checks = compare.checks(direction, nums, limits, cfg["guarantees"],
                            missing)
    log(f"reference: {time.perf_counter() - t0:.3f} s; "
        + ", ".join(f"{k_}={v}" for k_, v in nums.items()))
    return result(checks, n, missing, metrics, device, breakdown)


def drive(gw, tenant, mix, rows, sched, seconds, trace, compiles):
    """One open-loop window: submit ``rows[i]`` at ``sched[i]``, collect
    every answer (waiting up to ``WAIT_AFTER_WINDOW_S`` past the window),
    and log what the generator and the queue did."""
    import jax
    direction, k, n = mix["direction"], mix["k"], sched.size
    annotate = (jax.profiler.TraceAnnotation if trace
                else lambda name: contextlib.nullcontext())
    stats0 = gw.stats().tenants[tenant]
    t_w0 = time.perf_counter() + 0.05
    span = TracedSpan(trace, t_w0, seconds)
    coll = Collector(direction, t_w0 + seconds + WAIT_AFTER_WINDOW_S,
                     annotate)
    coll.start()
    lateness = np.zeros(n)
    compiles["window"] = True
    time.sleep(max(0.0, t_w0 - time.perf_counter()))
    for i in range(n):
        target = t_w0 + sched[i]
        span.until(target)
        delay = target - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        lateness[i] = time.perf_counter() - target
        with annotate("bench.submit"):
            coll.inbox.put((i, gw.submit(tenant, rows[i], k=k)))
    span.until(t_w0 + seconds)
    time.sleep(max(0.0, t_w0 + seconds - time.perf_counter()))
    coll.inbox.put(None)
    coll.join()
    stats1 = gw.stats().tenants[tenant]
    compiles["window"] = False
    span.finish()

    end = t_w0 + seconds
    lat_ms = [((coll.done.get(i, coll.deadline)) - (t_w0 + sched[i])) * 1e3
              for i in range(n)]
    in_window = sum(1 for t in coll.done.values() if t <= end)
    # the window closes when every ticket sent in it is answered (or
    # waiting stops): all of that work over all of that time
    t_last = coll.deadline if len(coll.done) < n else max(coll.done.values())
    served_per_s = len(coll.done) / (t_last - t_w0)
    log(f"window: {n} tickets offered at {mix['rate']}/s over {seconds} s; "
        f"{len(coll.answers)} answered, {n - len(coll.answers)} missing, "
        f"{in_window} completed inside the window; percentiles over {n} "
        f"tickets")
    log(f"served: {len(coll.answers)} tickets in {t_last - t_w0:.3f} s "
        f"from the window's start, {served_per_s:.6f} tickets/s")
    log(f"generator lateness: p95 {schedule.pct(lateness, .95) * 1e3:.3f} "
        f"ms, max {lateness.max() * 1e3:.3f} ms")
    half = (n + 1) // 2
    first, second = np.mean(lat_ms[:half]), np.mean(lat_ms[half:] or [0.0])
    log(f"backlog: mean latency {first:.3f} ms over the first half of "
        f"arrivals, {second:.3f} ms over the second; {n - in_window} "
        f"tickets unfinished at the window's end")
    overloaded = bool(len(coll.answers) < n or second > 1.5 * first + 200
                      or n - in_window > 0.25 * n)
    return SimpleNamespace(coll=coll, lat_ms=lat_ms, in_window=in_window,
                           served_per_s=served_per_s, stats0=stats0,
                           stats1=stats1, span=span, overloaded=overloaded)


def run_sweep(gw, tenant, mix, items, users, seconds, seed, r0, t_start,
              compiles):
    """The knee: windows at doubling rates from ``r0`` until one holds a
    growing backlog (a second-half mean latency over 1.5 x the first
    half's + 200 ms, over a quarter of the tickets unfinished at the
    window's end, or a ticket missing), or at halving rates until one
    does not, then one bisection. Each window waits for its tickets
    before the next starts. Returns the rates tried and 0.8 x the
    highest rate that held."""
    log(f"sweep: set-up {time.perf_counter() - t_start:.3f} s")
    tried, good, bad, r = [], None, None, r0

    def one(rate, step):
        m = dict(mix, rate=rate)
        sched = schedule.arrivals(m, seconds, seed + step)
        qs = traffic_queries(m, items, users, sched.size, seed + step)
        rows = [qs[i] for i in range(sched.size)]
        w = drive(gw, tenant, m, rows, sched, seconds, False, compiles)
        tried.append({"rate": rate, "overloaded": w.overloaded,
                      "p50_ms": float(schedule.pct(w.lat_ms, .5)),
                      "p95_ms": float(schedule.pct(w.lat_ms, .95)),
                      "done_per_s": w.in_window / seconds,
                      "served_per_s": w.served_per_s})
        log(f"sweep: {tried[-1]}")
        return w.overloaded
    # doubling from r0 while it holds, or halving while it does not
    for step in range(10):
        if one(r, step):
            bad = r
            if good is not None:
                break
            r = r / 2
        else:
            good = r
            if bad is not None:
                break
            r = 2 * r
    if good is not None and bad is not None:
        mid = (good + bad) / 2
        if one(mid, 10):
            bad = mid
        else:
            good = mid
    log(f"sweep: compiles_in_windows={compiles['n']}")
    return {"sweep": tried, "knee": good, "first_overloaded": bad,
            "rate": None if good is None else 0.8 * good}


def run_control(cell, direction, k, items, users, qs, qs_host, device):
    """The reference one precision step below the configuration's answers
    the run's tickets, judged as the program's would be."""
    cfg = cell["config"]
    low = reference.CONTROL[cfg["precision"]]
    tie_eps = engine_config(cfg, direction).tie_eps
    limits = json.loads((spec.BENCH_DIR / "limits" /
                         f"{cell['name']}.json").read_text())
    n = qs_host.shape[0]
    if direction == "reverse":
        s_k = reference.kth_scores(items, users, k, dtype=low)
        bits = []
        for lo in range(0, n, compare.CHUNK):
            a = reference.reverse_answers(users, s_k, qs[lo:lo + compare.CHUNK],
                                          tie_eps, dtype=low)
            bits += [reference.pack(row) for row in np.asarray(a)]
        nums = compare.reverse_numbers(items, users, k, tie_eps, qs_host,
                                       bits)
    else:
        vals, ids = [], []
        for lo in range(0, n, compare.CHUNK):
            v, i = reference.forward_topk(items, qs[lo:lo + compare.CHUNK],
                                          k=k, dtype=low)
            vals.append(np.asarray(v))
            ids.append(np.asarray(i))
        nums = compare.forward_numbers(items, k, qs_host,
                                       np.concatenate(ids),
                                       np.concatenate(vals))
    log(f"control ({low.__name__} reference): "
        + ", ".join(f"{k_}={v}" for k_, v in nums.items()))
    checks = compare.checks(direction, nums, limits, cfg["guarantees"], 0)
    return result(checks, n, 0, {}, device, None)


def result(checks, attempted, failed, metrics, device, breakdown) -> dict:
    for c in checks:
        log(f"check {c['name']} {c['value']!r} {c['op']} {c['limit']!r} "
            f"{'ok' if c['ok'] else 'FAILED'}")
    line = {"correct": all(c["ok"] for c in checks), "attempted": attempted,
            "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"],
                                  "op": c["op"]} for c in checks}
    return line
