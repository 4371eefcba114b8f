"""Threaded serving runtime: ticket pipeline + background compaction.

DESIGN.md SS12 is the contract. ``engine/serving.py`` gives the repo
micro-batched serving as a *library* — callers submit tickets and then
flush on their own thread, and ``IndexArtifact.compact()`` stops the world
to rebuild. This module is the missing *loop*: a ``ServingRuntime`` wraps
either server in a thread pipeline so submitters get futures, flushes
happen off the caller's thread, and compaction runs in the background and
hot-swaps in between flushes.

Architecture (one runtime = up to three thread roles + the callers):

  callers ──submit──> admission deque ──workers──> dispatch ──> completion
                                            │         queue        thread
                                            │ (dispatch lock)        │
  maintenance thread ──compact off-thread──swap                  futures set

  * **admission**: ``submit`` validates the query up front
    (``serving.validate_query_rows``), enqueues one ``ServeTicket`` per
    row, and returns immediately — the ticket is a future
    (``result(timeout=)`` blocks, ``done()`` polls).
  * **workers** drain the queue into micro-batches of the server's
    ``serve_batch_size``: a batch is the longest run of queue-head tickets
    sharing one ``(k, n_cand, scan)`` signature, so every dispatch goes
    through the server's own ``_flush_batch`` — the *same* code path the
    synchronous ``flush`` uses, with the same padding. Runtime answers are
    therefore bitwise identical to library-mode serving, and compile
    counts stay pinned at one per batch shape (partial batches pad, they
    never shrink the shape). With a bucket ladder configured
    (``EngineConfig.serve_buckets``, DESIGN.md SS14) a partial run pads
    only up to the nearest rung (``server.bucket_for``) instead of the
    full batch — fewer dead rows per dispatch, same bitwise answers —
    and a run already sitting on a rung skips the linger entirely.
    ``ServingRuntime(warmup=True)`` precompiles every rung's executable
    before the first ticket, so bucketing never *adds* traces at
    runtime: ``stats.traces_after_warmup`` stays 0.
  * the **completion queue** decouples dispatch from reply: workers hand
    finished batches to a completion thread that resolves the futures, so
    a slow consumer can never stall the dispatch loop.
  * the **maintenance thread** (``compaction=True``) watches the live
    artifact's delta buffer; past ``compact_fill`` (or on
    ``request_compaction()``) it snapshots the live version, builds the
    next base off-thread via the staged build pipeline
    (``IndexArtifact.compact(policy=...)`` — XLA releases the GIL, so
    dispatch keeps flowing), then re-stages any churn that raced the build
    (``artifact.reconcile_compaction``) and ``swap()``s the result in
    under the dispatch lock — between flushes, never during one. With
    ``artifact_dir`` set, each compacted version is persisted with the
    ``keep=`` GC policy (the just-saved step is always protected).

Locking discipline (deadlock-free by ordering):

  * ``_admit`` (condition) guards the ticket deque + counters;
  * ``_dispatch_lock`` serializes batch dispatch with ``swap`` — a flush
    and a swap can never interleave, which is what "pending tickets
    survive a swap" means under threads;
  * ``_mutate_lock`` serializes artifact-version edits (staging mutations
    vs. compaction reconcile). Lock order is always mutate -> dispatch;
    workers take only the dispatch lock.

Deadlines: a ticket carries an optional wall-clock budget. Expiry is
checked at batch-formation time — an expired ticket is failed with
``TicketExpired`` *before* dispatch (in-flight batches are never
interrupted; XLA dispatches are not cancellable), so one stalled consumer
or a deep backlog can't wedge every later ticket behind work nobody
wants. Per-batch, expiry costs one clock read.

Tracing: each stage a ticket crosses is a ``jax.profiler.TraceAnnotation``
named ``rk.*`` — ``rk.submit`` (validation + enqueue), ``rk.form`` (batch
formation), ``rk.flush`` (one dispatch, with the server's ``rk.flush.*``
sub-spans nested inside), ``rk.resolve`` (the completion loop resolving
one batch) — each carrying the batch's first admission ``seq`` (``seq0``)
and its size ``n``, so one batch's spans line up across threads in a
profile. With the profiler off an annotation costs its construction and
nothing else: no string is formatted and no device value is read. The
waits that cross threads are counters instead (``RuntimeStats.queue_wait_s``
and ``linger_s``), readable without a profiler.

``drain()`` blocks until every admitted ticket has resolved; ``close()``
drains (optional), stops the threads, and fails whatever is left —
afterwards ``submit`` raises. The runtime is a context manager.
"""

from __future__ import annotations

import collections
import queue as _queue
import threading
import time
from typing import NamedTuple

from jax.profiler import TraceAnnotation

from repro.engine import artifact as _artifact
from repro.engine import serving as _serving

_UNSET = object()
_SHUTDOWN = object()


class TicketExpired(TimeoutError):
    """The ticket's deadline passed before its batch was dispatched."""


class ServeTicket:
    """One admitted query's future.

    ``result(timeout=)`` blocks until the runtime resolves the ticket and
    returns the server's answer (``ServeResult``/``ReverseResult``) or
    raises what dispatch raised (``TicketExpired`` after a missed
    deadline). ``done()`` polls. Tickets resolve exactly once; ``seq`` is
    the admission sequence number (tickets dispatch in ``seq`` order per
    signature run, and results never cross tickets — pinned by
    tests/test_runtime.py).

    Host stamps (``time.perf_counter()``): ``submitted_at`` at admission,
    ``formed_at`` when batch formation pops the ticket for dispatch (None
    if it expired or never left the queue), ``done_at`` at resolution.
    ``formed_at - submitted_at`` is the ticket's queue wait, linger
    included; ``done_at - formed_at`` its dispatch and completion.
    """

    __slots__ = ("query", "k", "n_cand", "scan", "seq", "deadline",
                 "submitted_at", "formed_at", "done_at", "_event", "_value",
                 "_error")

    def __init__(self, query, k: int, n_cand, scan, seq: int,
                 deadline: float | None):
        self.query = query
        self.k = k
        self.n_cand = n_cand
        self.scan = scan
        self.seq = seq
        self.deadline = deadline          # absolute monotonic time or None
        self.submitted_at = time.perf_counter()
        self.formed_at: float | None = None
        self.done_at: float | None = None
        self._event = threading.Event()
        self._value = None
        self._error: BaseException | None = None

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: float | None = None):
        """The answer, blocking up to ``timeout`` seconds for it."""
        if not self._event.wait(timeout):
            raise TimeoutError(f"ticket {self.seq} not resolved within "
                               f"{timeout}s")
        if self._error is not None:
            raise self._error
        return self._value

    def exception(self, timeout: float | None = None):
        """The dispatch error (None on success), blocking like result()."""
        if not self._event.wait(timeout):
            raise TimeoutError(f"ticket {self.seq} not resolved within "
                               f"{timeout}s")
        return self._error

    @property
    def latency(self) -> float | None:
        """Submit-to-resolve wall seconds; None while unresolved."""
        return None if self.done_at is None else \
            self.done_at - self.submitted_at

    def _resolve(self, value=None, error: BaseException | None = None):
        self._value = value
        self._error = error
        self.done_at = time.perf_counter()
        self._event.set()

    def __repr__(self) -> str:
        state = ("done" if self._error is None else
                 type(self._error).__name__) if self.done() else "pending"
        return f"ServeTicket(seq={self.seq}, k={self.k}, {state})"


class RuntimeStats(NamedTuple):
    """Counters snapshot (``ServingRuntime.stats``), monotone per runtime:
    every submitted ticket ends as exactly one of completed / expired /
    failed.

    The last three make warmup/bucketing regressions observable rather
    than inferred (DESIGN.md SS14): ``bucket_hits`` counts successful
    dispatches padded to a sub-maximal ladder rung (0 without
    ``serve_buckets``), ``bucket_pad_rows`` totals the dead padding rows
    those dispatches added (padding waste is measurable, not guessed),
    and ``traces_after_warmup`` is how many XLA traces the server's
    dispatch has cost since the warmup baseline (construction, or the
    last ``warmup()``) — a warmed runtime must hold it at 0, which CI
    asserts via benchmarks/bench_load.py.

    ``truncated`` counts tickets whose answer a scan budget
    (``EngineConfig.scan_budget``) resolved conservatively — the
    per-ticket ``ReverseResult.truncated`` flag aggregated per runtime,
    so budget pressure is attributable per tenant (DESIGN.md SS15),
    never silent.

    The last two are host seconds, summed and monotone, for the waits
    that start on one thread and end on another (a profiler span cannot
    cover those): ``queue_wait_s`` is, over every ticket popped for
    dispatch, ``formed_at - submitted_at`` (``ServeTicket``); ``linger_s``
    is, over every batch, how long a partial head batch was held for a
    fuller one, from the first moment a worker could have dispatched it
    to its formation — with a ``WorkerPool`` that includes the pool's
    wake-up, which can outlast ``batch_linger``. Over a window,
    ``Δqueue_wait_s / Δcompleted`` is the mean queue wait per ticket."""

    submitted: int
    completed: int
    expired: int      # deadline missed before dispatch (TicketExpired)
    failed: int       # dispatch raised, or runtime closed undrained
    batches: int      # successful micro-batch dispatches
    swaps: int        # artifact versions made live
    compactions: int  # background compact->reconcile->swap cycles
    bucket_hits: int      # dispatches padded to a sub-max ladder rung
    bucket_pad_rows: int  # dead rows added by bucket padding
    traces_after_warmup: int  # server traces since the warmup baseline
    truncated: int    # tickets answered under an exhausted scan budget
    queue_wait_s: float   # sum of formed_at - submitted_at, popped tickets
    linger_s: float       # sum of partial-batch hold time, per batch


class WorkerPool:
    """Shared dispatch workers for many ``ServingRuntime``s (the gateway
    tier, DESIGN.md SS15).

    A runtime constructed with ``pool=`` starts no worker threads of its
    own; instead the pool's threads round-robin over every registered
    runtime, forming and dispatching micro-batches through each one's own
    ``_try_next_batch`` / ``_dispatch_batch`` — the exact code path a
    dedicated worker would take, so pooled answers are bitwise identical
    to dedicated-runtime answers.

    Non-stall contract: a pool thread takes a runtime's dispatch lock
    with ``acquire(blocking=False)`` — if one tenant's lock is held (a
    hot-swap, a compaction landing, another pool thread mid-flush), the
    thread moves on to the next tenant instead of queueing behind it.
    One tenant's maintenance can therefore never stall another tenant's
    flushes (pinned by tests/test_gateway.py).
    """

    def __init__(self, workers: int = 1, *, poll_interval: float = 0.01):
        if workers < 1:
            raise ValueError(f"workers must be >= 1, got {workers}")
        self._cond = threading.Condition()
        self._members: list["ServingRuntime"] = []
        self._rr = 0
        self._stop = threading.Event()
        self._threads = [
            threading.Thread(target=self._run, name=f"pool-worker-{i}",
                             daemon=True)
            for i in range(workers)]
        self._poll = poll_interval
        for t in self._threads:
            t.start()

    def register(self, runtime: "ServingRuntime") -> None:
        with self._cond:
            if self._stop.is_set():
                raise RuntimeError("worker pool is closed")
            if runtime not in self._members:
                self._members.append(runtime)
            self._cond.notify_all()

    def unregister(self, runtime: "ServingRuntime") -> None:
        with self._cond:
            if runtime in self._members:
                self._members.remove(runtime)

    def notify(self) -> None:
        """Wake the pool: a member admitted tickets."""
        with self._cond:
            self._cond.notify_all()

    def _run(self) -> None:
        while not self._stop.is_set():
            with self._cond:
                members = list(self._members)
                start = self._rr
                self._rr = (self._rr + 1) % max(1, len(members))
            dispatched = False
            for i in range(len(members)):
                rt = members[(start + i) % len(members)]
                # non-blocking: a busy/swapping tenant is skipped, not
                # queued behind — the cross-tenant non-stall guarantee
                if not rt._dispatch_lock.acquire(blocking=False):
                    continue
                try:
                    batch = rt._try_next_batch()
                    if batch is None:
                        continue
                    dispatched = True
                    try:
                        results, pad_to = rt._dispatch_batch(batch)
                    except BaseException as e:  # noqa: BLE001 — to futures
                        rt._completion.put((batch, None, e, None))
                    else:
                        rt._completion.put((batch, results, None, pad_to))
                finally:
                    rt._dispatch_lock.release()
            if not dispatched:
                with self._cond:
                    self._cond.wait(self._poll)

    def close(self) -> None:
        """Stop the pool threads. Registered runtimes must be closed (or
        re-homed) first — a pooled runtime with live tickets and no pool
        would never dispatch them."""
        self._stop.set()
        with self._cond:
            self._cond.notify_all()
        for t in self._threads:
            t.join(timeout=30)

    def __enter__(self) -> "WorkerPool":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class ServingRuntime:
    """The threaded serving loop over a ``RetrievalServer`` or
    ``ReverseServer`` (module docstring; DESIGN.md SS12).

    Parameters:
      server        the wrapped server; its ``serve_batch_size`` is the
                    micro-batch size, its ``_flush_batch`` the dispatch.
      k             default k for ``submit`` (submit's ``k=`` overrides;
                    one of the two must be given).
      workers       dispatch worker threads. Dispatch itself is
                    serialized by the dispatch lock (one executable, one
                    device stream); extra workers only overlap batch
                    formation with dispatch, so the default of 1 is right
                    unless profiling says otherwise.
      deadline      default per-ticket budget in wall seconds (None: no
                    deadline). A ticket that waits longer is failed with
                    ``TicketExpired`` instead of dispatched.
      batch_linger  how long (seconds) a worker waits for a partial batch
                    to fill before dispatching it anyway — the classic
                    throughput/latency knob. With a bucket ladder
                    (``EngineConfig.serve_buckets``) a run whose length
                    already sits exactly on a rung skips the linger: it
                    can dispatch immediately with zero padding, so
                    waiting buys nothing.
      warmup        ahead-of-time compile every serving dispatch cell
                    before the worker threads start (DESIGN.md SS14):
                    calls ``server.warmup(warmup_ks)`` and then baselines
                    ``traces_after_warmup`` at 0 — the first request at
                    any ladder rung runs an already-built executable.
      warmup_ks     the ks warmup compiles for (default: the runtime's
                    ``k=``; warmup with neither raises).
      compaction    start the maintenance thread (requires an
                    artifact-backed server).
      compact_fill  delta-buffer fill fraction that triggers a background
                    compaction (``request_compaction()`` forces one).
      compact_policy ``ShardingPolicy`` for the off-thread rebuild
                    (default: the server's / engine's own policy).
      artifact_dir  persist each compacted version here (``save(step=n)``
                    with monotonically increasing steps).
      keep          GC/retention: prune the ``artifact_dir`` history to
                    the newest ``keep`` versions after each save (the
                    just-saved version is always protected).
      poll_interval idle-thread wakeup period in seconds (responsiveness
                    of compaction-trigger checks and close()).
      pool          a shared ``WorkerPool`` to dispatch through instead
                    of starting dedicated worker threads (``workers`` is
                    then ignored). The pool's threads run the same batch
                    formation and dispatch path, so answers are bitwise
                    identical; close() unregisters from the pool but
                    leaves it running for its other members.
    """

    def __init__(self, server, *, k: int | None = None, workers: int = 1,
                 deadline: float | None = None, batch_linger: float = 0.002,
                 warmup: bool = False, warmup_ks=None,
                 compaction: bool = False, compact_fill: float = 0.5,
                 compact_policy=None, artifact_dir: str | None = None,
                 keep: int | None = None, poll_interval: float = 0.05,
                 pool: "WorkerPool | None" = None):
        if workers < 1:
            raise ValueError(f"workers must be >= 1, got {workers}")
        if not 0.0 < compact_fill <= 1.0:
            raise ValueError(f"compact_fill must be in (0, 1], got "
                             f"{compact_fill}")
        self.server = server
        self._engine = getattr(server, "engine", None)
        self._is_reverse = self._engine is not None
        self.artifact = (self._engine.artifact if self._is_reverse
                         else server.artifact)
        if compaction and self.artifact is None:
            raise ValueError(
                "compaction=True needs an artifact-backed server: build "
                "the server from_artifact / engine.from_artifact so the "
                "runtime has a version to watch and swap")
        if keep is not None and artifact_dir is None:
            raise ValueError("keep= (artifact GC) needs artifact_dir=")
        self._default_k = k
        self._default_deadline = deadline
        self._linger = batch_linger
        self._poll = poll_interval
        self._compact_fill = compact_fill
        self._compact_policy = compact_policy if compact_policy is not None \
            else (self._engine.policy if self._is_reverse
                  else server.policy)
        self._artifact_dir = artifact_dir
        self._keep = keep
        self._save_step = 0

        self._admit = threading.Condition()
        self._ticket_deque: collections.deque[ServeTicket] = \
            collections.deque()
        self._dispatch_lock = threading.Lock()
        self._mutate_lock = threading.Lock()
        self._completion: _queue.SimpleQueue = _queue.SimpleQueue()
        self._stop = threading.Event()
        self._closed = False
        self._seq = 0
        self._unfinished = 0
        self._submitted = 0
        self._completed = 0
        self._expired = 0
        self._failed = 0
        self._batches = 0
        self._swaps = 0
        self._compactions = 0
        self._bucket_hits = 0
        self._bucket_pad_rows = 0
        self._truncated = 0
        self._queue_wait_s = 0.0
        self._linger_s = 0.0
        self._pool = pool
        # when a worker first held the current partial head batch for a
        # fuller one (perf_counter); None while nothing lingers
        self._linger_since: float | None = None
        self.last_compaction_seconds: float | None = None

        # AOT warmup runs before any worker exists, so no ticket can race
        # a live trace; the baseline makes traces_after_warmup read 0
        # until something actually traces post-warmup. Without warmup the
        # baseline is construction time: the counter then reads "traces
        # this runtime caused", the cold-start number bench_load reports.
        if warmup:
            ks = warmup_ks if warmup_ks is not None else \
                ([] if k is None else [k])
            if not ks:
                raise ValueError("warmup=True needs warmup_ks= (or a "
                                 "default k= to warm for)")
            server.warmup(tuple(ks))
        self._trace_base = server.compile_count

        # Pooled mode (DESIGN.md SS15): the runtime starts no dispatch
        # workers of its own — the shared WorkerPool's threads form and
        # dispatch its batches. Completion and maintenance threads stay
        # per-runtime (cheap, and their state is per-tenant anyway).
        self._threads = [] if pool is not None else [
            threading.Thread(target=self._worker_loop,
                             name=f"serve-worker-{i}", daemon=True)
            for i in range(workers)]
        self._completer = threading.Thread(target=self._completion_loop,
                                           name="serve-completer",
                                           daemon=True)
        self._compact_wake = threading.Event()
        self._compact_forced = threading.Event()
        self._compactor = None
        if compaction:
            self._compactor = threading.Thread(
                target=self._maintenance_loop, name="serve-compactor",
                daemon=True)
        self._completer.start()
        for t in self._threads:
            t.start()
        if self._compactor is not None:
            self._compactor.start()
        if pool is not None:
            pool.register(self)

    # -- admission ---------------------------------------------------------

    def submit(self, q, *, k: int | None = None, n_cand: int | None = None,
               scan: str | None = None, deadline=_UNSET):
        """Admit a query (d,) -> its ``ServeTicket``; a block (nq, d) ->
        one ticket per row, resolved independently.

        Validation (dtype/shape/dimensionality) happens here, before the
        queue — a malformed query raises ``ValueError`` and nothing is
        admitted. ``k``/``deadline`` default to the runtime's;
        ``n_cand``/``scan`` are forward-server knobs (tickets dispatch in
        same-signature micro-batches, so mixing knobs costs batch
        fragmentation, not correctness). Raises ``RuntimeError`` once the
        runtime is closed.
        """
        with TraceAnnotation("rk.submit") as span:
            q = _serving.validate_query_rows(q, self.server._dim,
                                             "runtime.submit")
            k = self._default_k if k is None else k
            if k is None:
                raise ValueError("no k for this ticket: pass submit(..., "
                                 "k=) or construct ServingRuntime(..., k=)")
            if self._is_reverse and (n_cand is not None or scan is not None):
                raise ValueError("n_cand/scan are forward-serving knobs; the "
                                 "reverse pipeline has no per-ticket "
                                 "override")
            budget = (self._default_deadline if deadline is _UNSET
                      else deadline)
            expiry = None if budget is None else time.monotonic() + budget
            rows = [q] if q.ndim == 1 else [q[i] for i in range(q.shape[0])]
            with self._admit:
                if self._closed:
                    raise RuntimeError("runtime is closed: no new tickets "
                                       "(create a new ServingRuntime)")
                tickets = []
                for row in rows:
                    t = ServeTicket(row, k, n_cand, scan, self._seq, expiry)
                    self._seq += 1
                    self._ticket_deque.append(t)
                    tickets.append(t)
                self._submitted += len(tickets)
                self._unfinished += len(tickets)
                self._admit.notify_all()
            if self._pool is not None:
                self._pool.notify()
            span.set_metadata(seq0=tickets[0].seq, n=len(tickets))
        return tickets[0] if q.ndim == 1 else tickets

    # -- worker / completion loops -----------------------------------------

    def _signature(self, t: ServeTicket) -> tuple:
        return (t.k, t.n_cand, t.scan)

    def _ladder(self) -> tuple:
        """The live config's bucket ladder (ascending dispatch sizes) —
        read per call, so a config swapped between flushes brings its own
        ladder along, like ``batch_size``."""
        cfg = (self._engine.config if self._is_reverse
               else self.server.config)
        return cfg.bucket_ladder()

    def _form_batch(self) -> list[ServeTicket]:
        """Pop the next signature run off the deque — the longest run of
        queue-head tickets sharing one signature, up to
        ``serve_batch_size``. Expired tickets are failed here,
        pre-dispatch. Caller holds ``_admit``. [] = nothing poppable."""
        with TraceAnnotation("rk.form") as span:
            size = self.server.batch_size
            batch: list[ServeTicket] = []
            sig = None
            now = time.monotonic()
            while self._ticket_deque and len(batch) < size:
                head = self._ticket_deque[0]
                if head.deadline is not None and now >= head.deadline:
                    self._ticket_deque.popleft()
                    self._completion.put(([head], None, TicketExpired(
                        f"ticket {head.seq} missed its deadline "
                        f"before dispatch"), None))
                    continue
                if sig is None:
                    sig = self._signature(head)
                elif self._signature(head) != sig:
                    break
                batch.append(self._ticket_deque.popleft())
            # an empty batch means every head ticket expired: the queue
            # is empty, so nothing lingers either way
            formed = time.perf_counter()
            if batch and self._linger_since is not None:
                self._linger_s += formed - self._linger_since
            self._linger_since = None
            for t in batch:
                t.formed_at = formed
                self._queue_wait_s += formed - t.submitted_at
            if batch:
                span.set_metadata(seq0=batch[0].seq, n=len(batch))
        return batch

    def _next_batch(self) -> list[ServeTicket] | None:
        """Blocking batch formation for this runtime's own workers.
        None = stopping and queue empty."""
        with self._admit:
            lingered = False
            while True:
                if not self._ticket_deque:
                    if self._stop.is_set():
                        return None
                    self._admit.wait(self._poll)
                    lingered = False
                    continue
                if (self._linger > 0 and not lingered
                        and len(self._ticket_deque) < self.server.batch_size
                        and len(self._ticket_deque) not in self._ladder()
                        and not self._stop.is_set()):
                    # one bounded wait for a fuller batch, then dispatch
                    # whatever is there — never a second linger. A queue
                    # already sitting exactly on a ladder rung skips the
                    # wait: it dispatches with zero padding, so lingering
                    # buys throughput nothing and costs latency.
                    lingered = True
                    if self._linger_since is None:
                        self._linger_since = time.perf_counter()
                    self._admit.wait(self._linger)
                    continue
                batch = self._form_batch()
                if batch:
                    return batch
                lingered = False  # head tickets all expired; go around

    def _try_next_batch(self) -> list[ServeTicket] | None:
        """Non-blocking batch formation for pooled workers (the caller —
        a ``WorkerPool`` thread — already holds this runtime's dispatch
        lock). Returns None when the queue is empty or still lingering
        for a fuller batch; the linger is a deadline (``_linger_since`` +
        ``batch_linger``)
        rather than a sleep, so a pool thread never blocks on one tenant
        while others have work."""
        with self._admit:
            n = len(self._ticket_deque)
            if n == 0:
                self._linger_since = None
                return None
            if (self._linger > 0
                    and n < self.server.batch_size
                    and n not in self._ladder()
                    and not self._stop.is_set()):
                now = time.perf_counter()
                if self._linger_since is None:
                    self._linger_since = now
                    return None
                if now < self._linger_since + self._linger:
                    return None
            return self._form_batch() or None

    def _dispatch_batch(self, batch: list[ServeTicket]) -> tuple[list, int]:
        """Dispatch one signature run through the server's own flush path,
        padded to the nearest ladder rung (``bucket_for``) rather than the
        full ``serve_batch_size`` — bitwise the same answers (padding is
        dead), one executable per rung, all precompiled by warmup.
        Returns (results, pad_to)."""
        first = batch[0]
        group = [t.query for t in batch]
        pad_to = self.server.bucket_for(len(group))
        with TraceAnnotation("rk.flush", seq0=first.seq, n=len(group),
                             pad_to=pad_to):
            if self._is_reverse:
                return (self.server._flush_batch(group, first.k,
                                                 pad_to=pad_to), pad_to)
            return (self.server._flush_batch(group, first.k,
                                             n_cand=first.n_cand,
                                             scan=first.scan,
                                             pad_to=pad_to), pad_to)

    def _worker_loop(self) -> None:
        while True:
            batch = self._next_batch()
            if batch is None:
                return
            try:
                with self._dispatch_lock:
                    results, pad_to = self._dispatch_batch(batch)
            except BaseException as e:  # noqa: BLE001 — routed to futures
                self._completion.put((batch, None, e, None))
                continue
            self._completion.put((batch, results, None, pad_to))

    def _completion_loop(self) -> None:
        while True:
            item = self._completion.get()
            if item is _SHUTDOWN:
                return
            batch, results, error, pad_to = item
            with TraceAnnotation("rk.resolve", seq0=batch[0].seq,
                                 n=len(batch)):
                if error is not None:
                    for t in batch:
                        t._resolve(error=error)
                else:
                    for t, r in zip(batch, results):
                        t._resolve(value=r)
                with self._admit:
                    self._unfinished -= len(batch)
                    if error is None:
                        self._completed += len(batch)
                        self._batches += 1
                        self._truncated += sum(
                            1 for r in results
                            if getattr(r, "truncated", False))
                        if pad_to is not None:
                            if pad_to < self.server.batch_size:
                                self._bucket_hits += 1
                            self._bucket_pad_rows += pad_to - len(batch)
                    elif isinstance(error, TicketExpired):
                        self._expired += len(batch)
                    else:
                        self._failed += len(batch)
                    self._admit.notify_all()

    # -- artifact lifecycle ------------------------------------------------

    def _require_artifact(self) -> "_artifact.IndexArtifact":
        if self.artifact is None:
            raise RuntimeError("runtime has no artifact: build the server "
                               "from an IndexArtifact to stream mutations")
        return self.artifact

    def _swap_live(self, artifact) -> None:
        # caller holds _mutate_lock; the dispatch lock is what makes the
        # swap land *between* flushes
        with self._dispatch_lock:
            self.server.swap(artifact)
            self.artifact = artifact
            with self._admit:
                self._swaps += 1

    def swap(self, artifact) -> None:
        """Make an externally built artifact version live, between
        flushes; pending tickets survive and are answered against it."""
        with self._mutate_lock:
            self._swap_live(artifact)

    def insert_items(self, rows) -> "_artifact.IndexArtifact":
        """Stage rows into the live version's delta buffer and swap the
        new version in (between flushes). Returns the new version."""
        with self._mutate_lock:
            art = self._require_artifact().insert_items(rows)
            self._swap_live(art)
        self._compact_wake.set()   # let the compactor re-check the fill
        return art

    def delete_items(self, ids) -> "_artifact.IndexArtifact":
        """Retire rows on the live version and swap the new version in
        (between flushes). Returns the new version."""
        with self._mutate_lock:
            art = self._require_artifact().delete_items(ids)
            self._swap_live(art)
        self._compact_wake.set()
        return art

    def request_compaction(self) -> None:
        """Ask the maintenance thread for a compaction now, regardless of
        fill (no-op without ``compaction=True`` or pending churn)."""
        self._compact_forced.set()
        self._compact_wake.set()

    def _maintenance_loop(self) -> None:
        while not self._stop.is_set():
            self._compact_wake.wait(self._poll)
            self._compact_wake.clear()
            if self._stop.is_set():
                return
            snapshot = self.artifact
            if snapshot is None or not snapshot.has_pending:
                self._compact_forced.clear()
                continue
            fill = snapshot.delta_used / snapshot.delta_capacity
            if not (self._compact_forced.is_set()
                    or fill >= self._compact_fill):
                continue
            self._compact_forced.clear()
            t0 = time.perf_counter()
            # the slow part runs unlocked: traffic keeps flushing, and
            # mutations keep staging onto descendants of `snapshot`
            compacted = snapshot.compact(policy=self._compact_policy)
            with self._mutate_lock:
                merged = _artifact.reconcile_compaction(
                    snapshot, self.artifact, compacted)
                self._swap_live(merged)
                with self._admit:
                    self._compactions += 1
            self.last_compaction_seconds = time.perf_counter() - t0
            if self._artifact_dir is not None:
                step = self._save_step
                self._save_step += 1
                merged.save(self._artifact_dir, step=step, keep=self._keep)

    # -- lifecycle ---------------------------------------------------------

    def warmup(self, ks=None, **server_kwargs) -> int:
        """Re-run the server's AOT warmup under the dispatch lock (never
        mid-flush) and re-baseline ``traces_after_warmup`` at 0 — e.g.
        after swapping in a config with a different ladder, or to warm
        extra ks mid-flight. ``ks`` defaults to the runtime's ``k=``;
        extra keyword args go to ``server.warmup`` (n_cands/scans/buckets
        on the forward server, buckets on the reverse). Returns the
        number of cells compiled."""
        ks = ks if ks is not None else \
            ([] if self._default_k is None else [self._default_k])
        if not ks:
            raise ValueError("warmup needs ks= (or a default k= on the "
                             "runtime)")
        with self._dispatch_lock:
            cells = self.server.warmup(tuple(ks), **server_kwargs)
            self._trace_base = self.server.compile_count
        return cells

    def rebaseline_traces(self) -> None:
        """Zero ``traces_after_warmup`` at the server's current compile
        count. The gateway's gateway-wide warmup uses this: tenants that
        share a compiled dispatch are warmed once through a single
        representative, then every sharer is re-baselined — so
        ``traces_after_warmup == 0`` holds across all tenants without
        per-tenant re-tracing (DESIGN.md SS15)."""
        with self._dispatch_lock:
            self._trace_base = self.server.compile_count

    @property
    def stats(self) -> RuntimeStats:
        """A consistent snapshot of the runtime counters (see
        ``RuntimeStats`` for the field contract; ``traces_after_warmup``
        is derived live from the server's ``compile_count`` against the
        warmup baseline)."""
        traces = self.server.compile_count - self._trace_base
        with self._admit:
            return RuntimeStats(self._submitted, self._completed,
                                self._expired, self._failed, self._batches,
                                self._swaps, self._compactions,
                                self._bucket_hits, self._bucket_pad_rows,
                                traces, self._truncated, self._queue_wait_s,
                                self._linger_s)

    @property
    def pending(self) -> int:
        """Tickets admitted but not yet resolved (queued + in flight)."""
        with self._admit:
            return self._unfinished

    def drain(self, timeout: float | None = None) -> bool:
        """Block until every admitted ticket has resolved (completed,
        expired, or failed). True on fully drained; False on timeout."""
        end = None if timeout is None else time.monotonic() + timeout
        with self._admit:
            while self._unfinished > 0:
                remaining = self._poll if end is None \
                    else end - time.monotonic()
                if remaining <= 0:
                    return False
                self._admit.wait(min(remaining, self._poll))
            return True

    def close(self, *, drain: bool = True,
              timeout: float | None = None) -> None:
        """Stop the runtime: refuse new tickets, optionally ``drain()``,
        stop and join every thread, and fail whatever is left undispatched
        (so no future ever hangs). Idempotent."""
        with self._admit:
            already = self._closed
            self._closed = True
        if not already and drain:
            self.drain(timeout)
        self._stop.set()
        self._compact_wake.set()
        with self._admit:
            self._admit.notify_all()
        for t in self._threads:
            t.join(timeout=30)
        if self._compactor is not None:
            self._compactor.join(timeout=60)
        if self._pool is not None:
            # Unregister, then take the dispatch lock once: pool threads
            # form batches only while holding it, so after this no pooled
            # worker can race the leftover sweep below.
            self._pool.unregister(self)
            with self._dispatch_lock:
                pass
        with self._admit:
            leftover = list(self._ticket_deque)
            self._ticket_deque.clear()
        if leftover:
            self._completion.put((leftover, None, RuntimeError(
                "runtime closed before these tickets were dispatched"),
                None))
        if self._completer.is_alive():
            self._completion.put(_SHUTDOWN)
            self._completer.join(timeout=30)

    def __enter__(self) -> "ServingRuntime":
        return self

    def __exit__(self, *exc) -> None:
        self.close(drain=exc == (None, None, None))
