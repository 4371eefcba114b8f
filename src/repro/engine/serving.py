"""Engine-level online serving: micro-batched (R)kMIPS behind one front door.

DESIGN.md SS8 is the contract. This module is what ``launch/serve.py`` and
``examples/serve_retrieval.py`` sit on: single queries arrive one at a time,
are accumulated and padded into fixed-size micro-batches (static shapes —
exactly one compile per distinct batch size), and dispatched through the
mesh-aware sharded scan ``engine/sharding.py::kmips_flat_arrays``. Built
serving state — norm-ordered item rows, SRP codes, the query-side
projection, and their padded, mesh-placed layout — is cached in an LRU
keyed by the frozen ``EngineConfig``, so swapping presets on a live server
rebuilds nothing it has already built.

Forward (kMIPS) serving, three layers, separable on purpose:

  * ``build_serving_state`` — offline: SA-ALSH index build, row padding to
    the mesh's shard multiple (``pad_item_rows``), device placement.
  * ``ServingCache`` — the LRU of built states, keyed by (corpus
    fingerprint, index recipe); ``get`` is the only entry, ``builds``
    counts misses (asserted in tests).
  * ``RetrievalServer`` — online: ``submit`` enqueues a query and returns
    its ticket, ``flush`` answers every pending ticket in order; ``kmips``
    is the submit+flush convenience for a lone query.

Hot swap (DESIGN.md SS10): both servers accept a new ``IndexArtifact``
version between flushes via ``swap(artifact)`` — pending tickets survive
(they are answered against the new version by the next flush), and when the
swapped-in shapes match the live ones the compiled dispatch is reused
(``compile_count`` += 0). The cache key's fingerprint prefix is what makes
this safe: built states of *different* corpus versions can coexist in one
LRU, so swapping back to a cached version is a hit, and a stale state can
never be served as a "hit" for new content. For artifact-backed forward
servers the prefix is the **base** fingerprint and staged deltas are served
as an incremental overlay (deletion mask + exactly-scanned staged rows), so
streaming churn never rebuilds serving state — the cache key only moves at
``compact()``, when the base actually changes.

The synchronous path here is also the substrate of the threaded serving
runtime (engine/runtime.py, DESIGN.md SS12): runtime workers dispatch
through the same ``_flush_batch`` the synchronous ``flush`` uses, which is
what makes runtime answers bitwise identical to library-mode serving.

Reverse (RkMIPS) serving rides the batched plan/execute pipeline
(DESIGN.md SS9): ``ReverseServer`` accumulates promoted-item queries and
answers them through ``RkMIPSEngine.query_batch`` in fixed-size
micro-batches. Because the flat cross-query work queue made batch size a
pure throughput knob — one trace per batch shape, fast queries' lanes
never idle behind slow ones — online reverse dispatch needs no path of
its own: the server is a ticket queue over the engine.

Invariant (tests/test_serving.py): per-query results are bitwise identical
whether a query is served alone, inside any micro-batch, or in a one-shot
batch — flat-scan rows and RkMIPS work-queue lanes are both independent
and padding is dead, so batching is a latency/throughput knob, never an
accuracy knob.

Tracing: ``_flush_batch`` of both servers opens the host spans
``rk.flush.pad`` (stack and pad: for the forward server, one launch of
the compiled ``_pad_batch``), ``rk.flush.launch`` (the compiled
dispatch, or the engine call), ``rk.flush.merge`` (the delta fold-in, only
with staged rows) and ``rk.flush.split`` (per-ticket results: for the
forward server, the batch's one device-to-host copy and its row views) as
``jax.profiler.TraceAnnotation``s, nested in the runtime's ``rk.flush``.
On the device, the forward stages run under ``jax.named_scope``s
(``kmips.hash``, ``.scan``, ``.select``, ``.rerank``, ``.merge``), and
``op_scopes`` maps the compiled instructions to them.
"""

from __future__ import annotations

import functools
import re
import threading
from collections import OrderedDict
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import sa_alsh as _alsh
from repro.dist.policy import NO_SHARDING, ShardingPolicy
from repro.engine import sharding as _sharding
from repro.engine.artifact import IndexArtifact, corpus_fingerprint
from repro.engine.config import EngineConfig, get_config
from repro.engine.engine import _TraceCount
from repro.kernels import ops as kops


_SCOPE_PREFIX = "kmips."
_HLO_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
_HLO_INSTR = re.compile(r"^\s*(?:ROOT )?%([^\s=]+) = (.*)$", re.M)
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# (HLO module name, instruction name) -> every scope ("" = none) it had in
# an executable RetrievalServer.warmup compiled in this process
_SCOPES_SEEN: dict[tuple[str, str], set[str]] = {}
_SCOPES_LOCK = threading.Lock()


def instruction_scopes(hlo_text: str) -> tuple[str, dict[str, str]]:
    """One compiled HLO module's text (``compiled.as_text()``) -> (its
    module name, {instruction name: the innermost ``kmips.*`` named scope
    of the instruction's ``op_name`` metadata, ``""`` when it has none})."""
    module = _HLO_MODULE.search(hlo_text)
    scopes = {}
    for name, rest in _HLO_INSTR.findall(hlo_text):
        op = _OP_NAME.search(rest)
        path = op.group(1).split("/") if op else ()
        inner = [part for part in path if part.startswith(_SCOPE_PREFIX)]
        scopes[name] = inner[-1] if inner else ""
    return (module.group(1) if module else ""), scopes


@functools.partial(jax.jit, static_argnames=("pad_to",))
def _pad_batch(rows: tuple, *, pad_to: int) -> jnp.ndarray:
    """A forward micro-batch's (d,) query rows -> the (pad_to, d) batch
    the dispatch takes: stacked, then zero rows after the live ones.

    One launch of one XLA program per (group length, dtype, pad_to),
    where eager ops would launch and allocate per row and upload the
    zero fill; ``jnp.stack``'s dtype promotion and placement, so answers
    are bitwise those of the eager stack and pad. Module-level, so every
    ``RetrievalServer`` shares its executables; ``warmup`` compiles one
    per group length at each rung (DESIGN.md SS8, SS14)."""
    qs = jnp.stack(rows)
    return jnp.pad(qs, ((0, pad_to - qs.shape[0]), (0, 0)))


def _record_scopes(compiled) -> None:
    module, scopes = instruction_scopes(compiled.as_text())
    if not any(scopes.values()):
        # JAX's persistent cache keys leave metadata out, so an entry
        # compiled before these scopes existed comes back without them
        return
    with _SCOPES_LOCK:
        for name, scope in scopes.items():
            _SCOPES_SEEN.setdefault((module, name), set()).add(scope)


def op_scopes() -> dict:
    """Which forward stage each device instruction runs, over every
    executable ``RetrievalServer.warmup`` has compiled in this process:
    {(HLO module name, instruction name): ``kmips.*`` scope}.

    A device profile names each op by its instruction (``%sort.10 =
    ...``) inside a module launch (``jit__scan(...)``) and carries no
    scope, so this map is what attributes device time to the stages
    ``kmips.hash``, ``.scan``, ``.select``, ``.rerank`` and ``.merge``.
    An instruction that executables of one module name (the ladder's
    rungs) place in two scopes maps to None — ambiguous, to be left
    unattributed, never guessed; instructions outside every scope are
    left out."""
    with _SCOPES_LOCK:
        return {key: next(iter(seen)) if len(seen) == 1 else None
                for key, seen in _SCOPES_SEEN.items() if seen != {""}}


class ServingState(NamedTuple):
    """Everything one config's online scan needs, built offline.

    Item arrays are in descending-norm order (SA-ALSH layout), padded to a
    multiple of the mesh's device count with dead rows, and — under a mesh
    policy — already placed: rows sharded over every axis, the projection
    replicated. ``item_ids`` maps back to the caller's original rows.
    """

    items: jnp.ndarray       # (N_pad, d) f32
    item_ids: jnp.ndarray    # (N_pad,) int32, -1 on padding
    item_mask: jnp.ndarray   # (N_pad,) bool
    codes: jnp.ndarray       # (N_pad, W) uint32
    proj_q: jnp.ndarray      # (d, n_bits) query-side SRP projection
    config: EngineConfig
    n_items: int             # real (unpadded) item count, k's upper bound


class ServeResult(NamedTuple):
    """One served query's answer (values descending; ids in the caller's
    corpus row space — for artifact-backed servers that is artifact id
    space: base rows keep their ids, staged row j is n_base + j).

    ``values`` and ``ids`` are host ``np.ndarray``s: read-only row views
    of the one device-to-host copy ``_flush_batch`` makes of its whole
    micro-batch, with the dispatch's dtypes."""

    values: np.ndarray
    ids: np.ndarray
    k: int


def state_from_index(index, config: EngineConfig | str = "sah", *,
                     policy: ShardingPolicy = NO_SHARDING) -> ServingState:
    """Serving state from an already-built SA-ALSH index — no rebuild.

    Pads the item rows to the mesh's shard multiple and places them
    (rows sharded over every axis, projection replicated); the engine uses
    this to seed a server's cache from its own kMIPS index.
    """
    if isinstance(config, str):
        config = get_config(config)
    arrays = (index.items, index.item_ids, index.item_mask, index.codes)
    n_items = int(index.item_mask.sum())
    proj_q = index.proj[:-1]
    if policy.mesh is not None:
        arrays = _sharding.pad_item_rows(*arrays,
                                         _sharding.n_shards(policy))
        axes = tuple(policy.mesh.axis_names)
        row = lambda x: jax.device_put(x, NamedSharding(
            policy.mesh, P(axes, *([None] * (x.ndim - 1)))))
        arrays = tuple(row(x) for x in arrays)
        proj_q = jax.device_put(proj_q, NamedSharding(policy.mesh, P()))
    return ServingState(*arrays, proj_q=proj_q, config=config,
                        n_items=n_items)


def build_serving_state(items: jnp.ndarray, key: jax.Array,
                        config: EngineConfig | str = "sah", *,
                        policy: ShardingPolicy = NO_SHARDING
                        ) -> ServingState:
    """Offline build: SA-ALSH index -> padded, mesh-placed serving arrays.

    The index build consumes ``key`` exactly as the engine's kMIPS index
    would, so a server and an ``RkMIPSEngine`` handed the same key and
    config scan identical codes.
    """
    if isinstance(config, str):
        config = get_config(config)
    idx = _alsh.build_index(items, key,
                            **config.kmips_build_kwargs(items.shape[0]))
    return state_from_index(idx, config, policy=policy)


def validate_query_rows(q, dim: int | None, what: str) -> jnp.ndarray:
    """Submit-time validation shared by every ticket surface.

    Rejects wrong-dtype / wrong-shape queries with a clear ``ValueError``
    at ``submit`` time — before they sit in the queue — instead of failing
    inside a later flush, which (by the retry contract) would leave the
    whole batch pending behind one malformed row. Returns the query as a
    jnp array (1-D single query or 2-D block).
    """
    q = jnp.asarray(q)
    if not jnp.issubdtype(q.dtype, jnp.floating):
        raise ValueError(f"{what}: queries must have a floating dtype, "
                         f"got {q.dtype}")
    if q.ndim not in (1, 2):
        raise ValueError(f"{what}: queries must be one row (d,) or a "
                         f"block (nq, d), got shape {q.shape}")
    if dim is not None and q.shape[-1] != dim:
        raise ValueError(f"{what}: query dimensionality {q.shape[-1]} != "
                         f"corpus dimensionality {dim}")
    return q


def _index_recipe(config: EngineConfig, n_items: int) -> tuple:
    """The build-kwargs tuple that determines the built serving arrays.

    Derived from ``EngineConfig.kmips_build_kwargs`` — the same recipe
    every builder consumes — so the cache key can never drift from the
    build. Serve-only knobs (batch size, cache capacity) and query-time
    knobs (k, n_cand, scan, ...) do not change the offline build, so
    configs differing only there share one cached state.
    """
    return tuple(sorted(config.kmips_build_kwargs(n_items).items()))


class ServingCache:
    """LRU of built ``ServingState``, keyed by (corpus fingerprint, index
    recipe).

    ``EngineConfig`` is frozen and hashable (engine/config.py), and the
    cache keys on exactly the fields that feed the offline build
    (``_index_recipe``): a hit is guaranteed to return arrays built with
    the requested knobs — the identical arrays, no rebuild (``builds``
    counts actual builds) — and configs that differ only in serve/query
    knobs share one entry instead of thrashing the LRU.

    The key's fingerprint prefix identifies the *corpus version*
    (``IndexArtifact.fingerprint`` for artifact-backed servers,
    ``corpus_fingerprint(items, key)`` otherwise). ``rebind`` points the
    cache at a new live version for a hot swap: old versions' entries stay
    resident under their own fingerprints (swapping back is a hit, subject
    to the LRU), and content changes can never alias onto a stale state.
    """

    def __init__(self, items: jnp.ndarray, key: jax.Array, *,
                 policy: ShardingPolicy = NO_SHARDING, capacity: int = 4,
                 fingerprint: str | None = None):
        if capacity < 1:
            raise ValueError(f"cache capacity must be >= 1, got {capacity}")
        self._items = items
        self._key = key
        self._policy = policy
        # lazy: a never-swapped server (one corpus version ever) should
        # not pay a full-corpus host hash at construction
        self._fp = fingerprint
        self.capacity = capacity
        self._states: OrderedDict[tuple, ServingState] = OrderedDict()
        self.builds = 0

    def __len__(self) -> int:
        return len(self._states)

    @property
    def fingerprint(self) -> str:
        """Fingerprint of the live corpus version (the current key prefix,
        computed on first use when not supplied)."""
        if self._fp is None:
            self._fp = corpus_fingerprint(self._items, self._key)
        return self._fp

    def rebind(self, items: jnp.ndarray, key: jax.Array, *,
               fingerprint: str | None = None) -> None:
        """Make a new corpus version live (hot swap). Cached states of
        previous versions remain retrievable under their fingerprints."""
        self._items = items
        self._key = key
        self._fp = (fingerprint if fingerprint is not None
                    else corpus_fingerprint(items, key))

    def _recipe(self, config: EngineConfig) -> tuple:
        return (self.fingerprint, _index_recipe(config, self._items.shape[0]))

    def __contains__(self, config: EngineConfig) -> bool:
        return self._recipe(config) in self._states

    def put(self, config: EngineConfig | str, state: ServingState) -> None:
        """Seed the cache with a pre-built state (no build counted) —
        e.g. the engine's own kMIPS index via ``state_from_index``."""
        if isinstance(config, str):
            config = get_config(config)
        recipe = self._recipe(config)
        self._states[recipe] = state
        self._states.move_to_end(recipe)
        while len(self._states) > self.capacity:
            self._states.popitem(last=False)

    def get(self, config: EngineConfig | str) -> ServingState:
        """The state for ``config``: cached on hit, built+inserted on miss
        (evicting the least-recently-used state past capacity)."""
        if isinstance(config, str):
            config = get_config(config)
        recipe = self._recipe(config)
        state = self._states.get(recipe)
        if state is not None:
            self._states.move_to_end(recipe)
            return state
        state = build_serving_state(self._items, self._key, config,
                                    policy=self._policy)
        self.builds += 1
        self._states[recipe] = state
        while len(self._states) > self.capacity:
            self._states.popitem(last=False)
        return state


class _TicketQueue:
    """Shared ticket bookkeeping for the online servers.

    FIFO: ``submit`` enqueues a query (d,) — or a block (nq, d), one
    ticket per row — and returns the ticket(s); a server's ``flush``
    answers every pending ticket in submission order and consumes the
    queue only on success (a failed flush leaves every ticket pending, so
    a retry answers them all). One implementation, so the ticket
    arithmetic and failure contract can never drift between the forward
    and reverse servers.

    ``submit`` validates dtype/shape up front (``validate_query_rows``):
    a malformed query raises immediately instead of poisoning a later
    flush — the queue only ever holds dispatchable rows.
    """

    def __init__(self, dim: int | None = None):
        self._pending: list[jnp.ndarray] = []
        self._next_ticket = 0
        self._dim = dim  # corpus dimensionality; None skips the dim check

    @property
    def pending(self) -> int:
        """Tickets submitted but not yet flushed."""
        return len(self._pending)

    def submit(self, q: jnp.ndarray) -> int | list[int]:
        """Enqueue a query (d,) -> its ticket; (nq, d) -> one per row.

        Tickets are served strictly in submission order by the next
        ``flush``; a ticket's position in flush's result list is
        ``ticket - first_pending_ticket``. Wrong dtype/shape raises a
        ``ValueError`` here, at submit time.
        """
        q = validate_query_rows(q, self._dim, "submit")
        if q.ndim == 1:
            self._pending.append(q)
            self._next_ticket += 1
            return self._next_ticket - 1
        tickets = list(range(self._next_ticket,
                             self._next_ticket + q.shape[0]))
        self._pending.extend(q[i] for i in range(q.shape[0]))
        self._next_ticket += q.shape[0]
        return tickets

    def _serve_one(self, q: jnp.ndarray, flush, what: str):
        """Submit one query (d,) and flush now, returning its answer.
        Pending tickets (if any) are answered by the same flush, in
        submission order."""
        if jnp.asarray(q).ndim != 1:
            raise ValueError(f"{what} serves one query (d,); use "
                             f"submit/flush for batches")
        ticket = self.submit(q)
        first = self._next_ticket - len(self._pending)
        return flush()[ticket - first]


class RetrievalServer(_TicketQueue):
    """Online kMIPS serving: accumulate single queries, answer in batches.

    ``submit`` enqueues a query (d,) — or a block (nq, d), one ticket per
    row — and returns the ticket(s); ``flush(k)`` answers every pending
    ticket, in submission order, by grouping them into micro-batches of
    ``config.serve_batch_size``, padding the last group with zero queries
    (their rows are computed and discarded — static shapes buy one compile
    per batch size), and dispatching each batch through the sharded flat
    scan. ``compile_count`` exposes how many traces the dispatch function
    has cost: it must stay at one per distinct (batch size, k, n_cand,
    scan) tuple, which tests/test_serving.py pins.

    The server owns a ``ServingCache`` over its corpus; per-flush state
    lookup is O(1) on a hit, so swapping ``config`` between flushes (e.g.
    an A/B of presets) costs one build each, once. ``swap(artifact)``
    makes a new corpus version live between flushes (DESIGN.md SS10).

    Artifact-backed servers serve the delta buffer *incrementally*: the
    cached ``ServingState`` is built from (and keyed by) the artifact's
    **base** corpus (``base_fingerprint``), so staged inserts/deletes
    never trigger a state rebuild. Deletions mask rows out of the scan
    (same shapes — the compiled dispatch is reused), staged inserts are
    folded in by an exact jitted scan of the fixed-capacity buffer
    (``sa_alsh.merge_topk`` — one extra executable ever, its capacity
    being static), and answers come back natively in artifact id space.
    Every delta-descendant of one build shares one cached state: a
    streaming ``swap`` is O(1), not O(rebuild).
    """

    def __init__(self, items: jnp.ndarray, key: jax.Array, *,
                 config: EngineConfig | str = "sah",
                 policy: ShardingPolicy = NO_SHARDING,
                 fingerprint: str | None = None,
                 share_dispatch: "RetrievalServer | None" = None):
        super().__init__(dim=items.shape[1])
        if isinstance(config, str):
            config = get_config(config)
        self.config = config
        self.policy = policy
        self.artifact: IndexArtifact | None = None
        # live staged rows (items, mask, qitems, qscale) | (None,) * 4 —
        # the quantized twin rides along so the int8 screen covers churn
        self._delta = (None, None, None, None)
        self._deleted = None         # host (n_base,) bool; None = no deletes
        self._mask_memo = None       # (ServingState, masked item_mask)
        self.cache = ServingCache(items, key, policy=policy,
                                  capacity=config.serve_cache_capacity,
                                  fingerprint=fingerprint)

        if share_dispatch is not None:
            # Adopt the donor's compiled dispatch + trace counter. Both
            # closures are config-free (k/n_cand/scan/n_base/precision
            # arrive as call-time statics; only the sharding policy is
            # baked in), so any two servers on the same mesh share every
            # executable — tenants with identical signatures re-trace
            # nothing.
            donor = share_dispatch
            if not isinstance(donor, RetrievalServer):
                raise TypeError("share_dispatch must be a RetrievalServer, "
                                f"got {type(donor).__name__}")
            if donor.policy.mesh is not policy.mesh:
                raise ValueError(
                    "share_dispatch requires the same sharding policy "
                    "mesh: compiled executables are specialized to it")
            self._traces = donor._traces
            self._dispatch = donor._dispatch
            self._merge = donor._merge
            return

        self._traces = _TraceCount()

        def _scan(items_a, ids_a, mask_a, codes_a, proj_q, queries, *,
                  k, n_cand, scan):
            # Traced once per static signature; the counter increments at
            # trace time only, so it counts compiles, not calls.
            self._traces.n += 1
            with jax.named_scope("kmips.hash"):
                ucodes = kops.srp_hash(queries, proj_q)
            return _sharding.kmips_flat_arrays(
                items_a, ids_a, mask_a, codes_a, ucodes, queries, k,
                self.policy, n_cand=n_cand, scan=scan)

        self._dispatch = jax.jit(_scan,
                                 static_argnames=("k", "n_cand", "scan"))

        def _merge(vals, ids, queries, d_items, d_mask, d_qitems,
                   d_qscale, *, k, n_base, scan_precision):
            # Fold-in of the staged delta buffer — the same merge
            # RkMIPSEngine.kmips applies, so ids agree id-for-id. The
            # buffer's capacity is static: one trace per (batch, k,
            # n_base, precision) ever, however much churn streams
            # through. Under scan_precision="int8" the persisted
            # quantized twin screens staged rows first (bitwise-equal
            # contract: sa_alsh.merge_delta_topk).
            self._traces.n += 1
            return _alsh.merge_delta_topk(
                vals, ids, queries, d_items, d_mask, k, n_base,
                d_qitems=d_qitems, d_qscale=d_qscale,
                scan_precision=scan_precision)

        self._merge = jax.jit(
            _merge, static_argnames=("k", "n_base", "scan_precision"))

    @property
    def compile_count(self) -> int:
        """Traces taken through this server's dispatch — shared with
        every server constructed with ``share_dispatch=self``."""
        return self._traces.n

    @classmethod
    def from_artifact(cls, artifact: IndexArtifact, *,
                      policy: ShardingPolicy = NO_SHARDING,
                      share_dispatch: "RetrievalServer | None" = None
                      ) -> "RetrievalServer":
        """A server over an ``IndexArtifact``'s corpus.

        The serving key derivation matches every other kMIPS surface, and
        the cache is keyed by the artifact **base** fingerprint — when the
        artifact's kMIPS index is already built, the cache is seeded from
        it, so the server scans the exact codes the engine ranks with,
        with zero extra builds. Staged deltas ride as an incremental
        overlay (class docstring); answers are natively in **artifact id
        space** (base ids; staged row j is n_base + j), agreeing
        id-for-id with ``RkMIPSEngine.kmips`` even when the artifact
        carries pending deltas.
        """
        items, key, fp = artifact.serving_base()
        srv = cls(items, key, config=artifact.config, policy=policy,
                  fingerprint=fp, share_dispatch=share_dispatch)
        srv._bind_artifact(artifact)
        return srv

    def _bind_artifact(self, artifact: IndexArtifact) -> None:
        self.artifact = artifact
        self._delta = artifact.kmips_delta_quantized()
        deleted = np.asarray(artifact.deleted)
        self._deleted = deleted if deleted.any() else None
        self._mask_memo = None
        if artifact.kmips_index is not None \
                and artifact.config not in self.cache:
            self.cache.put(artifact.config, state_from_index(
                artifact.kmips_index, artifact.config, policy=self.policy))

    def _masked_item_mask(self, state: ServingState) -> jnp.ndarray:
        """The state's scan mask with the artifact's deleted base rows
        retired — same shape, so the compiled dispatch is reused.

        Computed host-side (artifact ``deleted`` is host layout; eager ops
        on mesh-committed arrays are the hazard engine/build.py
        documents) and memoized per bound (state, artifact): one O(n)
        pass per swap, zero per flush.
        """
        if self._deleted is None:
            return state.item_mask
        if self._mask_memo is not None and self._mask_memo[0] is state:
            return self._mask_memo[1]
        ids = np.asarray(jax.device_get(state.item_ids))
        dead = (ids >= 0) & self._deleted[np.clip(ids, 0, None)]
        mask = np.asarray(jax.device_get(state.item_mask)) & ~dead
        marr = jnp.asarray(mask)
        if self.policy.mesh is not None:
            axes = tuple(self.policy.mesh.axis_names)
            marr = jax.device_put(marr, NamedSharding(self.policy.mesh,
                                                      P(axes)))
        self._mask_memo = (state, marr)
        return marr

    def swap(self, artifact: IndexArtifact) -> "RetrievalServer":
        """Make a new artifact version live between flushes.

        Pending tickets survive and are answered against the new version
        by the next ``flush``; previously built versions stay in the cache
        under their base fingerprints (swapping back is a hit). Delta
        mutations of the live base are served from the *same* cached
        state — rebind is O(1) — and when a new base's built shapes match
        the live ones, the compiled dispatch is reused — ``compile_count``
        += 0 (pinned in tests).
        """
        items, key, fp = artifact.serving_base()
        self.config = artifact.config
        self.cache.capacity = artifact.config.serve_cache_capacity
        self.cache.rebind(items, key, fingerprint=fp)
        self._dim = items.shape[1]
        self._bind_artifact(artifact)
        return self

    @property
    def batch_size(self) -> int:
        """The micro-batch size — read from the *current* config, so a
        config swapped between flushes brings its own batching along."""
        return self.config.serve_batch_size

    def bucket_for(self, n: int) -> int:
        """The dispatch size ``n`` queries pad up to: the smallest rung of
        ``config.bucket_ladder()`` that fits them. With no buckets
        configured this is always ``serve_batch_size`` — the pre-bucketing
        contract."""
        if not 1 <= n <= self.batch_size:
            raise ValueError(f"group of {n} outside [1, "
                             f"batch_size={self.batch_size}]")
        return next(b for b in self.config.bucket_ladder() if b >= n)

    def _flush_batch(self, group: list, k: int, *,
                     n_cand: int | None = None,
                     scan: str | None = None,
                     pad_to: int | None = None) -> list[ServeResult]:
        """Answer one micro-batch (<= ``batch_size`` queries) through the
        compiled dispatch — THE flush path: the synchronous ``flush`` and
        the threaded runtime's workers (engine/runtime.py) both call this,
        so their answers are bitwise identical by construction (same
        padding, same executables, same delta fold-in).

        ``pad_to`` overrides the padded dispatch size (a ladder rung from
        ``bucket_for``; defaults to the full ``batch_size``). Padding is
        dead either way — zero queries computed and discarded — so a
        bucket-padded dispatch is bitwise equal to the unbucketed one;
        only the static shape (and hence which executable runs) differs.
        """
        state = self.cache.get(self.config)
        bound = (state.n_items if self.artifact is None
                 else self.artifact.n_items)
        if not 1 <= k <= bound:
            raise ValueError(f"k={k} outside [1, {bound}] "
                             f"supported by this corpus")
        n_cand = self.config.n_cand if n_cand is None else n_cand
        scan = self.config.scan if scan is None else scan
        batch = self.batch_size if pad_to is None else pad_to
        if len(group) > batch:
            raise ValueError(f"group of {len(group)} does not fit "
                             f"pad_to={batch}")
        with TraceAnnotation("rk.flush.pad"):
            qs = _pad_batch(tuple(group), pad_to=batch)
        with TraceAnnotation("rk.flush.launch"):
            vals, ids = self._dispatch(state.items, state.item_ids,
                                       self._masked_item_mask(state),
                                       state.codes, state.proj_q, qs, k=k,
                                       n_cand=n_cand, scan=scan)
        d_items, d_mask, d_qitems, d_qscale = self._delta
        if d_items is not None:
            with TraceAnnotation("rk.flush.merge"):
                vals, ids = self._merge(
                    vals, ids, qs, d_items, d_mask, d_qitems, d_qscale, k=k,
                    n_base=self.artifact.n_base,
                    scan_precision=self.config.scan_precision)
        with TraceAnnotation("rk.flush.split"):
            # one device-to-host copy of the whole batch; each ticket's
            # answer is a read-only row view of it
            vals, ids = jax.device_get((vals, ids))
            return [ServeResult(vals[j], ids[j], k)
                    for j in range(len(group))]

    def warmup(self, ks, *, n_cands=None, scans=None,
               buckets=None) -> int:
        """Ahead-of-time compile every (bucket, k, n_cand, scan) dispatch
        cell — plus the delta merge when an artifact with live staged rows
        is bound — via ``jit(...).lower().compile()`` (DESIGN.md SS14), so
        the first real request at any ladder rung runs an executable that
        already exists: zero traces after startup, pinned by the runtime's
        ``traces_after_warmup`` counter.

        ``ks`` is the iterable of query-time ks traffic will use;
        ``n_cands``/``scans``/``buckets`` default to the config's single
        n_cand / scan and the full ``bucket_ladder()``. Returns the number
        of cells compiled. Lowering traces the same jitted callables the
        live path calls (``compile_count`` counts these warmup traces
        too), and the populated jit cache is what the live calls hit.
        Each compiled cell's instruction scopes are recorded for
        ``op_scopes``. The stack-and-pad program ``_pad_batch`` is
        compiled too, for every group length up to each rung; it is not
        a dispatch cell, so neither the returned count nor
        ``compile_count`` includes it.
        """
        state = self.cache.get(self.config)
        mask = self._masked_item_mask(state)
        d = state.items.shape[1]
        ks = tuple(ks)
        n_cands = ((self.config.n_cand,) if n_cands is None
                   else tuple(n_cands))
        scans = (self.config.scan,) if scans is None else tuple(scans)
        buckets = (self.config.bucket_ladder() if buckets is None
                   else tuple(buckets))
        # warm the merge off the artifact's raw buffer arrays, not the
        # liveness-gated self._delta: the buffer's capacity/dtypes are
        # fixed, so the executable built here is the one post-warmup
        # churn will hit — staging the first insert must not trace
        art = self.artifact
        cells = 0
        row = jax.ShapeDtypeStruct((d,), state.items.dtype)
        for b in buckets:
            for n in range(1, b + 1):
                _pad_batch.lower((row,) * n, pad_to=b).compile()
            qs = jnp.zeros((b, d), state.items.dtype)
            for k in ks:
                for nc in n_cands:
                    for sc in scans:
                        _record_scopes(self._dispatch.lower(
                            state.items, state.item_ids, mask,
                            state.codes, state.proj_q, qs, k=k,
                            n_cand=nc, scan=sc).compile())
                        cells += 1
                if art is not None:
                    vals = jnp.zeros((b, k), state.items.dtype)
                    ids = jnp.zeros((b, k), state.item_ids.dtype)
                    _record_scopes(self._merge.lower(
                        vals, ids, qs, art.delta_items, art.delta_mask,
                        art.delta_qitems, art.delta_qscale, k=k,
                        n_base=art.n_base,
                        scan_precision=self.config.scan_precision
                    ).compile())
                    cells += 1
        return cells

    def flush(self, k: int, *, n_cand: int | None = None,
              scan: str | None = None) -> list[ServeResult]:
        """Answer every pending ticket; results in submission order.

        Pending queries are grouped into micro-batches of
        ``serve_batch_size``; the final partial group is padded to the full
        batch size with zero queries so every dispatch reuses the same
        compiled executable. k/n_cand/scan default to the server's config.

        Tickets stay pending until the whole flush succeeds: a failed
        dispatch (or a bad ``k``) raises without consuming the queue, so a
        retry answers every ticket — dispatch is deterministic, no answer
        is lost or doubled.
        """
        if not self._pending:
            return []
        batch = self.batch_size
        queue = list(self._pending)
        out: list[ServeResult] = []
        for i in range(0, len(queue), batch):
            out.extend(self._flush_batch(queue[i:i + batch], k,
                                         n_cand=n_cand, scan=scan))
        del self._pending[:len(queue)]
        return out

    def kmips(self, q: jnp.ndarray, k: int, *, n_cand: int | None = None,
              scan: str | None = None) -> ServeResult:
        """Serve one query now: submit + flush. Pending tickets (if any)
        are answered by the same flush, preserving submission order."""
        return self._serve_one(
            q, lambda: self.flush(k, n_cand=n_cand, scan=scan), "kmips")


class ReverseResult(NamedTuple):
    """One served reverse (RkMIPS) query's answer.

    predictions: (m,) bool in original user rows — which users would see
                 the promoted item in their top-k.
    stats:       this query's row of core/sah.py::QueryStats.
    k:           the k answered.
    truncated:   True iff a scan budget (EngineConfig.scan_budget) stopped
                 this query's execute scan early. A truncated answer is
                 conservative — skipped lanes resolve to "not in the
                 audience" — never silently wrong, and ``funnel`` carries
                 the batch's pruning snapshot so the caller can see how
                 far the scan got.
    funnel:      engine.PruningFunnel for the dispatch that answered this
                 ticket (batch-level; None until filled by the server).
    """

    predictions: jnp.ndarray
    stats: object
    k: int
    truncated: bool = False
    funnel: object = None


class ReverseServer(_TicketQueue):
    """Online RkMIPS serving: accumulate promoted items, answer in batches.

    A ticket queue over ``RkMIPSEngine.query_batch`` — the batched
    plan/execute pipeline IS the online dispatch (DESIGN.md SS9): batch
    size is a pure throughput knob (one trace per batch shape, mixed-query
    chunks load-balance themselves), so reverse serving needs no private
    scan path the way forward serving once did.

    ``submit`` enqueues a query (d,) — or a block (nq, d), one ticket per
    row — and returns the ticket(s); ``flush(k)`` answers every pending
    ticket in submission order, grouping them into micro-batches of
    ``config.serve_batch_size``. The final partial group is padded to the
    full batch size by repeating its first query (a real vector, so every
    bound stays well-behaved; the padded rows are computed and discarded),
    keeping shapes static: the engine's ``rkmips_compile_count`` — exposed
    here as ``compile_count`` — stays at one per distinct (batch size, k),
    pinned by tests/test_serving.py. Per-ticket answers are bitwise the
    matching rows of a one-shot ``query_batch`` (work-queue lanes are
    independent, see core/sah.py).

    Tickets stay pending until a flush succeeds: a failed dispatch (or a
    bad ``k``) raises without consuming the queue, so a retry answers
    every ticket.
    """

    def __init__(self, engine):
        engine.index                      # raises unless built for RkMIPS
        super().__init__(dim=engine.index.users.shape[-1])
        self.engine = engine

    def swap(self, artifact: IndexArtifact) -> "ReverseServer":
        """Make a new artifact version live between flushes (DESIGN.md
        SS10): re-attaches the underlying engine. Pending tickets survive
        and are answered against the new version by the next ``flush``;
        when the new version's shapes match the live ones the engine's
        compiled dispatch is reused (``compile_count`` += 0 — a staged
        delta buffer adds at most one executable ever, its capacity being
        static)."""
        if artifact.users is None:
            # refuse BEFORE touching the engine: a half-applied swap would
            # strand every pending ticket (the retry contract)
            raise RuntimeError(
                "cannot swap a kMIPS-only artifact into a ReverseServer: "
                "the artifact is not built for RkMIPS (users=None)")
        self.engine.attach(artifact)
        self._dim = self.engine.index.users.shape[-1]
        return self

    @property
    def batch_size(self) -> int:
        """Micro-batch size, read from the engine's config."""
        return self.engine.config.serve_batch_size

    @property
    def compile_count(self) -> int:
        """Traces the engine's reverse dispatch has cost (one per distinct
        (batch shape, k); serving adds no executables of its own)."""
        return self.engine.rkmips_compile_count

    def bucket_for(self, n: int) -> int:
        """The dispatch size ``n`` queries pad up to: the smallest rung of
        the engine config's ``bucket_ladder()`` that fits them. With no
        buckets configured this is always ``serve_batch_size``."""
        if not 1 <= n <= self.batch_size:
            raise ValueError(f"group of {n} outside [1, "
                             f"batch_size={self.batch_size}]")
        return next(b for b in self.engine.config.bucket_ladder()
                    if b >= n)

    def warmup(self, ks, *, buckets=None) -> int:
        """Ahead-of-time compile the engine's reverse dispatch at every
        (bucket, k) cell (DESIGN.md SS14) — delegates to
        ``RkMIPSEngine.warmup``, since reverse serving owns no executables
        of its own. Returns the number of cells compiled."""
        buckets = (self.engine.config.bucket_ladder() if buckets is None
                   else tuple(buckets))
        return self.engine.warmup(ks, batch_sizes=buckets)

    def _flush_batch(self, group: list, k: int, *,
                     pad_to: int | None = None) -> list[ReverseResult]:
        """Answer one micro-batch (<= ``batch_size`` queries) through the
        engine's batched dispatch — THE flush path shared by the
        synchronous ``flush`` and the threaded runtime's workers
        (engine/runtime.py): same repeat-padding, same executable, so
        their answers are bitwise identical by construction.

        ``pad_to`` overrides the padded dispatch size (a ladder rung from
        ``bucket_for``; defaults to the full ``batch_size``). Repeat-padded
        rows are computed and discarded and work-queue lanes are
        independent, so a bucket-padded dispatch is bitwise equal to the
        unbucketed one — only the static shape differs."""
        batch = self.batch_size if pad_to is None else pad_to
        if len(group) > batch:
            raise ValueError(f"group of {len(group)} does not fit "
                             f"pad_to={batch}")
        with TraceAnnotation("rk.flush.pad"):
            qs = jnp.stack(group)
            if len(group) < batch:
                qs = jnp.concatenate(
                    [qs, jnp.broadcast_to(qs[:1], (batch - len(group),)
                                          + qs.shape[1:])])
        with TraceAnnotation("rk.flush.launch"):
            res = self.engine.query_batch(qs, k)
        with TraceAnnotation("rk.flush.split"):
            # Per-ticket truncation flag: the stats row carries 1 iff a
            # scan budget skipped lanes of THAT query (core/sah.py
            # trunc_q); the funnel snapshot rides along so truncation is
            # never silent.
            trunc = np.asarray(res.stats.truncated)
            return [
                ReverseResult(res.predictions[j],
                              jax.tree.map(lambda s, j=j: s[j], res.stats),
                              k,
                              truncated=bool(trunc[j] > 0),
                              funnel=res.funnel)
                for j in range(len(group))]

    def flush(self, k: int) -> list[ReverseResult]:
        """Answer every pending ticket; results in submission order."""
        if not self._pending:
            return []
        batch = self.batch_size
        queue = list(self._pending)
        out: list[ReverseResult] = []
        for i in range(0, len(queue), batch):
            out.extend(self._flush_batch(queue[i:i + batch], k))
        del self._pending[:len(queue)]
        return out

    def rkmips(self, q: jnp.ndarray, k: int) -> ReverseResult:
        """Serve one reverse query now: submit + flush. Pending tickets
        (if any) are answered by the same flush, in submission order."""
        return self._serve_one(q, lambda: self.flush(k), "rkmips")
