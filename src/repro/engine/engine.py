"""RkMIPSEngine: the one front door for (R)kMIPS (DESIGN.md SS7, SS10).

The facade owns the full query lifecycle that examples, benchmarks and the
serving stack used to hand-roll from ``core/`` pieces:

    eng = RkMIPSEngine("sah").build(items, users, key)
    res = eng.query_batch(promoted_items, k=10)     # res.predictions (nq, m)
    truth = eng.oracle(promoted_items, k=10)        # same tie_eps, always

Since the artifact redesign (DESIGN.md SS10), *building* is separate from
*serving*: ``build()`` is sugar for "make an ``IndexArtifact``, then
``attach`` it", and an engine can equally be stood up from a saved or
streamed-in artifact version:

    art = IndexArtifact.build(items, users, key, config=cfg)   # offline
    art.save("/ckpt/sah")                                      # ship it
    eng = RkMIPSEngine.from_artifact(IndexArtifact.load("/ckpt/sah"),
                                     policy=mesh_policy)       # any mesh
    eng.attach(art.insert_items(new_rows))                     # hot swap

Guarantees the raw ``core/sah.py`` path does not give:

  * predictions come back in **original user-id space** — the leaf-order /
    ``predictions_to_original`` footgun lives behind the facade;
  * build and query can never disagree on a knob: both read one frozen
    ``EngineConfig`` (including ``tie_eps``, which ``oracle()`` shares);
  * a ``ShardingPolicy`` with a mesh transparently shards the dense tau
    matvec + sketch scans over users (queries) and over items (kmips) —
    ``engine/sharding.py`` — with no caller-visible API change. Artifacts
    are stored host-side and mesh-agnostic; ``attach`` lays them out for
    *this* engine's policy, which is what makes a save on one mesh load
    onto any other (the SS6 elastic-restore story applied to indexes);
  * an attached artifact with staged corpus deltas is served honestly:
    deletions leave the scans, staged inserts are exactly counted from the
    fixed-capacity delta buffer (one extra executable ever), and the
    ``oracle`` answers over the *mutated* corpus.

``core/`` stays purely functional underneath (SS1): the engine holds arrays
and timings, never the other way around.
"""

from __future__ import annotations

import time
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import exact as _exact
from repro.core import sa_alsh as _alsh
from repro.core import sah as _sah
from repro.dist.policy import NO_SHARDING, ShardingPolicy
from repro.engine import artifact as _artifact
from repro.engine import sharding as _sharding
from repro.engine.config import EngineConfig, get_config

# Backward-compat alias; the tag lives with the artifact lifecycle now.
_KMIPS_KEY_TAG = _artifact.KMIPS_KEY_TAG


class PruningFunnel(NamedTuple):
    """Aggregate pruning funnel of one RkMIPS batch, summed over queries:
    blocks -> users -> scan lanes -> tiles (derived from the per-query
    ``QueryStats`` counters the batched driver recovers per lane).

    blocks_total / users_total are nq * (count the counters are measured
    against): alive fractions read directly as funnel stage widths.
    tiles_scanned / chunks are the execute phase's packing diagnostics
    (mixed-query chunks share tile visits, see core/sah.py).
    """

    queries: int
    blocks_total: int
    blocks_alive: int
    users_total: int
    users_alive: int
    decided_no_lb: int
    decided_yes_norm: int
    scan_lanes: int
    tiles_scanned: int
    chunks: int
    truncated: int = 0          # queries the scan budget cut short (SS15)

    def format(self) -> str:
        """One human-readable funnel line (examples/quickstart.py)."""
        tail = (f" ({self.truncated} budget-truncated)"
                if self.truncated else "")
        return (f"{self.queries} queries: "
                f"blocks {self.blocks_alive}/{self.blocks_total} alive -> "
                f"users {self.users_alive}/{self.users_total} alive -> "
                f"scan lanes {self.scan_lanes} "
                f"(no-by-bound {self.decided_no_lb}, "
                f"yes-by-norm {self.decided_yes_norm}) -> "
                f"{self.tiles_scanned} tile-visits in {self.chunks} chunks"
                f"{tail}")


class QueryResult(NamedTuple):
    """One RkMIPS answer, already mapped to original user rows.

    predictions: bool, (m,) for query() / (nq, m) for query_batch().
    stats:       core/sah.py::QueryStats (scalar / (nq,) counters).
    seconds:     wall time of the call, compile included on first use.
    k:           the k answered.
    funnel:      aggregate PruningFunnel over the batch.
    """

    predictions: jnp.ndarray
    stats: _sah.QueryStats
    seconds: float
    k: int
    funnel: PruningFunnel | None = None


class KMIPSResult(NamedTuple):
    """Forward top-k MIPS answer (values descending, original item rows)."""

    values: jnp.ndarray
    ids: jnp.ndarray
    tiles_visited: int
    seconds: float
    k: int


class _TraceCount:
    """Mutable compile counter, shared by every engine/server adopting one
    dispatch (``share_dispatch``): the trace fires inside the *owner's*
    closure, so sharers must read the owner's count, not a private zero."""

    __slots__ = ("n",)

    def __init__(self):
        self.n = 0


class RkMIPSEngine:
    """Config-driven, mesh-aware engine for RkMIPS and kMIPS.

    config: an ``EngineConfig`` or a registry name ("sah", "simpfer", ...).
    policy: sharding policy; ``NO_SHARDING`` (default) is single-device,
            a mesh policy shards users/items over every mesh axis.
    share_dispatch: another ``RkMIPSEngine`` whose compiled reverse
            dispatch (jitted callables + trace counter) this engine adopts
            instead of building its own — the multi-tenant trace-sharing
            seam (DESIGN.md SS15): tenants whose configs agree on every
            query knob (``scan_budget``, an execution-only traced operand,
            may differ) and whose artifacts share shapes then share one
            executable cache, so the second tenant's warmup adds zero
            traces. Requires config equality up to ``scan_budget`` and the
            same mesh.

    The engine serves whatever ``IndexArtifact`` version is currently
    attached (``self.artifact``); ``build()`` both makes and attaches one.
    """

    def __init__(self, config: EngineConfig | str = "sah", *,
                 policy: ShardingPolicy = NO_SHARDING,
                 share_dispatch: "RkMIPSEngine | None" = None):
        if isinstance(config, str):
            config = get_config(config)
        if not isinstance(config, EngineConfig):
            raise TypeError(f"config must be an EngineConfig or a registry "
                            f"name, got {type(config).__name__}")
        self.config = config
        self.policy = policy
        self.build_seconds: float | None = None
        self.artifact: _artifact.IndexArtifact | None = None
        self._index: _sah.SAHIndex | None = None
        self._delta: tuple = (None, None, None, None)
        self._items: jnp.ndarray | None = None
        self._users_unit: jnp.ndarray | None = None
        self._key: jax.Array | None = None
        self.n_users: int | None = None
        # The per-query scan budget rides every dispatch as a TRACED int32
        # operand (never a static): engines differing only in budget hit
        # the same executable.
        self._budget = jnp.asarray(config.scan_budget, jnp.int32)
        self.rkmips_mapped_compile_count = 0

        def _rkmips(index, queries, d_items, d_mask, d_qitems, d_qscale,
                    budget, *, k):
            self._traces.n += 1
            return _sharding.rkmips_batch(index, queries, k, self.policy,
                                          delta_items=d_items,
                                          delta_mask=d_mask,
                                          delta_qitems=d_qitems,
                                          delta_qscale=d_qscale,
                                          scan_budget=budget,
                                          **self.config.query_kwargs())

        def _rkmips_eager(index, queries, d_items, d_mask, d_qitems,
                          d_qscale, budget, *, k):
            # Key on everything the executable cache keys on: the index
            # leaves' shapes too, so a rebuild with new sizes counts its
            # recompile instead of hiding behind an old query signature.
            sig = (queries.shape, str(queries.dtype), k,
                   None if d_items is None else
                   (d_items.shape, str(d_items.dtype)),
                   tuple((l.shape, str(l.dtype))
                         for l in jax.tree.leaves(index)))
            if sig not in self._rkmips_seen:
                self._rkmips_seen.add(sig)
                self._traces.n += 1
            return _sharding.rkmips_batch(index, queries, k, self.policy,
                                          delta_items=d_items,
                                          delta_mask=d_mask,
                                          delta_qitems=d_qitems,
                                          delta_qscale=d_qscale,
                                          scan_budget=budget,
                                          **self.config.query_kwargs())

        def _rkmips_mapped(index, queries, d_items, d_mask, d_qitems,
                           d_qscale, *, k):
            self.rkmips_mapped_compile_count += 1
            return _sah.rkmips_batch_mapped(index, queries, k,
                                            delta_items=d_items,
                                            delta_mask=d_mask,
                                            delta_qitems=d_qitems,
                                            delta_qscale=d_qscale,
                                            **self.config.query_kwargs())

        if share_dispatch is not None:
            donor = share_dispatch
            if not isinstance(donor, RkMIPSEngine):
                raise TypeError(f"share_dispatch expects an RkMIPSEngine, "
                                f"got {type(donor).__name__}")
            # Everything but the budget must agree: the adopted closure
            # reads the DONOR's query_kwargs() at trace time, so any other
            # difference would silently serve the donor's knobs.
            if donor.config.replace(
                    scan_budget=config.scan_budget) != config:
                raise ValueError(
                    "share_dispatch requires configs equal in every field "
                    "except scan_budget (the budget is a traced operand; "
                    "all other query knobs bake into the shared trace)")
            if donor.policy.mesh is not policy.mesh:
                raise ValueError("share_dispatch requires the same "
                                 "sharding policy mesh")
            self._traces = donor._traces
            self._rkmips_seen = donor._rkmips_seen
            self._rkmips_dispatch = donor._rkmips_dispatch
        else:
            # Every reverse query routes through one dispatch of the
            # batched plan/execute pipeline (sharded or not).
            # rkmips_compile_count counts compiles, not calls: exactly one
            # per distinct (batch shape, k) — batch size is a pure
            # throughput knob (pinned by tests/test_batched.py), and an
            # attached delta buffer adds exactly one more signature (its
            # capacity is static, so corpus churn never retraces).
            # Single-device the counter increments at jit trace time
            # (ground truth); under a mesh the shard_map must dispatch
            # eagerly — an *outer* jit staged around it once miscompiled
            # the while-driver (wrong predictions, caught by the
            # sharded-equivalence test, DESIGN.md SS7) — so there the
            # counter keys on distinct dispatch signatures, which is
            # exactly how the XLA executable cache keys its compiles.
            self._traces = _TraceCount()
            self._rkmips_seen: set = set()
            if policy.mesh is None:
                self._rkmips_dispatch = jax.jit(_rkmips,
                                                static_argnames=("k",))
            else:
                self._rkmips_dispatch = _rkmips_eager
        self._rkmips_mapped_dispatch = jax.jit(_rkmips_mapped,
                                               static_argnames=("k",))

    @property
    def rkmips_compile_count(self) -> int:
        """Reverse-dispatch traces so far — shared with every engine in
        this engine's ``share_dispatch`` group (the trace happens in one
        closure, whoever triggered it)."""
        return self._traces.n

    # -- lifecycle ---------------------------------------------------------

    def build(self, items: jnp.ndarray, users: jnp.ndarray | None,
              key: jax.Array) -> "RkMIPSEngine":
        """Index ``items`` (n, d) for ``users`` (m, d). Returns self.

        Sugar for ``attach(IndexArtifact.build(items, users, key,
        config=self.config, policy=self.policy))`` — bit-for-bit the raw
        ``sah.build`` path with this config's kwargs (the staged pipeline
        of engine/build.py; under a mesh policy the row-parallel stages
        shard per ``config.build_sharding``, same artifact bitwise).
        ``users=None`` builds a kMIPS-only engine (no user-side SAH
        index): ``kmips()`` works, ``query*()`` raise. The kMIPS index
        key is derived with the same ``fold_in`` tag whether it is built
        eagerly (users=None) or lazily on first ``kmips()``, so
        ``server()`` and every kMIPS path rank with the identical SRP
        codes. Inputs are validated up front (2-D, floating, matching
        dimensionality; positive build knobs) with a clear ``ValueError``.
        The per-stage wall-time breakdown lands on ``self.build_timings``.
        """
        t0 = time.perf_counter()
        art = _artifact.IndexArtifact.build(items, users, key,
                                            config=self.config,
                                            policy=self.policy)
        self.attach(art)
        self.build_seconds = time.perf_counter() - t0
        return self

    @classmethod
    def from_artifact(cls, artifact: "_artifact.IndexArtifact", *,
                      policy: ShardingPolicy = NO_SHARDING
                      ) -> "RkMIPSEngine":
        """An engine serving ``artifact`` under ``policy`` — the restore /
        hand-off path: the artifact's own config drives every knob, and
        ``attach`` lays its host-side arrays out for this policy's mesh
        (elastic: the saving mesh is irrelevant)."""
        return cls(artifact.config, policy=policy).attach(artifact)

    def attach(self, artifact: "_artifact.IndexArtifact") -> "RkMIPSEngine":
        """Make ``artifact`` the engine's live index version. Returns self.

        Drops every derived product of the previous version, places the
        user/block arrays on the mesh when the policy carries one, and
        wires up the staged-delta buffer (if any). Attaching a same-shape
        version (a hot swap) reuses every compiled executable — the
        dispatch signatures are shape-keyed, and the delta buffer's
        capacity is static.
        """
        if not isinstance(artifact, _artifact.IndexArtifact):
            raise TypeError(f"attach expects an IndexArtifact, got "
                            f"{type(artifact).__name__}")
        # delta_capacity, build_sharding, scan_precision and scan_budget
        # are lifecycle/execution knobs, not build/query recipe fields
        # (engine/config.py): the artifact's own buffer governs, the built
        # content is sharding-independent, both scan precisions predict
        # bitwise alike, and the budget only caps execution, so configs
        # differing only there are interchangeable here
        if artifact.config.replace(
                delta_capacity=self.config.delta_capacity,
                build_sharding=self.config.build_sharding,
                scan_precision=self.config.scan_precision,
                scan_budget=self.config.scan_budget) != self.config:
            raise ValueError(
                "artifact config does not match this engine's config; use "
                "RkMIPSEngine.from_artifact(artifact) (or rebuild the "
                "artifact with the engine's config)")
        self.artifact = artifact
        self._items = artifact.effective_items()
        self._key = artifact.key
        self._index = None
        self._users_unit = None
        self.n_users = None
        if artifact.users is None:
            # no user-side index, but live staged inserts still ride the
            # forward merge (kmips); query_view can't be asked here
            self._delta = artifact.kmips_delta_quantized()
            jax.block_until_ready(artifact.ensure_kmips_index().codes)
            return self
        # query_view owns the delta-liveness rule: the buffer it returns is
        # exactly the one its adjusted top_norms covers (stale-norm safety);
        # the persisted int8 twin rides along for the SS13 reverse screen
        view, d_items, d_mask = artifact.query_view()
        self._delta = ((None, None, None, None) if d_items is None else
                       (d_items, d_mask, artifact.delta_qitems,
                        artifact.delta_qscale))
        if self.policy.mesh is not None:
            view = _sharding.shard_index(view, self.policy)
        jax.block_until_ready(view.users)
        self._index = view
        self.n_users = artifact.n_users
        self._users_unit = artifact.users_unit()
        return self

    def _require_artifact(self) -> "_artifact.IndexArtifact":
        if self.artifact is None:
            raise RuntimeError("engine not built: call "
                               "build(items, users, key) first")
        return self.artifact

    @property
    def index(self) -> _sah.SAHIndex:
        """The attached query view (built arrays; read-only by convention).

        Under a mesh policy this is the padded, device-placed layout; the
        artifact keeps the mesh-agnostic original."""
        if self._index is None:
            raise RuntimeError("engine not built for RkMIPS: call "
                               "build(items, users, key) first")
        return self._index

    @property
    def kmips_index(self) -> _alsh.SAALSHIndex:
        """The full-base-corpus SA-ALSH index (built lazily on first use,
        memoized on the attached artifact)."""
        return self._require_artifact().ensure_kmips_index()

    @property
    def build_timings(self):
        """Per-stage ``BuildTimings`` of the attached artifact's build
        (engine/build.py), or None when the artifact was loaded from disk
        / wired from pieces rather than built this process."""
        return None if self.artifact is None else self.artifact.build_timings

    def _check_k(self, k: int) -> None:
        if not 1 <= k <= self.config.k_max:
            raise ValueError(f"k={k} outside [1, k_max={self.config.k_max}] "
                             f"supported by this index; rebuild with a "
                             f"larger k_max")

    # -- reverse queries ---------------------------------------------------

    def _funnel(self, stats: _sah.QueryStats, nq: int) -> PruningFunnel:
        """Aggregate the per-query counters into one PruningFunnel.

        Sums run host-side on the already-materialized (nq,) counters —
        the result is blocked on before this runs — so building the
        funnel launches no device work (serving flushes call this per
        micro-batch)."""
        tot = lambda x: int(np.asarray(x).sum())
        return PruningFunnel(
            queries=nq,
            blocks_total=nq * self.index.n_blocks,
            blocks_alive=tot(stats.blocks_alive),
            users_total=nq * self.n_users,
            users_alive=tot(stats.users_alive),
            decided_no_lb=tot(stats.n_no_lb),
            decided_yes_norm=tot(stats.n_yes_norm),
            scan_lanes=tot(stats.n_scan),
            tiles_scanned=tot(stats.tiles_scanned),
            chunks=tot(stats.chunks),
            truncated=int((np.asarray(stats.truncated) > 0).sum()))

    def query(self, q: jnp.ndarray, k: int) -> QueryResult:
        """RkMIPS for one query (d,): which users have q in their top-k.

        A batch of one through the same plan/execute dispatch as
        ``query_batch`` (bitwise equal to the per-query reference driver,
        see core/sah.py). Executables are keyed per (batch shape, k), so
        single queries compile their own (1, d) executable — once — and
        every later single query reuses it.
        """
        index = self.index
        self._check_k(k)
        t0 = time.perf_counter()
        pred, stats = self._rkmips_dispatch(index, q[None], *self._delta,
                                            self._budget, k=k)
        pred = pred[0]
        stats = jax.tree.map(lambda s: s[0], stats)
        po = _sah.predictions_to_original(index, pred, self.n_users)
        jax.block_until_ready(po)
        return QueryResult(po, stats, time.perf_counter() - t0, k,
                           self._funnel(stats, 1))

    def query_batch(self, queries: jnp.ndarray, k: int) -> QueryResult:
        """RkMIPS for a batch (nq, d) -> predictions (nq, m).

        One jitted dispatch of the batched plan/execute pipeline
        (core/sah.py, sharded by ``engine/sharding.py`` under a mesh
        policy): one trace per distinct (nq, k) however large the batch —
        ``rkmips_compile_count`` exposes the trace count. Answers reflect
        the attached artifact's staged corpus deltas (DESIGN.md SS10). The
        result's ``funnel`` aggregates the recovered per-query pruning
        counters.
        """
        index = self.index
        self._check_k(k)
        t0 = time.perf_counter()
        pred, stats = self._rkmips_dispatch(index, queries, *self._delta,
                                            self._budget, k=k)
        po = _sah.predictions_to_original(index, pred, self.n_users)
        jax.block_until_ready(po)
        return QueryResult(po, stats, time.perf_counter() - t0, k,
                           self._funnel(stats, queries.shape[0]))

    def query_batch_mapped(self, queries: jnp.ndarray, k: int) -> QueryResult:
        """The legacy ``lax.map``-of-per-query-while-loops batch driver.

        Retained behind the facade as the benchmark baseline the flat-queue
        ``query_batch`` is compared against (benchmarks/bench_rkmips.py) and
        as a second reference for equivalence tests. Single-device only:
        the sharded path is flat-queue only (DESIGN.md SS9).
        """
        index = self.index
        self._check_k(k)
        if self.policy.mesh is not None:
            raise RuntimeError("query_batch_mapped is the single-device "
                               "reference driver; use query_batch under a "
                               "mesh policy")
        t0 = time.perf_counter()
        pred, stats = self._rkmips_mapped_dispatch(index, queries,
                                                   *self._delta, k=k)
        po = _sah.predictions_to_original(index, pred, self.n_users)
        jax.block_until_ready(po)
        return QueryResult(po, stats, time.perf_counter() - t0, k,
                           self._funnel(stats, queries.shape[0]))

    def warmup(self, ks, *, batch_sizes=None) -> int:
        """Ahead-of-time compile the reverse dispatch at every (batch, k)
        cell (DESIGN.md SS14) so the first real query of any warmed shape
        runs an executable that already exists — the serving runtime's
        ``traces_after_warmup == 0`` guarantee.

        ``ks`` is the iterable of query-time ks traffic will use;
        ``batch_sizes`` defaults to the config's ``bucket_ladder()`` (the
        serving dispatch sizes). Single-device this lowers and compiles
        the jitted dispatch per cell (``jit(...).lower().compile()``
        populates the same executable cache live calls hit — the
        maxtext ``aot_compile`` pattern); under a mesh the dispatch is
        eager shard_map (DESIGN.md SS9), so warmup *runs* one dummy batch
        per cell instead, which primes the identical signature-keyed
        cache. ``rkmips_compile_count`` counts warmup traces like any
        others. Returns the number of cells compiled.
        """
        index = self.index                 # raises unless built for RkMIPS
        d = index.users.shape[-1]
        batch_sizes = (self.config.bucket_ladder() if batch_sizes is None
                       else tuple(batch_sizes))
        # warm the live delta signature — and, when the buffer is empty
        # but artifact-backed, the buffer-array signature too: the first
        # post-warmup insert flips self._delta from all-None to the
        # fixed-capacity arrays (plus their int8 twin), and that flip must
        # not trace
        deltas = [self._delta]
        if self.artifact is not None and self._delta[0] is None:
            deltas.append((self.artifact.delta_items,
                           self.artifact.delta_mask,
                           self.artifact.delta_qitems,
                           self.artifact.delta_qscale))
        cells = 0
        for b in batch_sizes:
            qs = jnp.zeros((b, d), index.users.dtype)
            for k in tuple(ks):
                self._check_k(k)
                for delta in deltas:
                    if self.policy.mesh is None:
                        self._rkmips_dispatch.lower(
                            index, qs, *delta, self._budget, k=k).compile()
                    else:
                        pred, _ = self._rkmips_dispatch(index, qs, *delta,
                                                        self._budget, k=k)
                        jax.block_until_ready(pred)
                    cells += 1
        return cells

    # -- forward queries ---------------------------------------------------

    def kmips(self, q: jnp.ndarray, k: int, *,
              n_cand: int | None = None) -> KMIPSResult:
        """Approximate top-k MIPS over the full (mutated) item set.

        q: (d,) or (Q, d). Wraps ``core/sa_alsh.py::kmips_topk`` (tiled,
        early-terminating) on one device; with a mesh policy, the sharded
        single-pass scan of engine/sharding.py — which covers every row,
        so ``tiles_visited`` reports the full tile count there by design.
        Deleted rows are masked out of the scan; staged inserts are folded
        in by a scan of the delta buffer (``sa_alsh.merge_delta_topk``),
        with ids ``n_base + slot`` — under ``scan_precision="int8"`` the
        buffer's persisted quantized twin screens staged rows first, with
        the same bitwise-equal answers. n_cand overrides the config's
        re-rank depth for recall/latency sweeps.
        """
        art = self._require_artifact()
        index = art.kmips_query_view()
        n_cand = self.config.n_cand if n_cand is None else n_cand
        queries = q if q.ndim == 2 else q[None]
        t0 = time.perf_counter()
        if self.policy.mesh is not None:
            vals, ids = _sharding.kmips_flat(index, queries, k, self.policy,
                                             n_cand=n_cand,
                                             scan=self.config.scan)
            tiles = index.tile_max_norm.shape[0]
        else:
            # the tiled scan re-ranks per tile: depth cannot exceed the tile
            vals, ids, tiles = _alsh.kmips_topk(index, queries, k,
                                                n_cand=min(n_cand,
                                                           index.tile),
                                                scan=self.config.scan)
            tiles = int(tiles)
        d_items, d_mask = self._delta[:2]
        if d_items is not None:
            vals, ids = _alsh.merge_delta_topk(
                vals, ids, queries, d_items, d_mask, k, art.n_base,
                d_qitems=art.delta_qitems, d_qscale=art.delta_qscale,
                scan_precision=self.config.scan_precision)
        jax.block_until_ready(vals)
        seconds = time.perf_counter() - t0
        if q.ndim == 1:
            vals, ids = vals[0], ids[0]
        return KMIPSResult(vals, ids, tiles, seconds, k)

    # -- online serving ----------------------------------------------------

    def server(self):
        """An online ``RetrievalServer`` over this engine's attached
        artifact (engine/serving.py, DESIGN.md SS8).

        The server inherits the artifact's config and this engine's
        sharding policy, and its state cache is keyed by the artifact
        fingerprint + index recipe — when the engine's kMIPS index is
        already built (and no deltas are staged), the cache is seeded from
        it, so no second offline build of the same corpus ever happens.
        A new artifact version goes live with ``server.swap(artifact)``.
        """
        from repro.engine import serving as _serving
        return _serving.RetrievalServer.from_artifact(
            self._require_artifact(), policy=self.policy)

    def reverse_server(self):
        """An online ``ReverseServer`` over this engine (engine/serving.py).

        Micro-batched RkMIPS serving as a ticket queue over
        ``query_batch``: the batched plan/execute dispatch is shared, so
        serving costs no extra executables and every answer is bitwise a
        row of the equivalent one-shot batch. Requires a user-side build.
        ``swap(artifact)`` re-attaches between flushes without dropping
        tickets.
        """
        from repro.engine import serving as _serving
        return _serving.ReverseServer(self)

    def async_server(self, **runtime_kwargs):
        """A threaded ``ServingRuntime`` over ``server()`` — forward
        serving as a loop: futures on submit, worker-thread flushes,
        optional background compaction (engine/runtime.py, DESIGN.md
        SS12). Keyword args go to ``ServingRuntime``."""
        from repro.engine import runtime as _runtime
        return _runtime.ServingRuntime(self.server(), **runtime_kwargs)

    def async_reverse_server(self, **runtime_kwargs):
        """A threaded ``ServingRuntime`` over ``reverse_server()`` —
        RkMIPS serving as a loop (engine/runtime.py, DESIGN.md SS12).
        Keyword args go to ``ServingRuntime``."""
        from repro.engine import runtime as _runtime
        return _runtime.ServingRuntime(self.reverse_server(),
                                       **runtime_kwargs)

    # -- ground truth ------------------------------------------------------

    def oracle(self, queries: jnp.ndarray, k: int) -> jnp.ndarray:
        """Exact RkMIPS truth (nq, m) with the engine's own tie_eps — the
        F1 denominator can never drift from the index's tie convention.
        Computed over the attached artifact's *effective* (mutated) corpus,
        so staged deltas are judged against the truth they changed."""
        if self._users_unit is None:
            raise RuntimeError("engine not built for RkMIPS: call "
                               "build(items, users, key) first")
        queries = queries if queries.ndim == 2 else queries[None]
        return _exact.rkmips_batch_chunked(self._items, self._users_unit,
                                           queries, k,
                                           tie_eps=self.config.tie_eps)


def serving_codes(item_vecs: jnp.ndarray, key: jax.Array, *,
                  n_bits: int = 256, config: EngineConfig | None = None
                  ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """DEPRECATED offline sketch build — use the artifact surface instead:

        art = IndexArtifact.build(item_vecs, None, key,
                                  config=cfg.replace(n_bits=n_bits))
        codes, proj_q = art.serving_codes()

    This shim builds exactly that artifact and forwards, so its codes are
    identical to every other kMIPS surface sharing the recipe (the key is
    folded with the shared tag; pre-artifact releases hashed with the raw
    key). Kept one release for ``launch/serve.py``-era callers.
    """
    warnings.warn(
        "repro.engine.serving_codes is deprecated: build an IndexArtifact "
        "and call artifact.serving_codes() (see engine/artifact.py). Note "
        "the codes now derive from fold_in(key, KMIPS_KEY_TAG) — the "
        "shared tag every kMIPS surface uses — and differ from "
        "pre-artifact releases, which hashed with the raw key: regenerate "
        "any persisted codes/projection pair together, never mix releases",
        DeprecationWarning, stacklevel=2)
    cfg = (config or get_config("sah")).replace(n_bits=n_bits)
    art = _artifact.IndexArtifact.build(item_vecs, None, key, config=cfg)
    return art.serving_codes()
