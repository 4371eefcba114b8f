"""SAH: Shifting-aware Asymmetric Hashing for RkMIPS (Algorithms 4-5).

Combines SA-ALSH (core/sa_alsh.py) over items with cone blocking
(core/cone.py) and Simpfer lower bounds (core/simpfer.py) over users.

Indexing (Algorithm 4):
  1. sort items by descending norm; P' = the n_top highest-norm items;
  2. exact lower-bound arrays L_u over P' for every user (batched matmul);
  3. SA-ALSH index over P \\ P';
  4. cone blocks over unit users; block lower bounds L_B = min over leaf.

Query (Algorithm 5), batched over queries AND users in two phases
(plan/execute, DESIGN.md SS9):

  plan (rkmips_plan) -- for every (query, user) pair of the batch:
  1. node-level bound (Lemma 2) kills whole blocks: ub_B < L_B[k-1];
  2. vector-level bound (Lemma 3) kills users: ub_u < L_u[k-1];
  3. tau = <u, q> computed densely (one (m,d) matvec per query -- on TPU
     this is cheaper than gathering survivors; the bounds' value is keeping
     users out of the expensive scan, and we report both pruning stages in
     the stats); "no" if tau < L_u[k-1]; "yes" if tau >= ||p_k|| (k-th
     largest item norm);
  4. the undecided (query, user) pairs of the WHOLE batch are compacted
     into one flat work queue, query-major with cone-leaf order preserved
     within each query (cone order => chunk locality: users in the same
     cone have correlated early-exit depths, so chunks finish together).

  execute (rkmips_execute) -- ONE while_loop drives fixed-size, possibly
  mixed-query chunks of that queue through the counting scan
  decide_count(): each lane carries its own tau and eps, so lanes from a
  fast query never idle next to a slow query's lanes, and batch size is a
  pure throughput knob (compile cost is O(1) in nq -- this is also what
  makes the sharded path trace once, see engine/sharding.py).

The per-query ``rkmips`` driver is retained as the reference oracle; the
batched path is bitwise equal to it, prediction for prediction (the plan
phase lax.maps the *identical* per-query dense math, and decide_count
lanes are chunk-composition-independent).

The same engine gives every paper baseline via two switches:
  user blocking: "cone" (SAH / H2-Cone) or "norm" (Simpfer-style blocks --
     with unit users, Simpfer's norm blocking degenerates to arbitrary
     contiguous blocks; see DESIGN.md)
  item scan: transform "sat" + scan "sketch" (SA-ALSH), transform "qnf"
     (H2-ALSH), scan "exact" (Simpfer's linear scan).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import cone as _cone
from repro.core import sa_alsh as _alsh
from repro.core import simpfer as _simpfer


class SAHIndex(NamedTuple):
    """Everything the query phase needs. Users live in cone-leaf order."""

    alsh: _alsh.SAALSHIndex          # over P \ P'
    users: jnp.ndarray               # (m_pad, d) unit users, leaf order
    user_ids: jnp.ndarray            # (m_pad,) original user row
    user_mask: jnp.ndarray           # (m_pad,) real (non-duplicate) users
    center: jnp.ndarray              # (n_blocks, d)
    omega: jnp.ndarray               # (n_blocks,)
    theta: jnp.ndarray               # (m_pad,)
    user_lb: jnp.ndarray             # (m_pad, kmax)
    block_lb: jnp.ndarray            # (n_blocks, kmax)
    top_norms: jnp.ndarray           # (n_top,) norms of P', descending
    top_items: jnp.ndarray           # (n_top, d) P' item vectors
    top_ids: jnp.ndarray             # (n_top,) original rows of P'

    @property
    def n_blocks(self) -> int:
        return self.center.shape[0]

    @property
    def kmax(self) -> int:
        return self.user_lb.shape[1]

    @property
    def n_users(self) -> int:
        return self.users.shape[0]


# ---------------------------------------------------------------------------
# Build stages (Algorithm 4 as a pipeline).
#
# ``build`` below composes four pure stage functions. engine/build.py
# composes the SAME functions with per-stage timing and optional mesh
# sharding of the row-parallel steps (SRP hashing over items, lower-bound
# rows over users); both compositions are bitwise identical by
# construction. Stage contract: DESIGN.md SS11.
# ---------------------------------------------------------------------------


class NormSplit(NamedTuple):
    """Stage 1 output: items split into P' (top n_top by norm) and the rest.

    ``order`` maps sorted position -> original item row (the argsort of
    descending norm); ``rest`` rows are positions n_top.. of that order.
    """

    order: jnp.ndarray       # (n,) sorted position -> original row
    top_items: jnp.ndarray   # (n_top, d) P' vectors, descending norm
    top_ids: jnp.ndarray     # (n_top,) int32 original rows of P'
    top_norms: jnp.ndarray   # (n_top,) f32 descending
    rest: jnp.ndarray        # (n - n_top, d) remaining items, sorted


class UserBlocking(NamedTuple):
    """Stage 3 output: users blocked into leaves (cone or norm order)."""

    users: jnp.ndarray       # (m_pad, d) unit users, leaf order
    user_ids: jnp.ndarray    # (m_pad,) int32 original user row
    user_mask: jnp.ndarray   # (m_pad,) real (non-duplicate) users
    center: jnp.ndarray      # (n_blocks, d)
    omega: jnp.ndarray       # (n_blocks,)
    theta: jnp.ndarray       # (m_pad,)


def build_keys(key: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(k_idx, k_cone): the per-stage keys every build path must derive
    identically -- part of the fingerprint-stability contract."""
    k_idx, k_cone = jax.random.split(jax.random.fold_in(key, 0))
    return k_idx, k_cone


def split_items_by_norm(items: jnp.ndarray, n_top: int) -> NormSplit:
    """Stage 1: descending-norm sort + top-``n_top`` split (P' vs rest)."""
    norms = jnp.linalg.norm(items, axis=-1)
    order = jnp.argsort(-norms)
    items_sorted = items[order]
    return NormSplit(order=order,
                     top_items=items_sorted[:n_top],
                     top_ids=order[:n_top].astype(jnp.int32),
                     top_norms=norms[order][:n_top],
                     rest=items_sorted[n_top:])


def shift_item_ids(alsh: _alsh.SAALSHIndex, order: jnp.ndarray,
                   n_top: int) -> _alsh.SAALSHIndex:
    """Stage 2 epilogue: alsh.item_ids index ``rest``; shift them back to
    original item rows (padding stays -1)."""
    return alsh._replace(item_ids=jnp.where(
        alsh.item_ids >= 0,
        jnp.take(order.astype(jnp.int32),
                 jnp.clip(alsh.item_ids, 0, None) + n_top),
        -1))


def block_users(users: jnp.ndarray, key: jax.Array, *, leaf_size: int = 32,
                blocking: str = "cone") -> UserBlocking:
    """Stage 3: unit-normalize users and block them (cone tree or
    Simpfer-style contiguous "norm" chunks)."""
    unorm = jnp.linalg.norm(users, axis=-1, keepdims=True)
    users_unit = users / jnp.maximum(unorm, 1e-12)

    if blocking == "cone":
        blocks, padded, mask = _cone.build_cone_blocks(users_unit, key,
                                                       leaf_size)
    elif blocking == "norm":
        blocks, padded, mask = _cone.norm_blocks(users_unit, leaf_size)
    else:
        raise ValueError(f"unknown blocking {blocking!r}")

    perm = blocks.perm
    m = users.shape[0]
    return UserBlocking(users=padded[perm],
                        user_ids=(perm % m).astype(jnp.int32),
                        user_mask=mask[perm],
                        center=blocks.center, omega=blocks.omega,
                        theta=blocks.theta)


def lower_bounds(users_leaf: jnp.ndarray, user_mask: jnp.ndarray,
                 top_items: jnp.ndarray, k_max: int, n_blocks: int, *,
                 lb_rows=None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Stage 4: Simpfer per-user and per-block lower bounds over P'.

    lb_rows(users, top_items, k_max) -> (m, k_max) overrides the
    lower-bound computation; the staged pipeline passes a user-sharded
    version of ``simpfer.user_lower_bounds_impl`` here (each row is
    independent, so any row slicing is bitwise equal)."""
    lb_fn = lb_rows or _simpfer.user_lower_bounds
    lb = lb_fn(users_leaf, top_items, k_max)
    block_lb = _simpfer.block_lower_bounds(
        jnp.where(user_mask[:, None], lb, jnp.inf), n_blocks)
    # All-padding blocks (impossible with cyclic padding, but be safe):
    block_lb = jnp.where(jnp.isfinite(block_lb), block_lb, -jnp.inf)
    return lb, block_lb


def build(items: jnp.ndarray, users: jnp.ndarray, key: jax.Array, *,
          k_max: int = 50, n_top: int | None = None, leaf_size: int = 32,
          b: float = 0.5, n_bits: int = 128, tile: int = 512,
          max_partitions: int = 64, transform: str = "sat",
          blocking: str = "cone") -> SAHIndex:
    """Build the SAH index (Algorithm 4). items (n,d), users (m,d).

    Single-device composition of the build stages; engine/build.py runs
    the same stages with timing and optional mesh sharding.
    """
    if n_top is None:
        n_top = 2 * k_max
    k_idx, k_cone = build_keys(key)

    split = split_items_by_norm(items, n_top)
    alsh = _alsh.build_index(split.rest, k_idx, b=b, n_bits=n_bits,
                             tile=tile, max_partitions=max_partitions,
                             transform=transform)
    alsh = shift_item_ids(alsh, split.order, n_top)

    ub = block_users(users, k_cone, leaf_size=leaf_size, blocking=blocking)

    lb, block_lb = lower_bounds(ub.users, ub.user_mask, split.top_items,
                                k_max, ub.center.shape[0])

    return SAHIndex(alsh=alsh, users=ub.users, user_ids=ub.user_ids,
                    user_mask=ub.user_mask, center=ub.center, omega=ub.omega,
                    theta=ub.theta, user_lb=lb, block_lb=block_lb,
                    top_norms=split.top_norms, top_items=split.top_items,
                    top_ids=split.top_ids)


class QueryStats(NamedTuple):
    """Per-query pruning counters: scalars from ``rkmips``, (nq,) rows from
    the batch drivers. The first five are exact and layout-independent
    (bitwise equal across per-query / batched / sharded execution);
    tiles_scanned and chunks are diagnostics of how the work happened to be
    chunked — in the batched driver a mixed-query chunk's tile visits are
    charged to every query with an active lane in it (DESIGN.md SS9)."""

    blocks_alive: jnp.ndarray    # after Lemma 2
    users_alive: jnp.ndarray     # after Lemma 3
    n_no_lb: jnp.ndarray         # decided no by tau < L[k-1]
    n_yes_norm: jnp.ndarray      # decided yes by tau >= ||p_k||
    n_scan: jnp.ndarray          # users that needed the item scan
    tiles_scanned: jnp.ndarray   # total tile-visits across chunks
    chunks: jnp.ndarray
    truncated: jnp.ndarray       # 1 iff a scan budget skipped lanes


def _plan_one(index: SAHIndex, q: jnp.ndarray, k: int, tie_eps: float,
              delta_ip: jnp.ndarray | None = None,
              delta_mask: jnp.ndarray | None = None,
              delta_screen=None):
    """Lemmas 2-3 + dense tau + the O(1) decisions for ONE query.

    Shared verbatim by the per-query reference driver (``rkmips_impl``) and
    the batched planner (``rkmips_plan_impl`` lax.maps it), which is what
    makes the two paths bitwise equal: every dense product is the same
    matvec, every bound the same elementwise expression.

    delta_ip (m_pad, cap) / delta_mask (cap,) carry a staged-insert delta
    buffer (engine/artifact.py): live staged rows are exactly counted into
    every lane's initial count with the same strict ``> tau + eps`` rule as
    the main scan. ``delta_ip`` is query-independent (<u, p> only), so the
    callers compute it once per dispatch, outside any per-query map. The
    caller must hand an index view whose ``top_norms`` covers the staged
    rows (the "yes by norm" shortcut would otherwise fire against a stale,
    too-small k-th norm).

    delta_screen (delta_items, qips, qerr) replaces the exact delta_ip with
    the int8 screen (``sa_alsh.delta_screen_tables``): lanes whose
    quantized inner product clears the threshold by more than the sound
    error radius count without any f32 work, lanes that miss it by more
    than the radius are skipped, and only the thin in-band remainder falls
    back to the exact GEMM — the identical ``users @ delta_items.T``
    expression, under a ``lax.cond`` so the zero-band case pays nothing.
    Counts (hence predictions) stay bitwise equal to the f32 path; only
    who computes them changes (the SS13 over-admission argument, applied
    to the strict-count comparison instead of a top-k band).

    Returns (tau, count0, pred0, undecided, eps, block_alive, user_alive,
    no_lb, yes_norm), all in cone-leaf order.
    """
    m_pad = index.n_users
    leaf = m_pad // index.n_blocks
    qn = jnp.linalg.norm(q)
    eps = tie_eps * qn
    # f32 slack: the cone bounds go through arccos/cos roundtrips whose
    # relative error is ~1e-4; without slack a mathematically-tight bound
    # can flip a pruning decision (caught by the property tests).
    slack = 2e-4 * qn + eps

    # --- Lemma 2: block-level pruning -------------------------------------
    node_ub, phi = _cone.node_upper_bound(q, _cone.ConeBlocks(
        perm=jnp.arange(m_pad, dtype=jnp.int32), center=index.center,
        omega=index.omega, theta=index.theta))
    block_alive = node_ub >= index.block_lb[:, k - 1] - slack
    # --- Lemma 3: vector-level pruning ------------------------------------
    phi_u = jnp.repeat(phi, leaf)
    vec_ub = qn * jnp.cos(jnp.abs(phi_u - index.theta))
    user_alive = (index.user_mask & jnp.repeat(block_alive, leaf)
                  & (vec_ub >= index.user_lb[:, k - 1] - slack))

    # --- exact tau + O(1) decisions ---------------------------------------
    tau = index.users @ q
    no_lb = index.user_lb[:, k - 1] > tau + eps
    yes_norm = tau >= index.top_norms[k - 1]
    undecided = user_alive & ~no_lb & ~yes_norm
    count0 = _simpfer.init_count(index.user_lb, tau + eps)
    if delta_screen is not None:
        d_items, qips, qerr = delta_screen
        thr = (tau + eps)[:, None]
        live = delta_mask[None, :]
        sure = live & (qips - qerr > thr)
        band = live & ~sure & (qips + qerr > thr)

        def exact_band():
            dip = index.users @ d_items.T
            return jnp.sum(band & (dip > thr), axis=-1).astype(jnp.int32)

        band_n = jax.lax.cond(
            jnp.any(band), exact_band,
            lambda: jnp.zeros((m_pad,), jnp.int32))
        count0 = count0 + jnp.sum(sure, axis=-1).astype(jnp.int32) + band_n
    elif delta_ip is not None:
        count0 = count0 + jnp.sum(
            delta_mask[None, :] & (delta_ip > (tau + eps)[:, None]),
            axis=-1).astype(jnp.int32)
    pred0 = yes_norm & index.user_mask
    return (tau, count0, pred0, undecided, eps, block_alive, user_alive,
            no_lb, yes_norm)


def rkmips_impl(index: SAHIndex, q: jnp.ndarray, k: int, *, n_cand: int = 64,
                scan: str = "sketch", chunk: int = 256,
                tie_eps: float = 0.0, scan_precision: str = "f32",
                delta_items: jnp.ndarray | None = None,
                delta_mask: jnp.ndarray | None = None,
                delta_qitems: jnp.ndarray | None = None,
                delta_qscale: jnp.ndarray | None = None):
    """Algorithm 5 for one query, undecorated: the per-query REFERENCE
    driver. Returns (pred (m_pad,), QueryStats).

    pred is in cone-leaf order; use predictions_to_original() to map back.
    tie_eps: relative tie tolerance, must match the oracle (core/exact.py).
    delta_items (cap, d) / delta_mask (cap,): optional staged-insert buffer
    counted exactly into every lane (see ``_plan_one``; the engine's
    artifact lifecycle is the caller). delta_qitems/delta_qscale: the
    buffer's persisted int8 twin — consumed (as the SS13 screen) only when
    ``scan_precision == "int8"``, ignored otherwise, and never changes the
    counts either way. Call ``rkmips`` (the jitted alias)
    directly. Production batches go through the plan/execute pipeline
    (``rkmips_batch``), which is bitwise equal to this driver query for
    query; this one survives as the oracle the batched path's equivalence
    tests compare against.
    """
    m_pad = index.n_users
    chunk = min(chunk, m_pad)
    if scan_precision != "int8":
        delta_qitems = delta_qscale = None
    delta_ip = None
    delta_screen = None
    if delta_items is not None and delta_qitems is not None:
        qips, qerr = _alsh.delta_screen_tables(index.users, delta_qitems,
                                               delta_qscale)
        delta_screen = (delta_items, qips, qerr)
    elif delta_items is not None:
        delta_ip = index.users @ delta_items.T
    (tau, count0, pred0, undecided, eps, block_alive, user_alive,
     no_lb, yes_norm) = _plan_one(index, q, k, tie_eps, delta_ip,
                                  delta_mask, delta_screen)

    # --- compact survivors (cone order preserved) and scan in chunks ------
    und_ids = jnp.argsort(~undecided)                     # undecided first
    n_und = jnp.sum(undecided)

    def cond(state):
        ci, _, _ = state
        return (ci * chunk) < n_und

    def body(state):
        ci, pred, tiles = state
        # Clamp the slice start exactly as dynamic_slice would, so `active`
        # flags the lanes actually fetched: an unclamped position mask
        # would silently skip the tail lanes of an almost-all-undecided
        # queue whose length is not a chunk multiple (the final slice
        # re-covers a few already-decided lanes instead — idempotent).
        start = jnp.minimum(ci * chunk, m_pad - chunk)
        ids = jax.lax.dynamic_slice(und_ids, (start,), (chunk,))
        active = (start + jnp.arange(chunk)) < n_und
        users_c = jnp.take(index.users, ids, axis=0)
        taus_c = jnp.take(tau, ids)
        counts_c = jnp.take(count0, ids)
        is_yes, t_vis = _alsh.decide_count_impl(
            index.alsh, users_c, taus_c, counts_c, active, k,
            n_cand=n_cand, scan=scan, eps=eps,
            scan_precision=scan_precision)
        pred = pred.at[ids].set(jnp.where(active, is_yes, pred[ids]))
        return ci + 1, pred, tiles + t_vis

    n_chunks, pred, tiles = jax.lax.while_loop(
        cond, body, (jnp.asarray(0, jnp.int32), pred0,
                     jnp.asarray(0, jnp.int32)))

    stats = QueryStats(
        blocks_alive=jnp.sum(block_alive),
        users_alive=jnp.sum(user_alive),
        n_no_lb=jnp.sum(no_lb & index.user_mask),
        n_yes_norm=jnp.sum(yes_norm & index.user_mask),
        n_scan=n_und,
        tiles_scanned=tiles,
        chunks=n_chunks,
        truncated=jnp.asarray(0, jnp.int32),
    )
    return pred, stats


rkmips = functools.partial(
    jax.jit, static_argnames=("k", "n_cand", "scan", "chunk", "tie_eps",
                              "scan_precision"),
)(rkmips_impl)


class RkMIPSPlan(NamedTuple):
    """Phase-1 output of the batched plan/execute pipeline (DESIGN.md SS9).

    Everything phase 2 needs to drive the flat work queue, plus the
    per-query pruning counters (already final at plan time -- the execute
    phase only adds the tile/chunk diagnostics).

    Attributes:
      tau:     (nq, m_pad) f32 dense <u, q>.
      count0:  (nq, m_pad) int32 items already known to beat tau (P').
      pred0:   (nq, m_pad) bool O(1) "yes" decisions (tau >= ||p_k||).
      queue:   (nq * m_pad,) int32 flat (query, user) ids into the
               row-major (nq, m_pad) grid, undecided lanes first --
               query-major, cone-leaf order preserved within each query
               (the stable compaction sort keeps chunk locality).
      n_work:  () int32 number of undecided lanes (queue[:n_work] is work).
      eps:     (nq,) f32 per-query absolute tie tolerance.
      blocks_alive / users_alive / n_no_lb / n_yes_norm / n_scan:
               (nq,) int32 per-query pruning counters (QueryStats fields).
    """

    tau: jnp.ndarray
    count0: jnp.ndarray
    pred0: jnp.ndarray
    queue: jnp.ndarray
    n_work: jnp.ndarray
    eps: jnp.ndarray
    blocks_alive: jnp.ndarray
    users_alive: jnp.ndarray
    n_no_lb: jnp.ndarray
    n_yes_norm: jnp.ndarray
    n_scan: jnp.ndarray


@jax.named_scope("sah.plan")
def rkmips_plan_impl(index: SAHIndex, queries: jnp.ndarray, k: int, *,
                     tie_eps: float = 0.0,
                     delta_items: jnp.ndarray | None = None,
                     delta_mask: jnp.ndarray | None = None,
                     delta_qitems: jnp.ndarray | None = None,
                     delta_qscale: jnp.ndarray | None = None) -> RkMIPSPlan:
    """Phase 1 (plan): Lemmas 2-3, dense tau, O(1) decisions for the whole
    (nq, m_pad) grid, then compaction into one flat cross-query work queue.

    The per-query dense math runs under ``lax.map`` of the same
    ``_plan_one`` body the reference driver uses: one trace regardless of
    nq, and each query's floats are the *identical* matvec/bound ops --
    which is what keeps the batched path bitwise equal to the per-query
    oracle (a (nq, m) GEMM would round differently than nq matvecs).
    The queue stores flat int32 ids, so a batch is limited to
    nq * m_pad < 2**31 lanes (checked: both are static shapes).

    delta_items/delta_mask: optional staged-insert buffer; its (m_pad, cap)
    inner products are query-independent, so they are computed ONCE here —
    outside the per-query lax.map — and every query's plan reads the same
    values the per-query reference driver computes (bitwise).

    delta_qitems/delta_qscale: the buffer's persisted int8 twin. When
    present, the query-independent screen tables
    (``sa_alsh.delta_screen_tables``) replace the exact delta GEMM, and
    each query's plan falls back to f32 only for its in-band lanes (see
    ``_plan_one``) — counts stay bitwise equal. The batch driver forwards
    them only under ``scan_precision == "int8"``.
    """
    if queries.shape[0] * index.n_users >= 2 ** 31:
        raise ValueError(
            f"batch too large for the int32 flat work queue: nq * m_pad = "
            f"{queries.shape[0]} * {index.n_users} >= 2**31; split the "
            f"query batch")
    delta_ip = None
    delta_screen = None
    if delta_items is not None and delta_qitems is not None:
        qips, qerr = _alsh.delta_screen_tables(index.users, delta_qitems,
                                               delta_qscale)
        delta_screen = (delta_items, qips, qerr)
    elif delta_items is not None:
        delta_ip = index.users @ delta_items.T

    def one(q):
        (tau, count0, pred0, undecided, eps, block_alive, user_alive,
         no_lb, yes_norm) = _plan_one(index, q, k, tie_eps, delta_ip,
                                      delta_mask, delta_screen)
        return (tau, count0, pred0, undecided, eps,
                jnp.sum(block_alive), jnp.sum(user_alive),
                jnp.sum(no_lb & index.user_mask),
                jnp.sum(yes_norm & index.user_mask),
                jnp.sum(undecided))

    (tau, count0, pred0, undecided, eps, blocks_alive, users_alive,
     n_no_lb, n_yes_norm, n_scan) = jax.lax.map(one, queries)

    # Stable flat compaction: undecided lanes first, original (query-major,
    # cone-leaf) order preserved among them.
    queue = jnp.argsort(~undecided.reshape(-1)).astype(jnp.int32)
    n_work = jnp.sum(undecided)
    return RkMIPSPlan(tau=tau, count0=count0, pred0=pred0, queue=queue,
                      n_work=n_work, eps=eps, blocks_alive=blocks_alive,
                      users_alive=users_alive, n_no_lb=n_no_lb,
                      n_yes_norm=n_yes_norm, n_scan=n_scan)


rkmips_plan = functools.partial(
    jax.jit, static_argnames=("k", "tie_eps"))(rkmips_plan_impl)


@jax.named_scope("sah.execute")
def rkmips_execute_impl(index: SAHIndex, plan: RkMIPSPlan, k: int, *,
                        n_cand: int = 64, scan: str = "sketch",
                        chunk: int = 256, scan_precision: str = "f32",
                        scan_budget=0):
    """Phase 2 (execute): ONE while_loop over fixed-size, possibly
    mixed-query chunks of the flat work queue. Returns
    (pred (nq, m_pad) bool, QueryStats with (nq,) counters).

    Each lane looks up its own user row, tau, init count and per-query eps
    (lane i of the queue belongs to query ``queue[i] // m_pad``), so
    ``decide_count`` needs no per-chunk query context and lanes from a
    fast query never idle next to a slow query's lanes. Lane decisions are
    chunk-composition-independent, so predictions are bitwise equal to the
    per-query driver however the queue happens to be packed.

    Per-query ``tiles_scanned`` / ``chunks`` are recovered by segment
    accumulation keyed on each lane's query id: a chunk's tile count is
    charged to every query with an active lane in it. For nq == 1 this
    reproduces the per-query driver's numbers exactly; for mixed-query
    chunks they are packing diagnostics (tile visits are shared by
    co-resident lanes), unlike the plan-time counters, which are exact.

    ``scan_budget`` (a TRACED int32 scalar — different budget values share
    one executable) is the execution-only per-query cap that bounds
    adversarial queries (DESIGN.md SS15): once a query's charged
    tile-visits reach the budget, its remaining lanes are masked out of
    every later chunk — they keep their conservative plan-time decision
    (``pred0``, i.e. "not in the audience") and the query's ``truncated``
    stat is set, never silently wrong. The check runs between chunks, so a
    query may overshoot its budget by at most one chunk's tile walk; lanes
    already decided stay decided, and co-batched queries that are still
    under budget keep scanning (one pathological query can no longer force
    the deep tile walks of every chunk it rides in). ``scan_budget <= 0``
    disables the cap: that path is bitwise identical to the pre-budget
    pipeline, and any query the budget never bites keeps bitwise-identical
    predictions under either setting.
    """
    nq, m_pad = plan.tau.shape
    chunk = min(chunk, nq * m_pad)
    tau_f = plan.tau.reshape(-1)
    count_f = plan.count0.reshape(-1)
    budget = jnp.asarray(scan_budget, jnp.int32)

    def cond(state):
        ci, _, _, _, _ = state
        return (ci * chunk) < plan.n_work

    def body(state):
        ci, pred, tiles_q, chunks_q, trunc_q = state
        # Clamped start, for the same almost-full-queue tail case as the
        # per-query driver (see rkmips_impl).
        start = jnp.minimum(ci * chunk, nq * m_pad - chunk)
        ids = jax.lax.dynamic_slice(plan.queue, (start,), (chunk,))
        in_work = (start + jnp.arange(chunk)) < plan.n_work
        qid = ids // m_pad
        # Budget gate: lanes of an exhausted query leave the chunk before
        # the scan, so they stop forcing tile depth on their neighbours.
        over = (budget > 0) & (jnp.take(tiles_q, qid) >= budget)
        active = in_work & ~over
        users_c = jnp.take(index.users, ids % m_pad, axis=0)
        taus_c = jnp.take(tau_f, ids)
        counts_c = jnp.take(count_f, ids)
        eps_c = jnp.take(plan.eps, qid)
        is_yes, t_vis = _alsh.decide_count_impl(
            index.alsh, users_c, taus_c, counts_c, active, k,
            n_cand=n_cand, scan=scan, eps=eps_c,
            scan_precision=scan_precision)
        pred = pred.at[ids].set(jnp.where(active, is_yes, pred[ids]))
        present = jnp.zeros((nq,), bool).at[qid].max(active)
        tiles_q = tiles_q + jnp.where(present, t_vis, 0)
        chunks_q = chunks_q + present.astype(jnp.int32)
        trunc_q = trunc_q.at[qid].max(in_work & over)
        return ci + 1, pred, tiles_q, chunks_q, trunc_q

    zeros_q = jnp.zeros((nq,), jnp.int32)
    _, pred, tiles_q, chunks_q, trunc_q = jax.lax.while_loop(
        cond, body, (jnp.asarray(0, jnp.int32), plan.pred0.reshape(-1),
                     zeros_q, zeros_q, jnp.zeros((nq,), bool)))

    stats = QueryStats(
        blocks_alive=plan.blocks_alive,
        users_alive=plan.users_alive,
        n_no_lb=plan.n_no_lb,
        n_yes_norm=plan.n_yes_norm,
        n_scan=plan.n_scan,
        tiles_scanned=tiles_q,
        chunks=chunks_q,
        truncated=trunc_q.astype(jnp.int32),
    )
    return pred.reshape(nq, m_pad), stats


rkmips_execute = functools.partial(
    jax.jit, static_argnames=("k", "n_cand", "scan", "chunk",
                              "scan_precision"),
)(rkmips_execute_impl)


def rkmips_batch_impl(index: SAHIndex, queries: jnp.ndarray, k: int, *,
                      n_cand: int = 64, scan: str = "sketch",
                      chunk: int = 256, tie_eps: float = 0.0,
                      scan_precision: str = "f32",
                      delta_items: jnp.ndarray | None = None,
                      delta_mask: jnp.ndarray | None = None,
                      delta_qitems: jnp.ndarray | None = None,
                      delta_qscale: jnp.ndarray | None = None,
                      scan_budget=0):
    """Batched Algorithm 5, undecorated: plan + execute (DESIGN.md SS9).

    (nq, d) queries -> (pred (nq, m_pad), QueryStats with (nq,) counters).
    Bitwise equal to stacking per-query ``rkmips`` calls (predictions and
    the plan-time counters; tiles/chunks are packing diagnostics). An
    optional staged-insert delta buffer (delta_items/delta_mask, see
    ``_plan_one``) threads through the plan; its static capacity keeps the
    trace count flat however often the corpus churns, and under
    ``scan_precision == "int8"`` its persisted quantized twin
    (delta_qitems/delta_qscale) turns the delta counting into the SS13
    screen (bitwise-equal counts, f32 only for in-band lanes).
    ``scan_budget`` is the traced execution-only per-query tile cap (see
    ``rkmips_execute_impl``; 0 = uncapped). Call ``rkmips_batch``
    (the jitted alias) directly; the impl exists so
    ``repro.engine.sharding`` can trace the raw body under ``shard_map`` --
    one flat while_loop, no nested jit and no scan-of-while, which is what
    retires the per-query unroll workaround (the plan's lax.map
    contains only dense per-query math and is shard_map-safe).
    """
    if scan_precision != "int8":
        delta_qitems = delta_qscale = None
    plan = rkmips_plan_impl(index, queries, k, tie_eps=tie_eps,
                            delta_items=delta_items, delta_mask=delta_mask,
                            delta_qitems=delta_qitems,
                            delta_qscale=delta_qscale)
    return rkmips_execute_impl(index, plan, k, n_cand=n_cand, scan=scan,
                               chunk=chunk, scan_precision=scan_precision,
                               scan_budget=scan_budget)


@functools.partial(
    jax.jit, static_argnames=("k", "n_cand", "scan", "chunk", "tie_eps",
                              "scan_precision"))
def rkmips_batch(index: SAHIndex, queries: jnp.ndarray, k: int, *,
                 n_cand: int = 64, scan: str = "sketch", chunk: int = 256,
                 tie_eps: float = 0.0, scan_precision: str = "f32",
                 delta_items: jnp.ndarray | None = None,
                 delta_mask: jnp.ndarray | None = None,
                 delta_qitems: jnp.ndarray | None = None,
                 delta_qscale: jnp.ndarray | None = None,
                 scan_budget=0):
    """Jitted batched Algorithm 5 — see ``rkmips_batch_impl``. (A wrapper
    rather than a jit alias so the impl binds late: the compile-count tests
    wrap it to prove one body invocation per trace. ``scan_budget`` is
    deliberately traced, not static: per-tenant budgets share one
    executable.)"""
    return rkmips_batch_impl(index, queries, k, n_cand=n_cand, scan=scan,
                             chunk=chunk, tie_eps=tie_eps,
                             scan_precision=scan_precision,
                             delta_items=delta_items, delta_mask=delta_mask,
                             delta_qitems=delta_qitems,
                             delta_qscale=delta_qscale,
                             scan_budget=scan_budget)


def rkmips_batch_mapped(index: SAHIndex, queries: jnp.ndarray, k: int, *,
                        n_cand: int = 64, scan: str = "sketch",
                        chunk: int = 256, tie_eps: float = 0.0,
                        scan_precision: str = "f32",
                        delta_items: jnp.ndarray | None = None,
                        delta_mask: jnp.ndarray | None = None,
                        delta_qitems: jnp.ndarray | None = None,
                        delta_qscale: jnp.ndarray | None = None):
    """The legacy batch driver: ``lax.map`` of independent per-query
    ``rkmips`` while-loops. Superseded by the flat-queue ``rkmips_batch``
    (a fast query's lanes no longer pad out their own chunk grid while a
    slow query scans); retained as the second reference for equivalence
    tests and as the baseline ``benchmarks/bench_rkmips.py`` reports
    batched-vs-mapped wall time against. Always unbudgeted (it is the
    oracle the budget's conservative truncation is judged against)."""
    fn = functools.partial(rkmips, index, k=k, n_cand=n_cand, scan=scan,
                           chunk=chunk, tie_eps=tie_eps,
                           scan_precision=scan_precision,
                           delta_items=delta_items, delta_mask=delta_mask,
                           delta_qitems=delta_qitems,
                           delta_qscale=delta_qscale)
    return jax.lax.map(lambda q: fn(q), queries)


def predictions_to_original(index: SAHIndex, pred: jnp.ndarray,
                            n_users: int) -> jnp.ndarray:
    """Map leaf-order predictions (..., m_pad) back to original rows (..., m).

    Every padding convention in the stack (SS2 cyclic user padding; the
    sharding-time dead duplicate leaves of ``engine/sharding.py::pad_index``)
    must keep this mapping exact: padded rows are masked (``user_mask`` is
    False) so they can never set an original row, and the scatter drops any
    id outside [0, n_users) outright — a phantom id (e.g. a -1 sentinel)
    cannot silently clamp onto a real user.
    """
    masked = (pred & index.user_mask).astype(jnp.int32)
    out = jnp.zeros(pred.shape[:-1] + (n_users,), jnp.int32)
    out = out.at[..., index.user_ids].max(masked, mode="drop")
    return out > 0
