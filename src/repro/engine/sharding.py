"""Mesh-sharded execution paths for the RkMIPS engine (DESIGN.md SS7-SS8).

The engine's two heavy loops shard cleanly because both are embarrassingly
parallel along one axis:

  * RkMIPS (Algorithm 5) is independent **per user**: the dense tau matvec,
    the Lemma 2/3 bounds and the counting scan of a user lane never look at
    another lane. So the user side of the ``SAHIndex`` (leaf-ordered users,
    angles, lower bounds, cone blocks) is row-sharded over every mesh axis,
    the item side (SA-ALSH index, top-norm prefix) is replicated, and each
    shard runs the stock batched plan/execute pipeline
    (``core/sah.py::rkmips_batch_impl``, DESIGN.md SS9) on its slice of the
    user rows for the WHOLE query batch at once; one tiled all-gather
    reassembles the (nq, m_pad) prediction grid and a psum merges the
    counters. The body is a single flat while_loop over the shard-local
    cross-query work queue -- no nested jit, no scan-of-while, no Python
    loop over queries -- so it traces exactly once per batch shape at any
    batch size (pinned by the compile-count test) and is safe under
    ``shard_map`` where the old per-query drivers (nested jit / lax.map)
    miscompiled (DESIGN.md SS7). Predictions are bitwise identical to the
    unsharded run (asserted in tests/test_engine.py): queue compaction
    regroups lanes but each lane's decision is self-contained.

  * kMIPS shards along **items**, reusing the proven pattern of
    ``launch/serve.py::sah_retrieve_step``: each shard Hamming-scans its code
    slice, re-ranks its local top-``n_cand`` exactly, keeps a local top-k,
    and one tiny all-gather + final top-k merges the winners — wire bytes
    per query are O(shards * k), independent of the item count. The sharded
    scan is single-pass (no tile early-exit; latency on a mesh is bounded by
    the slowest shard, so the bound check buys nothing).

Any user/item count shards over any mesh: when a count does not divide the
device count, the arrays are padded up to the next multiple with **dead**
rows before layout — cone blocks by cyclically duplicated leaves whose
``user_mask`` is False and whose block lower bound is +inf (so Lemma 2 kills
them before any work happens; the same convention as the SS2 cyclic user
padding), item rows by masked rows whose scores are forced to ``-inf``.
Results are bitwise equal to the unsharded path after mask stripping
(``predictions_to_original`` / the ``item_mask``), and the per-user /
per-block counters in ``QueryStats`` are unchanged because dead padding
never prunes, scans, or counts.

Sharding enters only via ``ShardingPolicy`` (DESIGN.md SS5): ``mesh=None``
routes every entry point to the identical single-device computation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import sa_alsh as _alsh
from repro.core import sah as _sah
from repro.dist.policy import ShardingPolicy
from repro.kernels import ops as kops

_BIG_HAMMING = jnp.int32(1 << 30)
_NEG = -jnp.inf

# SAHIndex fields whose leading axis is the (padded, leaf-ordered) user axis
# or the cone-block axis; everything else (the SA-ALSH item index, the
# top-norm prefix) is replicated.
_USER_AXIS_FIELDS = ("users", "user_ids", "user_mask", "theta", "user_lb")
_BLOCK_AXIS_FIELDS = ("center", "omega", "block_lb")


def n_shards(policy: ShardingPolicy) -> int:
    """Total device count of the policy's mesh (1 without a mesh)."""
    return policy.device_count


def pad_index(index: _sah.SAHIndex, shards: int) -> _sah.SAHIndex:
    """Pad the cone-block axis to a multiple of ``shards`` with dead leaves.

    Padding leaves are cyclic duplicates of real leaves (valid unit vectors,
    so every bound and matvec stays finite — the SS2 convention), except:
    ``user_mask`` is False on every padded row and ``block_lb`` is +inf on
    every padded block, so Lemma 2 prunes the block before any per-user work
    and no counter, prediction, or scan ever sees the duplicates. The result
    is query-for-query bitwise equal to the unpadded index after mask
    stripping. No-op when ``n_blocks`` already divides.
    """
    nb = index.n_blocks
    nb_pad = -(-nb // shards) * shards
    if nb_pad == nb:
        return index
    leaf = index.n_users // nb
    pad_blocks = (jnp.arange(nb, nb_pad, dtype=jnp.int32)) % nb
    pad_rows = (pad_blocks[:, None] * leaf
                + jnp.arange(leaf, dtype=jnp.int32)[None, :]).reshape(-1)

    def dup(x, rows):
        return jnp.concatenate([x, jnp.take(x, rows, axis=0)], axis=0)

    return index._replace(
        users=dup(index.users, pad_rows),
        user_ids=dup(index.user_ids, pad_rows),
        user_mask=jnp.concatenate(
            [index.user_mask, jnp.zeros((pad_rows.shape[0],), bool)]),
        theta=dup(index.theta, pad_rows),
        user_lb=dup(index.user_lb, pad_rows),
        center=dup(index.center, pad_blocks),
        omega=dup(index.omega, pad_blocks),
        block_lb=jnp.concatenate(
            [index.block_lb,
             jnp.full((nb_pad - nb, index.kmax), jnp.inf,
                      index.block_lb.dtype)]),
    )


def pad_item_rows(items: jnp.ndarray, item_ids: jnp.ndarray,
                  item_mask: jnp.ndarray, codes: jnp.ndarray,
                  shards: int, k: int = 1):
    """Pad item-axis arrays so every shard holds >= k rows and rows divide.

    Padding rows are dead: zero vectors, ``item_ids == -1``, mask False,
    zero codes — the scans force their scores to ``-inf`` (or their Hamming
    distance to +BIG), so they can never enter a top-k that a real row could
    occupy. No-op when the row count already divides and covers ``k``.
    """
    n = items.shape[0]
    rows_per = max(-(-n // shards), k)
    n_pad = rows_per * shards
    if n_pad == n:
        return items, item_ids, item_mask, codes
    pad = n_pad - n
    return (jnp.concatenate([items, jnp.zeros((pad,) + items.shape[1:],
                                              items.dtype)]),
            jnp.concatenate([item_ids,
                             jnp.full((pad,), -1, item_ids.dtype)]),
            jnp.concatenate([item_mask, jnp.zeros((pad,), bool)]),
            jnp.concatenate([codes, jnp.zeros((pad,) + codes.shape[1:],
                                              codes.dtype)]))


def index_specs(index: _sah.SAHIndex, policy: ShardingPolicy):
    """PartitionSpec pytree for a SAHIndex: user/block rows over every mesh
    axis, item side replicated. The index must already be padded to a
    block count that divides the mesh (``pad_index``)."""
    axes = tuple(policy.mesh.axis_names)
    specs = jax.tree.map(lambda _: P(), index)
    row = {f: P(axes, *([None] * (getattr(index, f).ndim - 1)))
           for f in _USER_AXIS_FIELDS + _BLOCK_AXIS_FIELDS}
    return specs._replace(**row)


def shard_index(index: _sah.SAHIndex, policy: ShardingPolicy
                ) -> _sah.SAHIndex:
    """Lay the index out for the mesh: user/block rows sharded, rest
    replicated. Pads the block axis first when it does not divide the
    device count (``pad_index``). No-op without a mesh."""
    if policy.mesh is None:
        return index
    index = pad_index(index, n_shards(policy))
    specs = index_specs(index, policy)
    shardings = jax.tree.map(lambda s: NamedSharding(policy.mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    return jax.device_put(index, shardings)


def rkmips_batch(index: _sah.SAHIndex, queries: jnp.ndarray, k: int,
                 policy: ShardingPolicy, *, n_cand: int = 64,
                 scan: str = "sketch", chunk: int = 256,
                 tie_eps: float = 0.0, scan_precision: str = "f32",
                 delta_items: jnp.ndarray | None = None,
                 delta_mask: jnp.ndarray | None = None,
                 delta_qitems: jnp.ndarray | None = None,
                 delta_qscale: jnp.ndarray | None = None,
                 scan_budget=0):
    """Sharded Algorithm 5 over a query batch (one trace per batch shape).

    Returns (pred (nq, m_pad) bool in global leaf order, QueryStats with
    per-query counters summed over shards). m_pad reflects block padding
    when the block count does not divide the mesh; ``pad_index`` rows are
    masked, so ``predictions_to_original`` strips them. Without a mesh this
    is exactly ``core/sah.py::rkmips_batch``.

    The shard_map body is the raw batched plan/execute driver on the
    shard's user slice: the plan's lax.map holds only dense per-query math
    and the execute phase is one flat while_loop, so — unlike the retired
    per-query drivers (nested jit / scan-of-while, the miscompile of
    DESIGN.md SS7, SS9) — the body traces once at any nq. The
    shard-local work queues are what make this load-balanced: a shard
    whose users die early for one query spends its chunks on the other
    queries' survivors instead of idling.

    delta_items/delta_mask: optional staged-insert buffer (DESIGN.md SS10),
    replicated across shards — each shard counts its own user rows against
    the full buffer ((m_local, cap) products, no collective), so the psum'd
    counters and gathered predictions match the single-device delta path
    bitwise. delta_qitems/delta_qscale (the buffer's int8 twin, consumed
    under ``scan_precision == "int8"``) replicate the same way.

    scan_budget: the traced per-query tile cap (``rkmips_execute_impl``).
    On a mesh each shard enforces it against its OWN charged tile count —
    the cap bounds the slowest shard's walk, which is what bounds the
    dispatch's wall time — and the psum'd ``truncated`` stat flags a query
    any shard truncated.
    """
    budget = jnp.asarray(scan_budget, jnp.int32)
    if policy.mesh is None:
        return _sah.rkmips_batch(index, queries, k, n_cand=n_cand,
                                 scan=scan, chunk=chunk, tie_eps=tie_eps,
                                 scan_precision=scan_precision,
                                 delta_items=delta_items,
                                 delta_mask=delta_mask,
                                 delta_qitems=delta_qitems,
                                 delta_qscale=delta_qscale,
                                 scan_budget=budget)
    index = pad_index(index, n_shards(policy))
    axes = tuple(policy.mesh.axis_names)
    specs = index_specs(index, policy)
    if scan_precision != "int8":
        delta_qitems = delta_qscale = None
    has_delta = delta_items is not None
    has_qdelta = has_delta and delta_qitems is not None

    def local(idx_l: _sah.SAHIndex, qs: jnp.ndarray, bgt, *delta):
        d_items = d_mask = d_qitems = d_qscale = None
        if has_qdelta:
            d_items, d_mask, d_qitems, d_qscale = delta
        elif has_delta:
            d_items, d_mask = delta
        pred_l, stats_l = _sah.rkmips_batch_impl(
            idx_l, qs, k, n_cand=n_cand, scan=scan, chunk=chunk,
            tie_eps=tie_eps, scan_precision=scan_precision,
            delta_items=d_items, delta_mask=d_mask,
            delta_qitems=d_qitems, delta_qscale=d_qscale,
            scan_budget=bgt)
        pred = jax.lax.all_gather(pred_l, axes, axis=1, tiled=True)
        stats = jax.tree.map(lambda s: jax.lax.psum(s, axes), stats_l)
        return pred, stats

    extras = ()
    extra_specs = ()
    if has_qdelta:
        extras = (delta_items, delta_mask, delta_qitems, delta_qscale)
        extra_specs = (P(), P(), P(), P())
    elif has_delta:
        extras = (delta_items, delta_mask)
        extra_specs = (P(), P())
    return jax.shard_map(local, mesh=policy.mesh,
                         in_specs=(specs, P(), P()) + extra_specs,
                         out_specs=(P(), P()),
                         check_vma=False)(index, queries, budget, *extras)


def _flat_candidates(items, item_ids, item_mask, codes, ucodes, queries,
                     k: int, n_cand: int, scan: str):
    """One-pass scan over a row slab: sketch (Hamming top-n_cand + exact
    re-rank) or exact (dense IPs), then top-k. Returns (vals (Q, k),
    ids (Q, k) original item rows).

    The f32 work maps over queries (``lax.map``) instead of batching the
    contraction across them: XLA lowers a batched contraction differently
    at different Q, so a batched expression's per-row results drift in
    the last ulp across batch shapes — which would break the serving
    contract that a bucket-padded dispatch (any ladder rung, DESIGN.md
    SS14) is bitwise equal to the full-batch flush. The per-query body is
    shape-identical at every Q, so every executable computes identical
    rows; the N-axis work inside each step stays fully vectorized, and Q
    is a micro-batch on the serving path.

    Each stage runs under a ``jax.named_scope`` — ``kmips.scan`` (Hamming
    scores and mask), ``kmips.select`` (the top-n_cand), ``kmips.rerank``
    (gather, inner products, top-k; all of the exact scan) — so a device
    profile can time them apart (``serving.op_scopes``). Scopes are
    metadata only: the arithmetic is unchanged.
    """
    if scan == "exact":
        @jax.named_scope("kmips.rerank")
        def one_exact(q):
            ips = jnp.where(item_mask, items @ q, _NEG)
            vals, pos = jax.lax.top_k(ips, k)
            return vals, jnp.take(item_ids, pos)
        return jax.lax.map(one_exact, queries)

    def one_sketch(args):
        uc, q = args
        with jax.named_scope("kmips.scan"):
            dist = kops.hamming_scores(uc[None], codes)[0]    # (N,)
            dist = jnp.where(item_mask, dist, _BIG_HAMMING)
        with jax.named_scope("kmips.select"):
            _, cand = jax.lax.top_k(-dist, n_cand)            # (n_cand,)
        with jax.named_scope("kmips.rerank"):
            ips = jnp.take(items, cand, axis=0) @ q
            ips = jnp.where(jnp.take(item_mask, cand), ips, _NEG)
            vals, pos = jax.lax.top_k(ips, k)
            return vals, jnp.take(jnp.take(item_ids, cand), pos)
    return jax.lax.map(one_sketch, (ucodes, queries))


def kmips_flat_arrays(items: jnp.ndarray, item_ids: jnp.ndarray,
                      item_mask: jnp.ndarray, codes: jnp.ndarray,
                      ucodes: jnp.ndarray, queries: jnp.ndarray, k: int,
                      policy: ShardingPolicy, *, n_cand: int = 64,
                      scan: str = "sketch"):
    """``kmips_flat`` on raw row arrays (the serving-stack entry point).

    items (N, d), item_ids (N,) int32 original rows (-1 padding), item_mask
    (N,) bool, codes (N, W) uint32 sketches, ucodes (Q, W) query sketches,
    queries (Q, d) -> (vals (Q, k), ids (Q, k)). Any N shards over any mesh:
    rows are padded to the next multiple of the device count with dead rows
    (``pad_item_rows``) before the shard_map. Per-query results are
    independent of batching, so micro-batched serving dispatch
    (engine/serving.py) is bitwise equal to a one-shot batch.
    """
    if policy.mesh is None:
        n_c = min(max(n_cand, k), items.shape[0])
        return _flat_candidates(items, item_ids, item_mask, codes, ucodes,
                                queries, k, n_c, scan)

    items, item_ids, item_mask, codes = pad_item_rows(
        items, item_ids, item_mask, codes, n_shards(policy), k)
    axes = tuple(policy.mesh.axis_names)

    def local(items_l, ids_l, mask_l, codes_l, uc, qs):
        vals_l, gids_l = _flat_candidates(items_l, ids_l, mask_l, codes_l,
                                          uc, qs, k,
                                          min(max(n_cand, k),
                                              items_l.shape[0]), scan)
        vals_all = jax.lax.all_gather(vals_l, axes, axis=1, tiled=True)
        gids_all = jax.lax.all_gather(gids_l, axes, axis=1, tiled=True)
        best, pos = jax.lax.top_k(vals_all, k)
        return best, jnp.take_along_axis(gids_all, pos, axis=-1)

    return jax.shard_map(
        local, mesh=policy.mesh,
        in_specs=(P(axes, None), P(axes), P(axes), P(axes, None), P(), P()),
        out_specs=(P(), P()), check_vma=False,
    )(items, item_ids, item_mask, codes, ucodes, queries)


def kmips_flat(index: _alsh.SAALSHIndex, queries: jnp.ndarray, k: int,
               policy: ShardingPolicy, *, n_cand: int = 64,
               scan: str = "sketch"):
    """Single-pass kMIPS, sharded over item rows.

    queries (Q, d) -> (vals (Q, k) descending, ids (Q, k) original item
    rows). scan="sketch" Hamming-ranks then re-ranks ``n_cand`` candidates
    **per shard** (``n_cand >=`` the local row count makes it exact);
    scan="exact" skips the sketch and re-ranks every row. The mesh=None
    branch is the single-device oracle of the shard_map body (exercised by
    tests/test_engine.py); the engine's unsharded kmips uses the tiled
    early-terminating ``kmips_topk`` instead. Row counts that do not divide
    the mesh are padded with dead rows (``pad_item_rows``).
    """
    ucodes = _alsh.user_codes(index, queries)
    return kmips_flat_arrays(index.items, index.item_ids, index.item_mask,
                             index.codes, ucodes, queries, k, policy,
                             n_cand=n_cand, scan=scan)
