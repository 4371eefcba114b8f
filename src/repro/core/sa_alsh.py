"""SA-ALSH: Shifting-Aware Asymmetric LSH index and query scans.

Faithful to Algorithms 1-2 of the paper, adapted to TPU dataflow as described
in DESIGN.md SS2:

  * items are sorted by descending l2-norm and partitioned into norm ranges
    (b*M_j, M_j] (Algorithm 1 lines 3-6);
  * each partition's items are SAT-transformed with its own centroid/radius
    (lines 7-11) and hashed with SRP; codes are bit-packed uint32 sketches
    rather than hash-table buckets (Hamming ranking == collision-count
    ranking in expectation, DESIGN.md SS2);
  * the query phase walks fixed-size, norm-ordered tiles with the
    Cauchy-Schwarz bound mu = max_norm_tile * ||u|| for early termination
    (Algorithm 2 lines 3-4), selecting candidates per tile by Hamming
    distance and re-ranking them with exact inner products.

Because the user transform U(u) = [lambda*u; 0] has a zero appended coordinate
and lambda > 0, a user's SRP code is sign(u @ proj[:d]) -- one code per user,
valid against every partition's item codes. All per-partition state is baked
into the item codes at indexing time.

Two query entry points:
  kmips_topk     -- approximate top-k MIPS (paper's Algorithm 2), used for the
                    kMIPS benchmarks (Fig. 6) and standalone retrieval.
  decide_count   -- the RkMIPS decision primitive: counts items with
                    <u, p> > tau until count >= k ("no") or the norm bound
                    certifies no further item can beat tau ("yes"). This is
                    Algorithm 2 reformulated as counting, which is exactly the
                    decision Algorithm 5 needs (see core/sah.py).

Both support scan="sketch" (SA-ALSH) and scan="exact" (Simpfer's linear scan
with the same early-termination rule), which gives the paper's baselines for
free.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import partitions as _parts
from repro.core import srp as _srp
from repro.core import transforms as _tf
from repro.kernels import ops as kops

_NEG = -jnp.inf
_BIG_HAMMING = jnp.int32(1 << 30)


class SAALSHIndex(NamedTuple):
    """Index over items sorted by descending norm, padded to a tile multiple.

    Attributes:
      items:      (n_pad, d) f32, descending-norm order, zero rows for padding.
      item_ids:   (n_pad,) int32, original item row; -1 for padding.
      norms:      (n_pad,) f32, descending; 0 for padding.
      item_mask:  (n_pad,) bool.
      codes:      (n_pad, W) uint32 SRP sketch of the per-partition
                  asymmetric transform of each item.
      proj:       (d+1, B) f32 shared SRP projection (rows 0..d-1 hash the
                  shifted item / the user; row d hashes the appended coord).
      part_id:    (n_pad,) int32 partition of each item.
      part_max_norm: (T,) f32 M_j per partition (0 padding).
      part_centroid: (T, d) f32 c_j.
      part_radius:   (T,) f32 R_j.
      n_parts:    () int32.
      tile_max_norm: (n_tiles,) f32 max norm *within* each tile; because the
                  global order is norm-descending, tile t's max also bounds
                  every row of every later tile t' > t, which is what makes
                  it the scan's early-termination bound.
      qitems:     (n_pad, d) int8 per-partition symmetric quantization of
                  ``items`` (DESIGN.md SS13): row i is
                  round(items[i] / qscale[i]), zero for padding. The
                  ``scan_precision="int8"`` screen reads these instead of
                  the f32 rows (~4x less bandwidth on the scan hot path).
      qscale:     (n_pad,) f32 dequantization scale of each row -- shared
                  within a partition (max |coord| in the partition / 127),
                  stored per row so candidate gathers need no second
                  ``part_id`` indirection; 0 for padding and all-zero
                  partitions.
    """

    items: jnp.ndarray
    item_ids: jnp.ndarray
    norms: jnp.ndarray
    item_mask: jnp.ndarray
    codes: jnp.ndarray
    proj: jnp.ndarray
    part_id: jnp.ndarray
    part_max_norm: jnp.ndarray
    part_centroid: jnp.ndarray
    part_radius: jnp.ndarray
    n_parts: jnp.ndarray
    tile_max_norm: jnp.ndarray
    qitems: jnp.ndarray
    qscale: jnp.ndarray

    @property
    def tile(self) -> int:
        return self.items.shape[0] // self.tile_max_norm.shape[0]

    @property
    def dim(self) -> int:
        return self.items.shape[1]


def _pad_rows(x: jnp.ndarray, n_pad: int, fill=0):
    pad = n_pad - x.shape[0]
    if pad == 0:
        return x
    widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, widths, constant_values=fill)


def _quantize_with_scale(rows: jnp.ndarray, scale: jnp.ndarray):
    """round(rows / scale) as int8; all-zero rows (scale 0) quantize to 0."""
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(rows / safe[:, None]), -127.0, 127.0)
    return q.astype(jnp.int8)


def quantize_rows(rows: jnp.ndarray):
    """Per-row symmetric int8 quantization: ``(qrows int8, scale f32)``.

    ``scale[i] = max|rows[i]| / 127`` (0 for an all-zero row, which
    quantizes to zeros). This is the staged-delta convention
    (engine/artifact.py::insert_items): delta rows have no norm partition,
    so each carries its own scale -- the error ball
    0.5 * scale * sqrt(d) * ||u|| (see ``decide_count``) holds per row
    either way.
    """
    scale = jnp.max(jnp.abs(rows), axis=-1) / 127.0
    return _quantize_with_scale(rows, scale), scale.astype(jnp.float32)


def quantize_partitioned(rows: jnp.ndarray, part_id: jnp.ndarray,
                         max_partitions: int):
    """Per-partition symmetric int8 quantization: ``(qrows, scale)`` with
    one shared scale per norm partition (max |coord| in the partition /
    127), broadcast back to a per-row (n,) array. Coarser than per-row --
    the scan gathers one scale per candidate with no ``part_id``
    indirection, and a partition's rows stay mutually comparable in code
    space."""
    absmax = jnp.max(jnp.abs(rows), axis=-1)
    pmax = jax.ops.segment_max(absmax, part_id,
                               num_segments=max_partitions)
    pmax = jnp.where(pmax > 0, pmax, 0.0)     # empty segments hold -inf
    scale = (pmax / 127.0)[part_id]
    return _quantize_with_scale(rows, scale), scale.astype(jnp.float32)


class PreparedItems(NamedTuple):
    """Item-side build state minus the SRP codes (stage 2a of the staged
    build pipeline, DESIGN.md SS11).

    Everything here is the output of one jitted, sequential computation
    (norm sort + partition scan + asymmetric transform). What remains --
    hashing ``transformed`` row-by-row against a projection -- is
    embarrassingly row-parallel, so the staged pipeline
    (``engine/build.py``) shards exactly that step over the mesh.

    All row-shaped fields are already padded to ``n_pad`` rows; padding
    rows of ``transformed`` are zero, so their codes are the hash of the
    zero vector no matter how rows are sharded.
    """

    items: jnp.ndarray          # (n_pad, d) descending-norm order
    item_ids: jnp.ndarray       # (n_pad,) int32, -1 padding
    norms: jnp.ndarray          # (n_pad,) f32
    item_mask: jnp.ndarray      # (n_pad,) bool
    part_id: jnp.ndarray        # (n_pad,) int32
    part_max_norm: jnp.ndarray  # (T,) f32
    part_centroid: jnp.ndarray  # (T, d) f32
    part_radius: jnp.ndarray    # (T,) f32
    n_parts: jnp.ndarray        # () int32
    tile_max_norm: jnp.ndarray  # (n_tiles,) f32
    transformed: jnp.ndarray    # (n_pad, d+1) f32 rows to hash; 0 padding
    qitems: jnp.ndarray         # (n_pad, d) int8 quantized rows; 0 padding
    qscale: jnp.ndarray         # (n_pad,) f32 per-row dequant scale


@functools.partial(jax.jit,
                   static_argnames=("b", "max_partitions", "tile",
                                    "transform", "n_pad"))
def _prepare(items, *, b, max_partitions, tile, transform, n_pad):
    n, d = items.shape
    norms = jnp.linalg.norm(items, axis=-1)
    order = jnp.argsort(-norms)
    items_sorted = items[order]
    norms_sorted = norms[order]

    parts = _parts.build_partitions(items_sorted, norms_sorted, b,
                                    max_partitions)

    # Per-item asymmetric transform using its partition's centroid / scale.
    if transform == "sat":
        c = parts.centroid[parts.part_id]                 # (n, d)
        r = parts.radius[parts.part_id]                   # (n,)
        shifted = items_sorted - c
        ext2 = jnp.maximum(r ** 2 - jnp.sum(shifted * shifted, -1), 0.0)
    elif transform == "qnf":
        shifted = items_sorted
        m = parts.max_norm[parts.part_id]
        ext2 = jnp.maximum(m ** 2 - norms_sorted ** 2, 0.0)
    else:
        raise ValueError(f"unknown transform {transform!r}")
    transformed = jnp.concatenate([shifted, jnp.sqrt(ext2)[:, None]], -1)

    item_mask = _pad_rows(jnp.ones((n,), bool), n_pad)
    norms_p = _pad_rows(norms_sorted, n_pad)
    tile_max = jnp.max(norms_p.reshape(-1, tile), axis=-1)
    qitems, qscale = quantize_partitioned(items_sorted, parts.part_id,
                                          max_partitions)

    return PreparedItems(
        items=_pad_rows(items_sorted, n_pad),
        item_ids=_pad_rows(order.astype(jnp.int32), n_pad, fill=-1),
        norms=norms_p,
        item_mask=item_mask,
        part_id=_pad_rows(parts.part_id, n_pad, fill=max_partitions - 1),
        part_max_norm=parts.max_norm,
        part_centroid=parts.centroid,
        part_radius=parts.radius,
        n_parts=parts.n_parts,
        tile_max_norm=tile_max,
        transformed=_pad_rows(transformed, n_pad),
        qitems=_pad_rows(qitems, n_pad),
        qscale=_pad_rows(qscale, n_pad),
    )


def prepare_items(items: jnp.ndarray, *, b: float = 0.5,
                  max_partitions: int = 64, tile: int = 512,
                  transform: str = "sat") -> PreparedItems:
    """Stage 2a: norm-sort, partition and transform items (no hashing)."""
    n = items.shape[0]
    n_pad = -(-n // tile) * tile
    return _prepare(items, b=b, max_partitions=max_partitions, tile=tile,
                    transform=transform, n_pad=n_pad)


def assemble_index(prep: PreparedItems, codes: jnp.ndarray,
                   proj: jnp.ndarray) -> SAALSHIndex:
    """Stage 2c: combine prepared item state with its SRP codes."""
    return SAALSHIndex(
        items=prep.items,
        item_ids=prep.item_ids,
        norms=prep.norms,
        item_mask=prep.item_mask,
        codes=codes,
        proj=proj,
        part_id=prep.part_id,
        part_max_norm=prep.part_max_norm,
        part_centroid=prep.part_centroid,
        part_radius=prep.part_radius,
        n_parts=prep.n_parts,
        tile_max_norm=prep.tile_max_norm,
        qitems=prep.qitems,
        qscale=prep.qscale,
    )


def build_index(items: jnp.ndarray, key: jax.Array, *, b: float = 0.5,
                n_bits: int = 128, max_partitions: int = 64,
                tile: int = 512, transform: str = "sat",
                hash_rows: Callable[[jnp.ndarray, jnp.ndarray],
                                    jnp.ndarray] | None = None
                ) -> SAALSHIndex:
    """Build an SA-ALSH (transform="sat") or H2-ALSH-style (="qnf") index.

    hash_rows(rows, proj) -> codes overrides the SRP hashing step (stage
    2b); the staged build pipeline passes a mesh-sharded row hasher here.
    Row hashing is independent per row, so any row-sliced hasher is
    bitwise equal to the default full-array ``kops.srp_hash``.
    """
    prep = prepare_items(items, b=b, max_partitions=max_partitions,
                         tile=tile, transform=transform)
    proj = _srp.make_projection(key, items.shape[1] + 1, n_bits)
    codes = (hash_rows or kops.srp_hash)(prep.transformed, proj)
    return assemble_index(prep, codes, proj)


def user_codes(index: SAALSHIndex, users: jnp.ndarray) -> jnp.ndarray:
    """SRP codes of user/query vectors: sign(u @ proj[:d]). (m, d)->(m, W)."""
    return kops.srp_hash(users, index.proj[:-1])


# ---------------------------------------------------------------------------
# Tile scans.
# ---------------------------------------------------------------------------


def _tile_slice(arr: jnp.ndarray, t: jnp.ndarray, tile: int) -> jnp.ndarray:
    start = (t * tile,) + (0,) * (arr.ndim - 1)
    size = (tile,) + arr.shape[1:]
    return jax.lax.dynamic_slice(arr, start, size)


def _tile_candidates(index: SAALSHIndex, ucodes, users, t, *, n_cand: int,
                     scan: str):
    """Exact IPs of the top-n_cand sketch candidates in tile t.

    Returns (ips (C, c), valid (C, c) bool, local (C, c) int32 tile-local
    candidate rows). scan="exact" treats the whole tile as candidates
    (c == tile).
    """
    tile = index.tile
    items_t = _tile_slice(index.items, t, tile)          # (tile, d)
    mask_t = _tile_slice(index.item_mask, t, tile)       # (tile,)
    if scan == "exact":
        ips = users @ items_t.T                          # (C, tile)
        local = jnp.broadcast_to(
            jnp.arange(tile, dtype=jnp.int32)[None, :], ips.shape)
        return ips, jnp.broadcast_to(mask_t[None, :], ips.shape), local
    codes_t = _tile_slice(index.codes, t, tile)          # (tile, W)
    dist = kops.hamming_scores(ucodes, codes_t)          # (C, tile)
    dist = jnp.where(mask_t[None, :], dist, _BIG_HAMMING)
    _, cand = jax.lax.top_k(-dist, n_cand)               # (C, n_cand)
    cand_vecs = jnp.take(items_t, cand, axis=0)          # (C, n_cand, d)
    ips = jnp.einsum("cnd,cd->cn", cand_vecs, users)
    valid = jnp.take(mask_t, cand, axis=0)
    return ips, valid, cand.astype(jnp.int32)


# Headroom multiplier on the quantization error ball: the ball bounds the
# *real-arithmetic* rounding residual; the extra 1% covers the f32 rounding
# of both the dequantized and the exact inner-product evaluations (each is
# ~127 * d * eps_f32 relative to the ball's own radius, < 0.5% at d = 4096).
_QERR_SLACK = 1.01

_SCAN_PRECISIONS = ("f32", "int8")


def _tile_beat_int8(index: SAALSHIndex, ucodes, users, unorm, thr, t, *,
                    n_cand: int, scan: str):
    """Per-lane survivor count of tile t under the quantized screen
    (DESIGN.md SS13) -- bitwise the f32 scan's count.

    Candidates are classified against ``thr`` with their dequantized int8
    inner products and the conservative error ball
    ``qerr = 0.5 * scale * sqrt(d) * ||u|| * slack`` (Cauchy-Schwarz on the
    per-coordinate rounding residual |delta_i| <= scale/2): a *definite*
    beat (qips - qerr > thr) counts immediately, a definite miss
    (qips + qerr <= thr) drops, and only the band in between is re-ranked
    with exact f32 rows. The ball can only widen the band (over-admission),
    never misclassify, so the count matches the f32 path's.
    """
    tile = index.tile
    radius = 0.5 * float(index.dim) ** 0.5 * _QERR_SLACK
    items_t = _tile_slice(index.items, t, tile)           # (tile, d)
    mask_t = _tile_slice(index.item_mask, t, tile)        # (tile,)
    qitems_t = _tile_slice(index.qitems, t, tile)         # (tile, d)
    qscale_t = _tile_slice(index.qscale, t, tile)         # (tile,)
    if scan == "exact":
        # Dense quantized screen over the whole tile. The band re-ranks
        # against the SAME (C, tile) f32 GEMM the f32 path computes (a
        # gathered-row einsum is not bitwise-stable against a GEMM), so
        # exact-scan int8 exercises the screen as a correctness mode; the
        # bandwidth win lives on the sketch path, where the exact re-rank
        # touches only the band rows.
        qips = (users @ qitems_t.T.astype(jnp.float32)) * qscale_t[None, :]
        qerr = (radius * qscale_t)[None, :] * unorm[:, None]
        valid = mask_t[None, :]
        definite = valid & (qips - qerr > thr[:, None])
        band = valid & ~definite & (qips + qerr > thr[:, None])
        ips = users @ items_t.T
        return (jnp.sum(definite, axis=-1)
                + jnp.sum(band & (ips > thr[:, None]), axis=-1))

    codes_t = _tile_slice(index.codes, t, tile)
    cand, qips = kops.fused_scan(ucodes, codes_t, mask_t, qitems_t,
                                 qscale_t, users, n_cand=n_cand)
    valid = jnp.take(mask_t, cand, axis=0)                # (C, n_cand)
    qerr = radius * jnp.take(qscale_t, cand, axis=0) * unorm[:, None]
    definite = valid & (qips - qerr > thr[:, None])
    band = valid & ~definite & (qips + qerr > thr[:, None])
    count = jnp.sum(definite, axis=-1)

    # Exact f32 re-rank of the band, <= s_slots rows per lane per pass
    # (one pass in practice: the band is the thin shell |ip - thr| < qerr).
    # s_slots >= 8 keeps the gathered (C, s, d) einsum bitwise equal to the
    # f32 path's (C, n_cand, d) einsum on this backend -- pinned by
    # tests/test_kernels.py::test_band_einsum_bitwise_stable; s == n_cand
    # is the identical shape outright.
    s_slots = min(16, n_cand)

    def have_band(state):
        left, _ = state
        return jnp.any(left)

    def rerank(state):
        left, c = state
        prio, pos = jax.lax.top_k(left.astype(jnp.int32), s_slots)
        real = prio > 0
        rows = jnp.take_along_axis(cand, pos, axis=-1)    # (C, s)
        vecs = jnp.take(items_t, rows, axis=0)            # (C, s, d)
        eips = jnp.einsum("cnd,cd->cn", vecs, users)
        c = c + jnp.sum(real & (eips > thr[:, None]), axis=-1)
        hit = jax.nn.one_hot(pos, n_cand, dtype=bool) & real[..., None]
        return left & ~jnp.any(hit, axis=-2), c

    _, band_count = jax.lax.while_loop(
        have_band, rerank, (band, jnp.zeros_like(count)))
    return count + band_count


def decide_count_impl(index: SAALSHIndex, users: jnp.ndarray,
                      taus: jnp.ndarray, init_count: jnp.ndarray,
                      active: jnp.ndarray, k: int, *, n_cand: int = 64,
                      scan: str = "sketch", eps: jnp.ndarray | float = 0.0,
                      scan_precision: str = "f32"):
    """RkMIPS decision for a chunk of user lanes against their thresholds.

    users (C, d) -- unit user vectors; taus (C,) = <u, q>; init_count (C,) --
    items already known to beat tau (from the Simpfer lower-bound arrays over
    the top-norm item set P'); active (C,) -- lanes that actually need work;
    eps -- absolute tie tolerance (see core/exact.py), a scalar or a (C,)
    per-lane array.

    Lanes are fully independent: each carries its own tau, its own eps and
    (through tau) its own early-exit bound, so a chunk may mix lanes from
    *different* RkMIPS queries -- the batched flat work queue of
    core/sah.py::rkmips_execute packs mixed-query chunks through this one
    function. (The query vector itself never appears here: it reaches the
    decision only via tau = <u, q>, and the Cauchy-Schwarz tile bound
    mu = max_norm_tile * ||u|| is query-free because users are unit.)
    A lane's outcome depends only on its own (user, tau, count, eps), never
    on which other lanes share the chunk.

    Returns (is_yes (C,), tiles_visited ()) where is_yes[i] means q stays in
    u_i's top-k. Decision rule (Definition 1, strict-count convention):
      no  <=> #{p : <u,p> > tau + eps} >= k
      yes <=> scan exhausted / bound mu_tile <= tau with count < k.

    scan_precision selects the tile screen (DESIGN.md SS13): "f32" (the
    stock float scan) or "int8" (the quantized screen + banded exact
    re-rank of ``_tile_beat_int8``, fed by the fused kernel
    ``repro.kernels.fused_scan``). Execution-only: both produce bitwise
    identical decisions, the early-exit bound and the tile walk are
    precision-independent, and the plan phase never sees the knob.

    This is the undecorated body; call ``decide_count`` (the jitted alias)
    directly. The impl exists for composition inside outer transforms --
    the batched driver traces it raw so the whole query phase stays a
    single-jit computation that is safe under ``shard_map`` (DESIGN.md SS9).
    """
    if scan_precision not in _SCAN_PRECISIONS:
        raise ValueError(f"scan_precision must be one of {_SCAN_PRECISIONS},"
                         f" got {scan_precision!r}")
    n_tiles = index.tile_max_norm.shape[0]
    n_cand_eff = index.tile if scan == "exact" else n_cand
    ucodes = user_codes(index, users) if scan == "sketch" else \
        jnp.zeros((users.shape[0], index.codes.shape[1]), jnp.uint32)
    # (taus + eps) broadcasts for scalar and per-lane eps alike, and is
    # bitwise the f32 additions the scalar-eps form performed.
    thr = taus + eps                                      # (C,)
    unorm = (jnp.linalg.norm(users, axis=-1)
             if scan_precision == "int8" else None)

    def cond(state):
        t, count, undecided = state
        return (t < n_tiles) & jnp.any(undecided)

    def body(state):
        t, count, undecided = state
        mu = index.tile_max_norm[t]                       # scalar bound
        # Lanes whose tau already dominates the bound are decided "yes".
        bound_done = mu <= taus
        still = undecided & ~bound_done
        if scan_precision == "int8":
            beat = _tile_beat_int8(index, ucodes, users, unorm, thr, t,
                                   n_cand=n_cand_eff, scan=scan)
        else:
            ips, valid, _ = _tile_candidates(index, ucodes, users, t,
                                             n_cand=n_cand_eff, scan=scan)
            beat = jnp.sum((ips > thr[:, None]) & valid, axis=-1)
        count = count + jnp.where(still, beat, 0)
        undecided = still & (count < k)
        return t + 1, count, undecided

    count0 = jnp.where(active, init_count, k)             # inactive: decided
    undecided0 = active & (count0 < k)
    t_fin, count_fin, _ = jax.lax.while_loop(
        cond, body, (jnp.asarray(0, jnp.int32), count0, undecided0))
    is_yes = active & (count_fin < k)
    return is_yes, t_fin


decide_count = functools.partial(
    jax.jit, static_argnames=("k", "n_cand", "scan", "scan_precision"),
)(decide_count_impl)


@functools.partial(jax.jit, static_argnames=("k",))
def merge_topk(vals: jnp.ndarray, ids: jnp.ndarray,
               extra_vals: jnp.ndarray, extra_ids: jnp.ndarray, k: int):
    """Row-wise merge of two candidate sets into one descending top-k.

    vals/ids (Q, a) and extra_vals/extra_ids (Q, b) -> (Q, k) each. Dead
    candidates must carry ``-inf`` values (and whatever sentinel id). Used
    by the engine to fold the exactly-scanned staged-insert delta buffer
    into a main-index kMIPS answer (engine/artifact.py), and generic
    enough for any local-top-k combination.
    """
    merged_v = jnp.concatenate([vals, extra_vals], axis=-1)
    merged_i = jnp.concatenate([ids, extra_ids], axis=-1)
    best, pos = jax.lax.top_k(merged_v, k)
    return best, jnp.take_along_axis(merged_i, pos, axis=-1)


@jax.named_scope("kmips.merge")
def merge_delta_topk(vals: jnp.ndarray, ids: jnp.ndarray,
                     queries: jnp.ndarray, d_items: jnp.ndarray,
                     d_mask: jnp.ndarray, k: int, n_base: int, *,
                     d_qitems: jnp.ndarray | None = None,
                     d_qscale: jnp.ndarray | None = None,
                     scan_precision: str = "f32"):
    """Fold the staged-insert delta buffer into a main-index top-k answer.

    vals/ids (Q, k) -- the main scan's descending top-k; queries (Q, d);
    d_items (cap, d) staged rows with liveness d_mask (cap,). Staged row j
    gets id ``n_base + j``. This is THE forward delta merge: the engine's
    ``kmips`` and the RetrievalServer's jitted merge both route through it,
    so the two surfaces can never disagree id-for-id (DESIGN.md SS10).

    ``scan_precision="int8"`` screens the buffer with its persisted
    quantized twin (``d_qitems``/``d_qscale``, per-row scales --
    engine/artifact.py stamps them at insert) before touching f32: a row
    whose dequantized IP plus the Cauchy-Schwarz error ball
    ``0.5 * sqrt(d) * slack * scale * ||q||`` cannot beat the main scan's
    k-th value is dropped outright -- it provably cannot displace any
    incumbent (ties break toward earlier positions, and the main top-k
    concatenates first). Only surviving band rows are scored in f32, by
    the *same* GEMM expression the f32 path uses, skipped entirely
    (``lax.cond``) when that query's band screens clean -- so the merged
    answer is BITWISE the f32 merge, and the screen may only over-admit
    (the SS13 contract, applied to the delta buffer).

    The f32 scoring maps over queries (``lax.map``) for the same reason
    as the main scan (engine/sharding.py): a batched contraction's
    per-row low bits vary with Q, and the serving bucket ladder dispatches
    this merge at every rung — bitwise rung-equality (DESIGN.md SS14)
    needs per-query bodies whose shapes never see Q.
    """
    if scan_precision not in _SCAN_PRECISIONS:
        raise ValueError(f"scan_precision must be one of {_SCAN_PRECISIONS},"
                         f" got {scan_precision!r}")
    if scan_precision == "int8":
        if d_qitems is None or d_qscale is None:
            raise ValueError("int8 delta merge needs the quantized buffer: "
                             "pass d_qitems/d_qscale "
                             "(artifact.kmips_delta_quantized)")
        radius = 0.5 * float(queries.shape[-1]) ** 0.5 * _QERR_SLACK
        qitems_f32 = d_qitems.astype(jnp.float32)

        def one_screened(args):
            q, v = args                                  # (d,), (k,)
            qips = (qitems_f32 @ q) * d_qscale
            qerr = radius * d_qscale * jnp.linalg.norm(q)
            band = d_mask & (qips + qerr > v[k - 1])
            ips = jax.lax.cond(
                jnp.any(band),
                lambda: d_items @ q,
                lambda: jnp.zeros((d_items.shape[0],), vals.dtype))
            return jnp.where(band, ips, -jnp.inf)
        d_vals = jax.lax.map(one_screened, (queries, vals))
    else:
        d_vals = jax.lax.map(
            lambda q: jnp.where(d_mask, d_items @ q, -jnp.inf), queries)
    d_ids = jnp.broadcast_to(
        n_base + jnp.arange(d_items.shape[0], dtype=ids.dtype),
        d_vals.shape)
    return merge_topk(vals, ids, d_vals, d_ids, k)


def delta_screen_tables(users: jnp.ndarray, d_qitems: jnp.ndarray,
                        d_qscale: jnp.ndarray):
    """Query-independent int8 screen tables for the staged delta buffer in
    the *reverse* plan (sah.py ``_plan_one``): ``(qips, qerr)``, both
    (m, cap).

    ``qips[u, j]`` is the dequantized inner product of user row u with
    staged row j; ``qerr[u, j]`` its sound error radius — the same
    ``0.5 * sqrt(d) * slack * scale * ||u||`` Cauchy-Schwarz ball the
    forward merge (``merge_delta_topk``) puts around a query's dequantized
    IP, with the user vector in the query role. Dead slots (scale 0) get
    qips = qerr = 0 and are masked by the caller's ``delta_mask`` anyway.
    Computed once per dispatch by every driver (the full GEMM is the
    identical expression in the per-query and batched paths, keeping their
    screen decisions bitwise consistent).
    """
    radius = 0.5 * float(users.shape[-1]) ** 0.5 * _QERR_SLACK
    qips = (users @ d_qitems.astype(jnp.float32).T) * d_qscale[None, :]
    qerr = radius * d_qscale[None, :] * \
        jnp.linalg.norm(users, axis=-1, keepdims=True)
    return qips, qerr


@functools.partial(jax.jit, static_argnames=("k", "n_cand", "scan"))
def kmips_topk(index: SAALSHIndex, queries: jnp.ndarray, k: int,
               *, n_cand: int = 64, scan: str = "sketch"):
    """Approximate kMIPS (Algorithm 2) for a batch of query/user vectors.

    queries (Q, d) -- need not be unit (the bound uses ||q||).
    Returns (vals (Q, k) descending, ids (Q, k) original item rows,
    tiles_visited ()). Early-terminates when the current kth best phi
    dominates the Cauchy-Schwarz bound mu_tile * ||q|| for every query.
    """
    n_tiles = index.tile_max_norm.shape[0]
    qn = jnp.linalg.norm(queries, axis=-1)                # (Q,)
    n_cand_eff = index.tile if scan == "exact" else n_cand
    ucodes = user_codes(index, queries) if scan == "sketch" else \
        jnp.zeros((queries.shape[0], index.codes.shape[1]), jnp.uint32)

    nq = queries.shape[0]
    vals0 = jnp.full((nq, k), _NEG, jnp.float32)
    ids0 = jnp.full((nq, k), -1, jnp.int32)

    def cond(state):
        t, vals, _ = state
        phi = vals[:, -1]                                 # kth best so far
        mu = index.tile_max_norm[jnp.minimum(t, n_tiles - 1)] * qn
        return (t < n_tiles) & jnp.any(phi < mu)

    def body(state):
        t, vals, ids = state
        tile = index.tile
        ips, valid, local = _tile_candidates(index, ucodes, queries, t,
                                             n_cand=n_cand_eff, scan=scan)
        ips = jnp.where(valid, ips, _NEG)
        global_ids = jnp.take(
            index.item_ids, t * tile + local, axis=0)     # (Q, c)
        merged_v = jnp.concatenate([vals, ips], axis=-1)
        merged_i = jnp.concatenate([ids, global_ids], axis=-1)
        best_v, pos = jax.lax.top_k(merged_v, k)
        best_i = jnp.take_along_axis(merged_i, pos, axis=-1)
        return t + 1, best_v, best_i

    t_fin, vals, ids = jax.lax.while_loop(cond, body, (jnp.asarray(0, jnp.int32),
                                                       vals0, ids0))
    return vals, ids, t_fin
