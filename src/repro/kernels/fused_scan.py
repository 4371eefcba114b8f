"""Pallas TPU kernel: fused Hamming filter + quantized IP for decide_count.

This is the int8 hot path of the RkMIPS execute loop (DESIGN.md SS13). For a
chunk of user lanes and one norm-ordered item tile it fuses three stages that
the f32 path runs as separate lax ops:

  1. popcount(xor(codes))         -- the SA-ALSH sketch filter,
  2. top-``n_cand`` selection     -- survivor compaction per lane,
  3. int8 gather + dequantized IP -- the quantized screening scores.

The caller (core/sa_alsh.py::_tile_beat_int8) classifies the returned scores
against its error ball and re-ranks only the ambiguous band in exact f32, so
nothing here needs to be bitwise anything -- correctness of the final counts
depends only on ``|qips - <qitems[cand], u> * qscale[cand]|`` staying inside
the float error the ball's 1% slack absorbs (see _QERR_SLACK).

Selection uses iterated argmin rather than a sort: argmin takes the lowest
index on ties, which is exactly ``jax.lax.top_k``'s tie-break on negated
distances, so the lax mirror below is candidate-for-candidate identical to
the ref.py oracle. Selected lanes are masked to INT32_MAX; unselected
entries are at most _BIG_HAMMING (1 << 30) < INT32_MAX, so a row can never
be picked twice while any unpicked row remains. The kernel spells the same
argmin as two f32 min-reductions (the minimum, then the lowest column
holding it), with +inf as the picked mask, and gathers the picked int8 row
by a one-hot bf16 matmul -- exact, since both operands are small integers.

Tiling: grid (C // block_q,). Each program instance owns ``block_q`` user
lanes and the whole (W, T) code tile (transposed, items on the lanes as in
kernels/hamming_scan.py) / (T, d) int8 tile -- T is the core library's
partition tile (<= 4096), so at T=4096, d=128, W=8 the resident VMEM is
4096*8*4 + 4096*128 + 4096*4 + block_q*(W*4 + d*4) ~ 0.7 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import hamming_scan as _hamming
from repro.kernels import ref as _ref

# Python ints, not jnp scalars: the Pallas kernel body may not capture
# traced constants, and weak-typed literals fold into int32 ops anyway.
_BIG_HAMMING = 1 << 30
_INT_MAX = 2**31 - 1


def fused_scan_lax(ucodes: jnp.ndarray, item_codes: jnp.ndarray,
                   item_mask: jnp.ndarray, qitems: jnp.ndarray,
                   qscale: jnp.ndarray, users: jnp.ndarray,
                   *, n_cand: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """lax mirror of the kernel; bitwise equal to ref.fused_scan.

    Same signature/result as ref.fused_scan but selects by iterated argmin
    instead of ``lax.top_k`` -- on CPU the O(T log T) sort inside top_k
    dominates the whole scan (BENCH kernel/fused_scan cells), while n_cand
    argmin sweeps stay O(n_cand * T) with trivial constants. Scores the
    selected rows with the identical gather + einsum the oracle uses, so the
    qips halves agree bitwise too. Not jitted: called inside already-jitted
    decide_count traces.
    """
    dist = _ref.hamming_scores(ucodes, item_codes)        # (C, T)
    dist = jnp.where(item_mask[None, :], dist, _BIG_HAMMING)
    c, t = dist.shape
    cand0 = jnp.zeros((c, n_cand), dtype=jnp.int32)

    def pick(i, state):
        d_, cand = state
        arg = jnp.argmin(d_, axis=-1)                     # ties -> lowest row
        cand = cand.at[:, i].set(arg.astype(jnp.int32))
        onehot = jax.nn.one_hot(arg, t, dtype=jnp.bool_)
        return jnp.where(onehot, _INT_MAX, d_), cand

    _, cand = jax.lax.fori_loop(0, n_cand, pick, (dist, cand0))
    qvecs = jnp.take(qitems, cand, axis=0).astype(jnp.float32)
    qips = jnp.einsum("cnd,cd->cn", qvecs, users)
    qips = qips * jnp.take(qscale, cand, axis=0)
    return cand, qips


def _fused_scan_kernel(uc_ref, codes_ref, mask_ref, qitems_ref, qscale_ref,
                       users_ref, cand_ref, qips_ref, *, n_cand):
    # int8 rows are small integers: exact in bf16, so the one-hot gather
    # below is an exact single-pass MXU matmul
    qi = qitems_ref[...].astype(jnp.float32).astype(jnp.bfloat16)  # (T, d)
    qs = qscale_ref[...]                 # (1, T) f32
    u = users_ref[...]                   # (bq, d) f32
    dist = _hamming.distances(uc_ref[...], codes_ref[...])        # (bq, T)
    # distances are integers < 2^24, exact in f32: the selection runs as
    # f32 min-reductions, the only reductions Mosaic lowers for it
    dist = jnp.where(mask_ref[...] > 0, dist,
                     _BIG_HAMMING).astype(jnp.float32)
    bq, t = dist.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (bq, t), 1).astype(jnp.float32)
    slots = jax.lax.broadcasted_iota(jnp.int32, (bq, n_cand), 1)

    def pick(i, state):
        d_, cand, qips = state
        best = jnp.min(d_, axis=-1, keepdims=True)               # (bq, 1)
        # lowest column among the minima: lax.top_k's tie-break
        arg = jnp.min(jnp.where(d_ == best, cols, float(t)), axis=-1,
                      keepdims=True)                             # (bq, 1)
        onehot = cols == arg                                     # (bq, T)
        row = jnp.dot(onehot.astype(jnp.bfloat16), qi,
                      preferred_element_type=jnp.float32)        # (bq, d)
        scale = jnp.sum(jnp.where(onehot, qs, 0.0), axis=-1, keepdims=True)
        ip = jnp.sum(row * u, axis=-1, keepdims=True) * scale
        # candidates accumulate in registers by select; one store at the end
        slot = slots == i
        cand = jnp.where(slot, arg, cand)
        qips = jnp.where(slot, ip, qips)
        return jnp.where(onehot, jnp.inf, d_), cand, qips

    zeros = jnp.zeros((bq, n_cand), jnp.float32)
    _, cand, qips = jax.lax.fori_loop(0, n_cand, pick, (dist, zeros, zeros))
    cand_ref[...] = cand.astype(jnp.int32)
    qips_ref[...] = qips


@functools.partial(jax.jit,
                   static_argnames=("n_cand", "block_q", "interpret"))
def fused_scan_tiles(ucodes: jnp.ndarray, item_codes: jnp.ndarray,
                     item_mask: jnp.ndarray, qitems: jnp.ndarray,
                     qscale: jnp.ndarray, users: jnp.ndarray,
                     *, n_cand: int, block_q: int = 8,
                     interpret: bool = False
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """ucodes (C, W) u32, item_codes (T, W) u32, item_mask (T,) bool,
    qitems (T, d) int8, qscale (T,) f32, users (C, d) f32
    -> (cand (C, n_cand) int32, qips (C, n_cand) f32).

    C must be a multiple of block_q (kernels/ops.py pads).
    cand matches ref.fused_scan exactly; qips matches to float tolerance
    (the in-kernel dot product reassociates the oracle's einsum).
    """
    c, w = ucodes.shape
    t, w2 = item_codes.shape
    d = qitems.shape[1]
    assert w == w2, (w, w2)
    assert c % block_q == 0, (c, block_q)
    mask2 = item_mask.astype(jnp.int32).reshape(1, t)
    qscale2 = qscale.reshape(1, t)
    grid = (c // block_q,)
    return pl.pallas_call(
        functools.partial(_fused_scan_kernel, n_cand=n_cand),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, w), lambda i: (i, 0)),
            pl.BlockSpec((w, t), lambda i: (0, 0)),
            pl.BlockSpec((1, t), lambda i: (0, 0)),
            pl.BlockSpec((t, d), lambda i: (0, 0)),
            pl.BlockSpec((1, t), lambda i: (0, 0)),
            pl.BlockSpec((block_q, d), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, n_cand), lambda i: (i, 0)),
            pl.BlockSpec((block_q, n_cand), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((c, n_cand), jnp.int32),
            jax.ShapeDtypeStruct((c, n_cand), jnp.float32),
        ],
        interpret=interpret,
        name="fused_scan",
    )(_hamming.as_words(ucodes), _hamming.as_words(item_codes).T, mask2,
      qitems, qscale2, users)
