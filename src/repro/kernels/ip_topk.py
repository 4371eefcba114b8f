"""Pallas TPU kernel: fused inner-product scoring + per-tile top-k.

Hot path of `retrieval_cand` (one query against 10^6 candidates) and of the
exact re-ranking step inside SAH: scores = Q @ C^T immediately reduced to the
k best per candidate tile, so the (q, n) score matrix never reaches HBM --
only (q, n_tiles, k) survives (a n/(tiles*k) ~ 64x output-byte reduction at
tile=2048, k=32). A cheap jnp merge of the per-tile winners produces the
global top-k (done in ops.ip_topk).

Per-tile top-k is a k-step select loop (argmax + mask) on the VPU; the matmul
runs on the MXU. k is a compile-time constant (<= 128 in all our uses).

Tiling: grid (q_blocks, n_tiles); block (bq, d) x (bn, d) -> out (bq, k).
VMEM at bq=128, bn=2048, d=256: inputs 128*256*4 + 2048*256*4 = 2.2 MB,
scores 128*2048*4 = 1 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ip_topk_kernel(q_ref, c_ref, vals_ref, ids_ref, *, k: int, block_n: int,
                   n_valid: int):
    base = pl.program_id(1) * block_n
    scores = jax.lax.dot_general(
        q_ref[...], c_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                # (bq, bn)
    bq = scores.shape[0]
    cols = jax.lax.broadcasted_iota(jnp.int32, (bq, block_n), 1)
    # rows past n_valid are the wrapper's padding: never above a real row
    scores = jnp.where(cols + base < n_valid, scores, -jnp.inf)
    colf = cols.astype(jnp.float32)
    slots = jax.lax.broadcasted_iota(jnp.int32, (bq, k), 1)

    def body(i, carry):
        taken, vals, ids = carry         # taken: 1.0 on picked columns
        s = jnp.where(taken > 0, -jnp.inf, scores)
        best = jnp.max(s, axis=-1, keepdims=True)                   # (bq, 1)
        # lowest untaken column holding the maximum (lax.top_k's
        # tie-break), as f32 reductions; columns < 2^24 are exact
        arg = jnp.min(jnp.where((s == best) & (taken == 0), colf,
                                float(block_n)), axis=-1, keepdims=True)
        slot = slots == i
        vals = jnp.where(slot, best, vals)
        ids = jnp.where(slot, arg, ids)
        return jnp.where(colf == arg, 1.0, taken), vals, ids

    init = (jnp.zeros((bq, block_n), jnp.float32),
            jnp.full((bq, k), -jnp.inf, jnp.float32),
            jnp.zeros((bq, k), jnp.float32))
    _, vals, ids = jax.lax.fori_loop(0, k, body, init)
    vals_ref[...] = vals
    ids_ref[...] = ids.astype(jnp.int32) + base


@functools.partial(jax.jit, static_argnames=("k", "block_q", "block_n",
                                             "n_valid", "interpret"))
def ip_topk_tiles(queries: jnp.ndarray, items: jnp.ndarray, k: int,
                  *, block_q: int = 128, block_n: int = 2048,
                  n_valid: int | None = None, interpret: bool = False
                  ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-tile top-k inner products.

    queries (q, d) f32, items (n, d) f32 -> (vals, ids) each (q, n_tiles, k);
    ids are global row indices into items. Requires q % block_q == 0,
    n % block_n == 0 and block_n >= k. Rows at or past ``n_valid`` (default
    n) score -inf: kernels/ops.py pads ``items`` with them.
    """
    q, d = queries.shape
    n, d2 = items.shape
    assert d == d2, (d, d2)
    assert q % block_q == 0 and n % block_n == 0 and block_n >= k
    n_tiles = n // block_n
    kernel = functools.partial(_ip_topk_kernel, k=k, block_n=block_n,
                               n_valid=n if n_valid is None else n_valid)
    # tile-major outputs keep each block's last two dims (block_q, k) legal
    # for the TPU's (8, 128) tiling; transposed back below
    vals, ids = pl.pallas_call(
        kernel,
        grid=(q // block_q, n_tiles),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, k), lambda i, j: (j, i, 0)),
            pl.BlockSpec((None, block_q, k), lambda i, j: (j, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_tiles, q, k), jnp.float32),
            jax.ShapeDtypeStruct((n_tiles, q, k), jnp.int32),
        ],
        interpret=interpret,
        name="ip_topk",
    )(queries, items)
    return vals.transpose(1, 0, 2), ids.transpose(1, 0, 2)
