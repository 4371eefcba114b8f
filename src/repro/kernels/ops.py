"""Jit'd public entry points for the Pallas kernels with CPU dispatch.

On TPU backends the Pallas kernels run compiled; on CPU (this container) the
vectorized jnp oracles from ref.py are used instead -- interpret=True Pallas
execution is reserved for the correctness tests (it runs the kernel body in
Python per grid step, which is far too slow for benchmark workloads).

Set REPRO_FORCE_INTERPRET=1 to route ops through the interpret-mode kernels
(used by integration tests to prove the kernels compose with the full system).

The Pallas path of each op (``*_pallas``) takes any shape: rows that do not
fill a block are padded up to the block multiple and the padding is sliced
off the result, with the block and padded size chosen by ``tiling``. A TPU
never runs a block of one row and never falls back to ref.py; the compile
tests (tests/test_tpu_compile.py) lower these same functions for a v5e.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _flash
from repro.kernels import fused_scan as _fused
from repro.kernels import hamming_scan as _hamming
from repro.kernels import ip_topk as _ip_topk
from repro.kernels import ref as _ref
from repro.kernels import srp_hash as _srp


def _use_pallas() -> bool:
    if os.environ.get("REPRO_FORCE_INTERPRET"):
        return True
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def tiling(n: int, block: int) -> tuple[int, int]:
    """(block rows, padded rows) for tiling ``n`` rows by ``block``.

    One full-extent block when ``n <= block`` (always a legal TPU block
    shape); otherwise ``block``-row tiles -- callers pass multiples of 8
    for sublane axes and of 128 for lane axes -- over ``n`` rounded up to
    a block multiple."""
    if n <= block:
        return n, n
    return block, -(-n // block) * block


def _pad_to(x: jnp.ndarray, n_pad: int) -> jnp.ndarray:
    if n_pad == x.shape[0]:
        return x
    return jnp.pad(x, [(0, n_pad - x.shape[0])] + [(0, 0)] * (x.ndim - 1))


def hamming_pallas(query_codes: jnp.ndarray, item_codes: jnp.ndarray, *,
                   interpret: bool = False) -> jnp.ndarray:
    """The Pallas path of ``hamming_scores`` at any (q, n)."""
    q, n = query_codes.shape[0], item_codes.shape[0]
    bq, q_pad = tiling(q, 128)
    bn, n_pad = tiling(n, 512)
    out = _hamming.hamming_scores(_pad_to(query_codes, q_pad),
                                  _pad_to(item_codes, n_pad), block_q=bq,
                                  block_n=bn, interpret=interpret)
    return out[:q, :n]


def hamming_scores(query_codes: jnp.ndarray,
                   item_codes: jnp.ndarray) -> jnp.ndarray:
    """(q, W) x (n, W) uint32 codes -> (q, n) int32 Hamming distances."""
    if _use_pallas():
        return hamming_pallas(query_codes, item_codes,
                              interpret=_interpret())
    return _ref.hamming_scores(query_codes, item_codes)


def fused_scan_pallas(ucodes: jnp.ndarray, item_codes: jnp.ndarray,
                      item_mask: jnp.ndarray, qitems: jnp.ndarray,
                      qscale: jnp.ndarray, users: jnp.ndarray, *,
                      n_cand: int, interpret: bool = False
                      ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The Pallas path of ``fused_scan`` at any lane count C."""
    c = users.shape[0]
    bq, c_pad = tiling(c, 8)
    cand, qips = _fused.fused_scan_tiles(
        _pad_to(ucodes, c_pad), item_codes, item_mask, qitems, qscale,
        _pad_to(users, c_pad), n_cand=n_cand, block_q=bq,
        interpret=interpret)
    return cand[:c], qips[:c]


def fused_scan(ucodes: jnp.ndarray, item_codes: jnp.ndarray,
               item_mask: jnp.ndarray, qitems: jnp.ndarray,
               qscale: jnp.ndarray, users: jnp.ndarray,
               *, n_cand: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused Hamming filter + top-n_cand + dequantized int8 IP per lane.

    (C, W) u32 x (T, W) u32 codes with (T,) mask, (T, d) int8 + (T,) scale
    -> (cand (C, n_cand) int32, qips (C, n_cand) f32). The CPU fallback is
    the lax mirror, not ref.py: identical results (cand bitwise, qips
    bitwise too -- same gather + einsum) but without lax.top_k's sort,
    which dominates the scan on CPU (see BENCH kernel/fused_scan cells).
    """
    if _use_pallas():
        return fused_scan_pallas(ucodes, item_codes, item_mask, qitems,
                                 qscale, users, n_cand=n_cand,
                                 interpret=_interpret())
    return _fused.fused_scan_lax(ucodes, item_codes, item_mask, qitems,
                                 qscale, users, n_cand=n_cand)


def srp_pallas(x: jnp.ndarray, proj: jnp.ndarray, *,
               interpret: bool = False) -> jnp.ndarray:
    """The Pallas path of ``srp_hash`` at any row count."""
    n = x.shape[0]
    bn, n_pad = tiling(n, 256)
    return _srp.srp_hash(_pad_to(x, n_pad), proj, block_n=bn,
                         interpret=interpret)[:n]


def srp_hash(x: jnp.ndarray, proj: jnp.ndarray) -> jnp.ndarray:
    """(n, d) f32 through (d, B) projection -> (n, B//32) uint32 codes."""
    if _use_pallas():
        return srp_pallas(x, proj, interpret=_interpret())
    return _ref.srp_hash(x, proj)


@functools.partial(jax.jit, static_argnames=("k",))
def _merge_topk(vals: jnp.ndarray, ids: jnp.ndarray, k: int):
    q = vals.shape[0]
    flat_v = vals.reshape(q, -1)
    flat_i = ids.reshape(q, -1)
    best_v, pos = jax.lax.top_k(flat_v, k)
    best_i = jnp.take_along_axis(flat_i, pos, axis=-1)
    return best_v, best_i


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    *, causal: bool = True) -> jnp.ndarray:
    """Fused causal attention: Pallas on TPU, jnp oracle elsewhere.

    The CPU fallback is the O(S^2)-memory oracle -- only smoke-scale shapes
    should take it (the transformer's default stays chunked attention;
    attn_impl='flash' is the TPU deployment path, see models/transformer)."""
    if _use_pallas():
        return _flash.flash_attention(q, k, v, causal=causal,
                                      interpret=_interpret())
    return _ref.flash_attention(q, k, v, causal=causal)


def ip_topk_pallas(queries: jnp.ndarray, items: jnp.ndarray, k: int, *,
                  block_n: int = 2048, interpret: bool = False
                  ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The Pallas path of ``ip_topk`` at any (q, n) with k <= n. Padded
    item rows score -inf inside the kernel, so they never outrank a real
    row."""
    q, n = queries.shape[0], items.shape[0]
    bq, q_pad = tiling(q, 128)
    bn, n_pad = tiling(n, block_n)
    vals, ids = _ip_topk.ip_topk_tiles(_pad_to(queries, q_pad),
                                       _pad_to(items, n_pad), k, block_q=bq,
                                       block_n=bn, n_valid=n,
                                       interpret=interpret)
    vals, ids = _merge_topk(vals, ids, k)
    return vals[:q], ids[:q]


def ip_topk(queries: jnp.ndarray, items: jnp.ndarray, k: int,
            *, block_n: int = 2048) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact top-k inner products: (q, d) x (n, d) -> (vals, ids) (q, k)."""
    if _use_pallas():
        return ip_topk_pallas(queries, items, k, block_n=block_n,
                              interpret=_interpret())
    return _ref.ip_topk(queries, items, k)
