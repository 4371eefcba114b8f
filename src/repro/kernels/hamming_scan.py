"""Pallas TPU kernel: all-pairs Hamming distance between bit-packed SRP codes.

This is the hot inner loop of SA-ALSH on TPU: for a chunk of users (queries)
and a norm-ordered tile of items, score every pair by popcount(xor(codes)).
Compared to the exact float scan it moves 32x fewer bytes per item
(B bits vs d floats) and runs entirely on the VPU.

Layout: item codes enter transposed, (W, n), so items lie along the 128
lanes and each of the W words is one (block_q, block_n) xor + popcount,
accumulated in int32 -- no (q, n, W) intermediate with W on the lanes, and
no unsigned arithmetic (Mosaic has no unsigned reductions): the wrapper
bitcasts the uint32 codes to int32, which popcount reads bit for bit.

Tiling: grid (q_tiles, n_tiles). Each program instance loads a
(block_q, W) query-code tile and a (W, block_n) item-code tile into VMEM and
writes a (block_q, block_n) int32 distance tile.

VMEM budget at defaults (block_q=128, block_n=512, W<=8):
  in: 128*8*4 + 8*512*4 = 20 KB, accumulator + out 2 * 256 KB
  -- comfortably inside the ~16 MB v5e VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def as_words(codes: jnp.ndarray) -> jnp.ndarray:
    """uint32 codes -> the int32 words the kernels read (same bits)."""
    return jax.lax.bitcast_convert_type(codes, jnp.int32)


def distances(q_words: jnp.ndarray, items_t: jnp.ndarray) -> jnp.ndarray:
    """In-kernel Hamming tile: (bq, W) x (W, bn) int32 words -> (bq, bn)."""
    acc = None
    for w in range(q_words.shape[1]):
        x = jnp.bitwise_xor(q_words[:, w:w + 1], items_t[w:w + 1, :])
        pc = jax.lax.population_count(x)
        acc = pc if acc is None else acc + pc
    return acc


def _hamming_kernel(q_ref, n_ref, out_ref):
    out_ref[...] = distances(q_ref[...], n_ref[...])


@functools.partial(jax.jit, static_argnames=("block_q", "block_n", "interpret"))
def hamming_scores(query_codes: jnp.ndarray, item_codes: jnp.ndarray,
                   *, block_q: int = 128, block_n: int = 512,
                   interpret: bool = False) -> jnp.ndarray:
    """query_codes (q, W) uint32, item_codes (n, W) uint32 -> (q, n) int32.

    q and n must be multiples of block_q / block_n (kernels/ops.py pads).
    """
    q, w = query_codes.shape
    n, w2 = item_codes.shape
    assert w == w2, (w, w2)
    assert q % block_q == 0 and n % block_n == 0, (q, n, block_q, block_n)
    grid = (q // block_q, n // block_n)
    return pl.pallas_call(
        _hamming_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, w), lambda i, j: (i, 0)),
            pl.BlockSpec((w, block_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_q, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((q, n), jnp.int32),
        interpret=interpret,
        name="hamming_scores",
    )(as_words(query_codes), as_words(item_codes).T)
