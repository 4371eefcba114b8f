"""Pallas TPU kernel: fused SRP hashing -- projection matmul + sign + bitpack.

Computes uint32-packed SimHash codes for a batch of (already transformed)
vectors:  code[i, w] bit j = (x[i] . proj[:, 32w+j] >= 0).

Fusion rationale (memory roofline): the naive composition materializes the
(n, B) sign/projection matrix in HBM (n*B*4 bytes with f32 projections) before
packing. Fused, only the (n, B/32) codes leave the chip: a 128x reduction in
output bytes. Both the projection and the bit packing run on the MXU, all
within one VMEM residency.

Packing is a second matmul, not a lane-splitting reshape (Mosaic cannot
relayout (bn, B) -> (bn, B/32, 32)) and not an unsigned reduction (Mosaic
has none): signs (bn, B) in {0, 1} times a (B, W) weight matrix holding 2^j
at row 32w + j of column w. Each word is packed as two 16-bit halves so
every partial sum stays below 2^16 -- exact in bf16 operands with f32
accumulation -- and the halves are joined with an int32 shift/or. The
kernel writes int32 words; the wrapper bitcasts them to uint32.

Tiling: grid over row blocks; each instance handles (block_n, d) x (d, B).
d (the vector dim, <= a few hundred here) and B (128-512 bits) are kept whole
per block: VMEM at block_n=256, d=512, B=256: in 256*512*4 = 512 KB,
proj 512*256*4 = 512 KB, scores 256*256*4 = 256 KB -- fine.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def _pack_weights(b: int) -> tuple[np.ndarray, np.ndarray]:
    """(lo, hi) (B, B//32) weights: bit j of word w -> 2^(j mod 16) in the
    half (lo for j < 16, hi otherwise) that holds it."""
    j = np.arange(b)
    word, bit = j // 32, j % 32
    onehot = word[:, None] == np.arange(b // 32)[None, :]
    weight = (2.0 ** (bit % 16))[:, None] * onehot
    return ((weight * (bit < 16)[:, None]).astype(np.float32),
            (weight * (bit >= 16)[:, None]).astype(np.float32))


def _srp_kernel(x_ref, p_ref, lo_ref, hi_ref, out_ref):
    scores = jnp.dot(x_ref[...], p_ref[...],
                     preferred_element_type=jnp.float32)         # (bn, B)
    signs = (scores >= 0.0).astype(jnp.bfloat16)
    lo = jnp.dot(signs, lo_ref[...],
                 preferred_element_type=jnp.float32)             # (bn, W)
    hi = jnp.dot(signs, hi_ref[...],
                 preferred_element_type=jnp.float32)
    out_ref[...] = jnp.bitwise_or(
        lo.astype(jnp.int32),
        jnp.left_shift(hi.astype(jnp.int32), 16))


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def srp_hash(x: jnp.ndarray, proj: jnp.ndarray, *, block_n: int = 256,
             interpret: bool = False) -> jnp.ndarray:
    """x (n, d) f32, proj (d, B) f32, B % 32 == 0 -> (n, B//32) uint32 codes.

    n must be a multiple of block_n (kernels/ops.py pads)."""
    n, d = x.shape
    d2, b = proj.shape
    assert d == d2 and b % 32 == 0, (d, d2, b)
    assert n % block_n == 0, (n, block_n)
    w = b // 32
    lo, hi = _pack_weights(b)
    words = pl.pallas_call(
        _srp_kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((d, b), lambda i: (0, 0)),
            pl.BlockSpec((b, w), lambda i: (0, 0)),
            pl.BlockSpec((b, w), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, w), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, w), jnp.int32),
        interpret=interpret,
        name="srp_hash",
    )(x, proj, jnp.asarray(lo, jnp.bfloat16), jnp.asarray(hi, jnp.bfloat16))
    return jax.lax.bitcast_convert_type(words, jnp.uint32)
