"""The plain reference: brute-force inner products in ``jax.numpy``.

It imports nothing of the program and takes nothing the program made:
only the corpus (``corpus.py``) and the queries. Every product runs at
``Precision.HIGHEST`` in float32. With another ``dtype`` the inputs are
first rounded to it: that is the lower-precision control, one step below
the precision the configuration states (``CONTROL``). Work is done in blocks of rows, so the reference
fits beside nothing else on one chip.

Semantics (the SAH paper's Definition 1, with the program's tie rule): a
user u is in the audience of query q at k iff fewer than k items p have
<u, p> > <u, q> + tie_eps * ||q||, i.e. iff u's k-th largest item score
s_k(u) <= <u, q> + tie_eps * ||q|| (q itself never counts against q).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST
# the precision a configuration states -> the control's, one step below
CONTROL = {"float32": jnp.bfloat16, "bfloat16": jnp.float8_e4m3fn}


def _ips(a, b, dtype):
    """a (p, d) x b (r, d) -> (p, r) float32 inner products of the inputs
    rounded to ``dtype`` (exact for float32): the products of rounded
    values are exact in float32, and the sums are float32."""
    a = a.astype(dtype).astype(jnp.float32)
    b = b.astype(dtype).astype(jnp.float32)
    return jnp.matmul(a, b.T, precision=_HIGHEST)


@functools.partial(jax.jit, static_argnames=("k", "dtype"))
def _kth_block(users, items, *, k, dtype):
    return jax.lax.top_k(_ips(users, items, dtype), k + 1)[0][:, k - 1:]


def kth_scores(items, users, k: int, dtype=jnp.float32,
               block: int = 4096) -> jax.Array:
    """(m, 2) each user's k-th and (k+1)-th largest item scores, computed
    in blocks of users."""
    m = users.shape[0]
    pad = -m % block
    u = jnp.pad(users, ((0, pad), (0, 0))) if pad else users
    out = [_kth_block(u[i:i + block], items, k=k, dtype=dtype)
           for i in range(0, m + pad, block)]
    return jnp.concatenate(out)[:m]


@functools.partial(jax.jit, static_argnames=("dtype",))
def reverse_answers(users, s_k, qs, tie_eps, *, dtype=jnp.float32):
    """(c, m) bool audiences of the queries ``qs`` (c, d)."""
    eps = tie_eps * jnp.linalg.norm(qs, axis=-1)
    return s_k[None, :, 0] <= _ips(qs, users, dtype) + eps[:, None]


@jax.jit
def reverse_tally(users, s_k, qs, tie_eps, preds, valid):
    """Against the float32 reference, per chunk of answers ``preds`` (c, m)
    of which the rows ``valid`` (c,) count: (true positives, false
    positives, misses, widest miss margin).

    A miss's margin is how far inside the audience the user was: how far
    <u, q> + eps lies above the score of the item that would push q out
    of u's top k, relative to that score. The queries are items of the
    corpus, so for a user in the audience that item is the (k+1)-th: q
    itself is one of the top k."""
    eps = tie_eps * jnp.linalg.norm(qs, axis=-1)
    thr = _ips(qs, users, jnp.float32) + eps[:, None]
    truth = (s_k[None, :, 0] <= thr) & valid[:, None]
    edge = jnp.where(truth, s_k[None, :, 1], s_k[None, :, 0])
    margin = (thr - edge) / jnp.maximum(jnp.abs(edge), 1e-30)
    preds = preds & valid[:, None]
    missed = truth & ~preds
    return (jnp.sum(truth & preds), jnp.sum(~truth & preds),
            jnp.sum(missed), jnp.max(jnp.where(missed, margin, 0.0)))


@functools.partial(jax.jit, static_argnames=("k", "dtype"))
def forward_topk(items, qs, *, k, dtype=jnp.float32):
    """(values, ids) (c, k) of each query's top-k items."""
    return jax.lax.top_k(_ips(qs, items, dtype), k)


@jax.jit
def exact_scores(items, qs, ids):
    """(c, k) float32 scores of the items ``ids`` for each query, summed
    elementwise (no matrix unit, so no reduced-precision pass)."""
    return jnp.sum(items[ids] * qs[:, None, :], axis=-1)


def pack(pred) -> np.ndarray:
    return np.packbits(np.asarray(pred, dtype=bool))


def unpack(bits: np.ndarray, m: int) -> np.ndarray:
    return np.unpackbits(bits, count=m).astype(bool)
