"""Exact (brute-force) oracles for kMIPS and RkMIPS.

Used as ground truth for F1-scores and by property tests. Also the "Simpfer"
inner scan is exact; this module holds the fully dense versions.

Tie/semantics convention (shared by every method in this repo):
  q is in the kMIPS result of u over P u {q}  <=>  #{p in P : <u,p> > <u,q>} <= k-1.
Strictly-greater counting means ties resolve in favor of the query, matching
the paper's Definition 1 where q itself is inserted into the item set.

Every inner product runs at ``Precision.HIGHEST``: a TPU otherwise feeds f32
matmuls to the MXU in reduced-precision passes, and the reference would no
longer be exact there. On the CPU the results are unchanged.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def _ips(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a (p, d) x b (r, d) -> (p, r) inner products at full f32 precision."""
    return jnp.matmul(a, b.T, precision=_HIGHEST)


def kmips(items: jnp.ndarray, queries: jnp.ndarray, k: int
          ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact top-k MIPS. items (n,d), queries (q,d) -> (values, indices) (q,k)."""
    return jax.lax.top_k(_ips(queries, items), k)


def rkmips_decision(items: jnp.ndarray, users: jnp.ndarray,
                    query: jnp.ndarray, k: int,
                    tie_eps: float = 0.0) -> jnp.ndarray:
    """Exact RkMIPS for one query. -> bool (m,): q in kMIPS_k(u, P u {q}).

    tie_eps: items only "beat" tau when ip > tau + tie_eps * ||q||. With
    tie_eps = 0 this is the strict rule; a tiny tie_eps makes the decision
    robust to float accumulation-order noise when queries are drawn from the
    item set (the self-duplicate has ip == tau mathematically and must not
    count; see tests/test_sah_engine.py). Use the same tie_eps in the engine.
    """
    eps = tie_eps * jnp.linalg.norm(query)
    tau = jnp.matmul(users, query, precision=_HIGHEST)   # (m,)
    ips = _ips(users, items)                  # (m, n)
    beat = jnp.sum(ips > tau[:, None] + eps, axis=-1)
    return beat <= k - 1


def rkmips_batch(items: jnp.ndarray, users: jnp.ndarray,
                 queries: jnp.ndarray, k: int,
                 tie_eps: float = 0.0) -> jnp.ndarray:
    """Exact RkMIPS for a batch of queries -> bool (q, m)."""
    eps = tie_eps * jnp.linalg.norm(queries, axis=-1)     # (q,)
    tau = _ips(queries, users)                # (q, m)
    ips = _ips(users, items)                  # (m, n)
    beat = jnp.sum(ips[None, :, :] > tau[:, :, None] + eps[:, None, None],
                   axis=-1)
    return beat <= k - 1


def rkmips_batch_chunked(items: jnp.ndarray, users: jnp.ndarray,
                         queries: jnp.ndarray, k: int, chunk: int = 4096,
                         tie_eps: float = 0.0) -> jnp.ndarray:
    """Memory-bounded exact RkMIPS oracle (chunks users to avoid q*m*n blowup)."""
    m = users.shape[0]
    outs = []
    fn = jax.jit(rkmips_batch, static_argnames=("k", "tie_eps"))
    for lo in range(0, m, chunk):
        outs.append(fn(items, users[lo:lo + chunk], queries, k,
                       tie_eps=tie_eps))
    return jnp.concatenate(outs, axis=1)
