"""The seeded surrogate corpus, made on the device in one jitted call.

A copy of the program's ``data/synthetic.py::recommendation_data`` (NMF
factor products with a long-tailed norm), kept here so that the
yardstick's data cannot move when the program does. One departure: the
low-rank product runs at ``Precision.HIGHEST``, so the corpus is the same
float32 numbers on every backend rather than whatever the chip's default
matmul precision makes of it. ``tests/test_rkbench_corpus.py`` pins the
output at a tiny size to a stored checksum.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def root_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (more than 32 bits)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _factors(key, n, d, rank, h, noise, skew):
    k1, _, k3, k4 = jax.random.split(key, 4)
    w = jnp.abs(jax.random.normal(k1, (n, rank)))
    x = (jnp.matmul(w, h, precision=_HIGHEST) / rank
         + noise * jnp.abs(jax.random.normal(k3, (n, d))))
    scale = jnp.exp(skew * jax.random.normal(k4, (n, 1)))
    return (x * scale).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("n_items", "m_users", "d",
                                             "rank", "noise", "skew"))
def make(key: jax.Array, *, n_items: int, m_users: int, d: int,
         rank: int = 16, noise: float = 1.0, skew: float = 0.1):
    """(items (n, d), users (m, d)) sharing one item-factor basis."""
    ki, ku, kh = jax.random.split(key, 3)
    h = jnp.abs(jax.random.normal(kh, (rank, d)))
    return (_factors(ki, n_items, d, rank, h, noise, skew),
            _factors(ku, m_users, d, rank, h, noise, skew))
