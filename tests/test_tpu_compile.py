"""Compile the main-path Pallas kernels for a TPU v5e at real widths.

Interpret mode (tests/test_kernels.py) accepts unaligned blocks and shapes
that the chip's compiler refuses. These tests lower the exact functions the
chip runs -- the ``*_pallas`` paths of ``kernels/ops.py``, padding and all
-- against a described ``v5e:2x2`` topology, with no chip attached, and
check that each kernel survives as a named ``tpu_custom_call``.

Shapes are the chip smoke's deployment (chip_smoke.py): the Netflix Prize
rating matrix, 480,189 users x 17,770 items at d = 64, 128-bit codes
(W = 4), execute chunks of 256 user lanes over 512-item tiles. ``ip_topk``
(exact top-k, off the served path) is compiled at the same widths.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as kops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, kernel, *shapes, one_chip):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert any("tpu_custom_call" in line and f"%{kernel}" in line
               for line in text.splitlines()), kernel
    print(kernel, [s for s, _ in shapes], compiled.memory_analysis())


@pytest.mark.parametrize("n,d", [(480_189, 64), (17_770, 65)],
                         ids=["users", "items"])
def test_srp_hash_compiles(n, d, one_chip):
    _compile(functools.partial(kops.srp_pallas, interpret=False), "srp_hash",
             ((n, d), jnp.float32), ((d, 128), jnp.float32),
             one_chip=one_chip)


@pytest.mark.parametrize("q,n", [(256, 512), (1, 17_770)],
                         ids=["execute_tile", "forward_slab"])
def test_hamming_scores_compiles(q, n, one_chip):
    _compile(functools.partial(kops.hamming_pallas, interpret=False),
             "hamming_scores", ((q, 4), jnp.uint32), ((n, 4), jnp.uint32),
             one_chip=one_chip)


def test_ip_topk_compiles(one_chip):
    # exact top-50 of a serving micro-batch over the whole item slab
    _compile(functools.partial(kops.ip_topk_pallas, k=50, interpret=False),
             "ip_topk", ((8, 64), jnp.float32), ((17_770, 64), jnp.float32),
             one_chip=one_chip)


def test_fused_scan_compiles(one_chip):
    c, t, w, d = 256, 512, 4, 64
    _compile(functools.partial(kops.fused_scan_pallas, n_cand=64,
                               interpret=False), "fused_scan",
             ((c, w), jnp.uint32), ((t, w), jnp.uint32), ((t,), jnp.bool_),
             ((t, d), jnp.int8), ((t,), jnp.float32), ((c, d), jnp.float32),
             one_chip=one_chip)
