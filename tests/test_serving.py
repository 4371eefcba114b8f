"""Engine-level online serving (engine/serving.py) + padding hardening.

Pins the DESIGN.md SS8 contracts: (1) micro-batched serving answers are
identical to one-at-a-time engine queries — batching is a throughput knob,
never an accuracy knob; (2) the serving-state cache returns the identical
arrays on a hit and never rebuilds below capacity; (3) the dispatch
compiles exactly once per distinct batch size; (4) the sharding-layer
padding (``pad_index`` / ``pad_item_rows``) is bitwise-invisible after mask
stripping. The padding checks here are the hypothesis-free mirrors of
tests/test_core_properties.py, so they run on minimal installs too.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sa_alsh, sah
from repro.data import synthetic
from repro.dist.policy import NO_SHARDING
from repro.engine import (IndexArtifact, RetrievalServer, RkMIPSEngine,
                          ServingCache, ServingRuntime, build_serving_state,
                          get_config)
from repro.engine import serving
from repro.engine import sharding as eng_sharding
from repro.kernels import ops as kops


def _corpus():
    key = jax.random.PRNGKey(11)
    ki, kq = jax.random.split(key)
    items, _ = synthetic.recommendation_data(ki, 509, 16, 24)   # prime n
    queries = synthetic.queries_from_items(kq, items, 7)
    return items, queries


def _server_cfg():
    return get_config("sah").replace(tile=128, n_bits=64, serve_batch_size=4)


@pytest.fixture(scope="module")
def corpus():
    return _corpus()


@pytest.fixture(scope="module")
def server_cfg():
    return _server_cfg()


def test_microbatch_matches_one_at_a_time_engine_kmips(corpus, server_cfg):
    """7 queries through B=4 micro-batches == 7 single engine.kmips calls
    (exact scan: both paths recover the true top-k)."""
    items, queries = corpus
    cfg = server_cfg.replace(scan="exact")
    eng = RkMIPSEngine(cfg).build(items, None, jax.random.PRNGKey(3))
    srv = eng.server()
    tickets = srv.submit(queries)
    assert tickets == list(range(7)) and srv.pending == 7
    res = srv.flush(5)
    assert len(res) == 7 and srv.pending == 0
    for i, r in enumerate(res):
        one = eng.kmips(queries[i], 5)
        np.testing.assert_array_equal(np.asarray(r.ids), np.asarray(one.ids))
        np.testing.assert_allclose(np.asarray(r.values),
                                   np.asarray(one.values), rtol=1e-6)
        assert r.k == 5


def test_microbatch_bitwise_equals_oneshot(corpus, server_cfg):
    """Micro-batched sketch dispatch is bitwise the one-shot batched scan:
    per-query rows are independent and the zero-query padding is dead."""
    items, queries = corpus
    srv = RetrievalServer(items, jax.random.PRNGKey(4), config=server_cfg)
    state = srv.cache.get(server_cfg)
    ucodes = kops.srp_hash(queries, state.proj_q)
    v0, i0 = eng_sharding.kmips_flat_arrays(
        state.items, state.item_ids, state.item_mask, state.codes, ucodes,
        queries, 5, NO_SHARDING, n_cand=server_cfg.n_cand)
    srv.submit(queries)
    res = srv.flush(5)
    np.testing.assert_array_equal(
        np.stack([np.asarray(r.ids) for r in res]), np.asarray(i0))
    np.testing.assert_array_equal(
        np.stack([np.asarray(r.values) for r in res]), np.asarray(v0))
    # single-query convenience path agrees too
    one = srv.kmips(queries[2], 5)
    np.testing.assert_array_equal(np.asarray(one.ids), np.asarray(i0[2]))


def test_cache_hit_returns_identical_arrays_without_rebuild(corpus,
                                                            server_cfg):
    items, _ = corpus
    cache = ServingCache(items, jax.random.PRNGKey(5), capacity=2)
    s1 = cache.get(server_cfg)
    assert cache.builds == 1
    s2 = cache.get(server_cfg)
    assert s2 is s1 and cache.builds == 1          # hit: same arrays, no build
    assert s2.items is s1.items and s2.codes is s1.codes
    # serve/query-only knobs don't change the built arrays: same entry
    assert cache.get(server_cfg.replace(serve_batch_size=2,
                                        serve_cache_capacity=9,
                                        n_cand=128)) is s1
    assert cache.builds == 1
    # LRU eviction past capacity forces a rebuild on the evicted key
    cache.get(server_cfg.replace(n_bits=32))
    cache.get(server_cfg.replace(n_bits=96))       # evicts server_cfg
    assert len(cache) == 2 and cache.builds == 3
    assert server_cfg not in cache
    s3 = cache.get(server_cfg)
    assert cache.builds == 4 and s3 is not s1
    np.testing.assert_array_equal(np.asarray(s3.codes), np.asarray(s1.codes))


def test_cache_lru_eviction_order(corpus, server_cfg):
    """LRU semantics under capacity pressure: a get() refreshes recency, so
    the evictee is the least-recently-USED entry, not the oldest-built;
    ``builds`` counts exactly the misses; query-only config changes share
    one entry (and refresh it)."""
    items, _ = corpus
    cache = ServingCache(items, jax.random.PRNGKey(21), capacity=2)
    cfg_a = server_cfg                                  # three distinct
    cfg_b = server_cfg.replace(n_bits=32)               # index recipes
    cfg_c = server_cfg.replace(n_bits=96)
    sa = cache.get(cfg_a)
    cache.get(cfg_b)
    assert len(cache) == 2 and cache.builds == 2
    # touch A via a query-only variant: same entry, recency refreshed
    assert cache.get(cfg_a.replace(n_cand=128, serve_batch_size=2)) is sa
    assert cache.builds == 2
    cache.get(cfg_c)                                    # evicts B, not A
    assert len(cache) == 2 and cache.builds == 3
    assert cfg_a in cache and cfg_c in cache and cfg_b not in cache
    assert cache.get(cfg_a) is sa and cache.builds == 3
    cache.get(cfg_b)                                    # miss: rebuild,
    assert cache.builds == 4                            # evicts C (LRU)
    assert cfg_c not in cache and cfg_a in cache
    # put() of a pre-built state counts no build and obeys capacity
    cache.put(cfg_c, build_serving_state(items, jax.random.PRNGKey(21),
                                         cfg_c))
    assert cache.builds == 4 and len(cache) == 2        # put counts no miss
    assert cfg_a not in cache                           # A was LRU by then
    with pytest.raises(ValueError, match=r"capacity must be >= 1"):
        ServingCache(items, jax.random.PRNGKey(21), capacity=0)


def test_server_ranks_with_engine_codes(corpus, server_cfg):
    """engine.server() must scan with the identical SRP codes as
    engine.kmips(), whether the engine's kMIPS index was built eagerly
    (users=None), lazily, or not at all yet — and a server seeded from an
    already-built index performs no build of its own."""
    items, queries = corpus
    eng = RkMIPSEngine(server_cfg).build(items, None, jax.random.PRNGKey(3))
    srv = eng.server()                             # index built eagerly
    assert srv.cache.builds == 0                   # seeded, not rebuilt
    state = srv.cache.get(server_cfg)
    assert srv.cache.builds == 0
    np.testing.assert_array_equal(np.asarray(state.codes),
                                  np.asarray(eng.kmips_index.codes))
    # sketch-scan answers agree with the engine's flat sharded path
    one = srv.kmips(queries[0], 5, n_cand=64)
    ref = eng.kmips(queries[0], 5, n_cand=509)     # full depth: exact
    assert set(np.asarray(one.ids)) <= set(range(items.shape[0]))
    np.testing.assert_array_equal(np.asarray(one.ids[:1]),
                                  np.asarray(ref.ids[:1]))
    # not-yet-materialized index: the server builds with the same key,
    # so the codes still match the engine's lazily-built index
    eng2 = RkMIPSEngine(server_cfg).build(items, items[:8],
                                          jax.random.PRNGKey(3))
    srv2 = eng2.server()
    assert srv2.cache.builds == 0 and server_cfg not in srv2.cache
    state2 = srv2.cache.get(server_cfg)            # built by the server
    assert srv2.cache.builds == 1
    np.testing.assert_array_equal(np.asarray(state2.codes)[:509],
                                  np.asarray(eng2.kmips_index.codes)[:509])


def test_flush_failures_keep_tickets(corpus, server_cfg):
    """An empty flush is free (no state build); a failed flush (bad k)
    consumes nothing — a retry answers every ticket."""
    items, queries = corpus
    srv = RetrievalServer(items, jax.random.PRNGKey(12), config=server_cfg)
    assert srv.flush(5) == [] and srv.cache.builds == 0
    srv.submit(queries[:2])
    # bound is the REAL corpus size (509), not the padded row count (512):
    # k=510 would otherwise return phantom (-1, -inf) tail entries
    with pytest.raises(ValueError, match=r"k=510 outside \[1, 509\]"):
        srv.flush(510)
    assert srv.pending == 2                        # queue survived the error
    res = srv.flush(5)
    assert len(res) == 2 and srv.pending == 0
    # a config swapped between flushes brings its own batch size
    srv.config = server_cfg.replace(serve_batch_size=2)
    assert srv.batch_size == 2
    srv.submit(queries[:3])
    assert len(srv.flush(5)) == 3


def test_seeded_and_rebuilt_states_agree():
    """A state seeded from the engine's index and one rebuilt by the cache
    (same key, same recipe) are interchangeable — identical shapes and
    codes even when the corpus is smaller than the config tile."""
    key = jax.random.PRNGKey(13)
    items = jax.random.normal(key, (50, 16))       # corpus < default tile
    cfg = get_config("sah").replace(n_bits=64, serve_cache_capacity=1)
    eng = RkMIPSEngine(cfg).build(items, None, key)
    srv = eng.server()
    seeded = srv.cache.get(cfg)
    assert srv.cache.builds == 0
    srv.cache.get(cfg.replace(n_bits=32))          # capacity 1: evicts seed
    rebuilt = srv.cache.get(cfg)                   # cache builds its own
    assert srv.cache.builds == 2
    assert rebuilt.items.shape == seeded.items.shape
    np.testing.assert_array_equal(np.asarray(rebuilt.codes),
                                  np.asarray(seeded.codes))
    np.testing.assert_array_equal(np.asarray(rebuilt.item_ids),
                                  np.asarray(seeded.item_ids))


def test_kmips_rejects_batch_without_enqueuing(corpus, server_cfg):
    items, queries = corpus
    srv = RetrievalServer(items, jax.random.PRNGKey(8), config=server_cfg)
    srv.submit(queries[0])
    with pytest.raises(ValueError, match=r"kmips serves one query"):
        srv.kmips(queries[:3], 5)
    assert srv.pending == 1                        # rejected rows not queued
    res = srv.flush(5)
    assert len(res) == 1


def test_submit_validates_queries_up_front(corpus, server_cfg):
    """Malformed queries are rejected AT SUBMIT with message-asserted
    ValueErrors — never enqueued, so they can't strand a later flush
    (which, by the retry contract, would leave the whole batch pending)."""
    items, queries = corpus
    srv = RetrievalServer(items, jax.random.PRNGKey(15), config=server_cfg)
    with pytest.raises(ValueError, match=r"submit: queries must have a "
                                         r"floating dtype, got int32"):
        srv.submit(np.ones((2, 24), np.int32))
    with pytest.raises(ValueError, match=r"submit: queries must be one row "
                                         r"\(d,\) or a block \(nq, d\), "
                                         r"got shape \(2, 3, 24\)"):
        srv.submit(np.ones((2, 3, 24), np.float32))
    with pytest.raises(ValueError, match=r"submit: query dimensionality 23 "
                                         r"!= corpus dimensionality 24"):
        srv.submit(np.ones((23,), np.float32))
    assert srv.pending == 0                        # nothing leaked in
    srv.submit(queries[0])                         # good rows still pass
    assert srv.pending == 1 and len(srv.flush(5)) == 1


def test_reverse_submit_validates_queries_up_front(reverse_engine):
    eng, queries = reverse_engine
    srv = eng.reverse_server()
    with pytest.raises(ValueError, match=r"floating dtype"):
        srv.submit(np.ones((2, 16), np.int64))
    with pytest.raises(ValueError, match=r"query dimensionality 8 != "
                                         r"corpus dimensionality 16"):
        srv.submit(np.ones((8,), np.float32))
    assert srv.pending == 0
    srv.submit(queries[0])
    assert srv.pending == 1 and len(srv.flush(3)) == 1


def test_one_compile_per_batch_size(corpus, server_cfg):
    items, queries = corpus
    srv = RetrievalServer(items, jax.random.PRNGKey(6), config=server_cfg)
    srv.submit(queries[:3])                        # partial batch (padded)
    srv.flush(5)
    assert srv.compile_count == 1
    srv.submit(queries)                            # 7 = full + partial batch
    srv.flush(5)
    srv.submit(queries[0])
    srv.flush(5)
    assert srv.compile_count == 1                  # every dispatch is (4, d)
    srv2 = RetrievalServer(items, jax.random.PRNGKey(6),
                           config=server_cfg.replace(serve_batch_size=2))
    srv2.submit(queries[:5])
    srv2.flush(5)
    assert srv2.compile_count == 1                 # its own (2, d) executable


def test_serving_state_invariants(corpus, server_cfg):
    """Padded rows are dead (-1 ids, mask off); real ids cover the corpus."""
    items, _ = corpus
    state = build_serving_state(items, jax.random.PRNGKey(7), server_cfg)
    ids = np.asarray(state.item_ids)
    mask = np.asarray(state.item_mask)
    assert state.n_items == items.shape[0]
    np.testing.assert_array_equal(np.sort(ids[mask]),
                                  np.arange(items.shape[0]))
    assert (ids[~mask] == -1).all()
    assert not np.asarray(state.items)[~mask].any()


# ---------------------------------------------------------------------------
# Reverse (RkMIPS) serving: a ticket queue over the batched plan/execute
# dispatch (DESIGN.md SS9) — batching is a throughput knob, never an
# accuracy knob, and serving adds no executables of its own.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def reverse_engine():
    key = jax.random.PRNGKey(19)
    ki, ku, kq = jax.random.split(key, 3)
    items, users = synthetic.recommendation_data(ki, 384, 512, 16)
    queries = synthetic.queries_from_items(kq, items, 7)
    cfg = get_config("sah").replace(tile=64, n_bits=32, k_max=8, n_top=8,
                                    serve_batch_size=4)
    eng = RkMIPSEngine(cfg).build(items, users, ku)
    return eng, queries


def test_reverse_microbatch_bitwise_equals_oneshot(reverse_engine):
    """7 tickets through B=4 micro-batches == the matching rows of one
    7-query query_batch — work-queue lanes are independent and the
    repeat-padding rows are discarded."""
    eng, queries = reverse_engine
    ref = eng.query_batch(queries, 3)
    srv = eng.reverse_server()
    tickets = srv.submit(queries)
    assert tickets == list(range(7)) and srv.pending == 7
    res = srv.flush(3)
    assert len(res) == 7 and srv.pending == 0
    for i, r in enumerate(res):
        np.testing.assert_array_equal(np.asarray(r.predictions),
                                      np.asarray(ref.predictions[i]))
        assert int(r.stats.n_scan) == int(ref.stats.n_scan[i])
        assert r.k == 3
    # single-query convenience path agrees too
    one = srv.rkmips(queries[2], 3)
    np.testing.assert_array_equal(np.asarray(one.predictions),
                                  np.asarray(ref.predictions[2]))


def test_reverse_server_shares_engine_executables(reverse_engine):
    """Every reverse flush dispatches at the serve batch size: one compile
    per distinct (batch size, k), shared with the engine — the server owns
    no dispatch of its own."""
    key = jax.random.PRNGKey(29)
    ki, ku = jax.random.split(key)
    items, users = synthetic.recommendation_data(ki, 256, 256, 16)
    cfg = get_config("sah").replace(tile=64, n_bits=32, k_max=8, n_top=8,
                                    serve_batch_size=4)
    eng = RkMIPSEngine(cfg).build(items, users, ku)
    srv = eng.reverse_server()
    srv.submit(items[:3])                  # partial batch (padded to 4)
    srv.flush(3)
    assert srv.compile_count == 1
    srv.submit(items[:7])                  # full + partial batch
    srv.flush(3)
    srv.submit(items[0])
    srv.flush(3)
    assert srv.compile_count == 1          # every dispatch is (4, d)
    assert srv.batch_size == 4
    # a one-shot engine batch of the same size reuses the same executable
    eng.query_batch(items[:4], 3)
    assert eng.rkmips_compile_count == 1


def test_reverse_flush_failures_keep_tickets(reverse_engine):
    eng, queries = reverse_engine
    srv = eng.reverse_server()
    assert srv.flush(3) == []
    srv.submit(queries[:2])
    with pytest.raises(ValueError, match=r"outside \[1, k_max=8\]"):
        srv.flush(9)                       # k > k_max: nothing consumed
    assert srv.pending == 2
    assert len(srv.flush(3)) == 2 and srv.pending == 0
    with pytest.raises(ValueError, match=r"rkmips serves one query"):
        srv.rkmips(queries[:2], 3)
    assert srv.pending == 0


def test_reverse_server_requires_user_side_build():
    key = jax.random.PRNGKey(31)
    items = jax.random.normal(key, (64, 8))
    eng = RkMIPSEngine(get_config("sah").replace(tile=32, n_bits=32)
                       ).build(items, None, key)
    with pytest.raises(RuntimeError, match=r"not built for RkMIPS"):
        eng.reverse_server()


# ---------------------------------------------------------------------------
# Padding equivalence, hypothesis-free mirrors (fixed non-divisible sizes).
# The drawn-size versions live in tests/test_core_properties.py.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,n,shards", [(53, 97, 3), (101, 67, 5),
                                        (96, 128, 7)])
def test_pad_index_rkmips_equivalence(m, n, shards):
    key = jax.random.PRNGKey(m + n + shards)
    ki, ku, kq, kb = jax.random.split(key, 4)
    items = jax.random.normal(ki, (n, 8))
    users = jax.random.normal(ku, (m, 8))
    q = jax.random.normal(kq, (8,)) * 2.0
    idx = sah.build(items, users, kb, k_max=4, n_top=4, tile=32,
                    leaf_size=8, n_bits=32)
    pidx = eng_sharding.pad_index(idx, shards)
    assert pidx.n_blocks % shards == 0
    for scan in ("sketch", "exact"):
        p0, s0 = sah.rkmips(idx, q, 3, n_cand=16, scan=scan)
        p1, s1 = sah.rkmips(pidx, q, 3, n_cand=16, scan=scan)
        np.testing.assert_array_equal(
            np.asarray(sah.predictions_to_original(idx, p0, m)),
            np.asarray(sah.predictions_to_original(pidx, p1, m)))
        for f in ("blocks_alive", "users_alive", "n_no_lb", "n_yes_norm",
                  "n_scan"):
            assert int(getattr(s0, f)) == int(getattr(s1, f)), (scan, f)
    # dead padding: each original id exactly once among unmasked rows
    ids = np.asarray(pidx.user_ids)[np.asarray(pidx.user_mask)]
    np.testing.assert_array_equal(np.sort(ids), np.arange(m))


@pytest.mark.parametrize("n,shards,k", [(97, 3, 5), (53, 7, 2), (64, 5, 1)])
def test_pad_item_rows_flat_scan_equivalence(n, shards, k):
    key = jax.random.PRNGKey(n * shards + k)
    ki, kq, kb = jax.random.split(key, 3)
    items = jax.random.normal(ki, (n, 12))
    queries = jax.random.normal(kq, (3, 12))
    idx = sa_alsh.build_index(items, kb, n_bits=32, tile=32)
    uc = sa_alsh.user_codes(idx, queries)
    padded = eng_sharding.pad_item_rows(idx.items, idx.item_ids,
                                        idx.item_mask, idx.codes, shards, k)
    assert padded[0].shape[0] % shards == 0
    assert padded[0].shape[0] // shards >= k
    for scan in ("sketch", "exact"):
        v0, i0 = eng_sharding.kmips_flat_arrays(
            idx.items, idx.item_ids, idx.item_mask, idx.codes, uc, queries,
            k, NO_SHARDING, n_cand=256, scan=scan)
        v1, i1 = eng_sharding.kmips_flat_arrays(*padded, uc, queries, k,
                                                NO_SHARDING, n_cand=256,
                                                scan=scan)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))


def test_result_mapping_drops_phantom_ids():
    """A phantom id (out of [0, n_users)) on a padding row must be dropped
    by predictions_to_original, never clamped onto a real user."""
    key = jax.random.PRNGKey(9)
    ki, ku, kb = jax.random.split(key, 3)
    items = jax.random.normal(ki, (40, 8))
    users = jax.random.normal(ku, (17, 8))
    idx = sah.build(items, users, kb, k_max=4, n_top=4, tile=32,
                    leaf_size=8, n_bits=32)
    pidx = eng_sharding.pad_index(idx, 5)
    m_pad = pidx.n_users
    # corrupt every padded (masked-off) slot with phantom ids AND force the
    # mask on, simulating a broken alternate padding convention
    pad_rows = jnp.arange(idx.n_users, m_pad)
    bad = pidx._replace(
        user_ids=pidx.user_ids.at[pad_rows].set(-1),
        user_mask=pidx.user_mask.at[pad_rows].set(True))
    all_yes = jnp.ones((m_pad,), bool)
    out = sah.predictions_to_original(bad, all_yes, 17)
    ref = sah.predictions_to_original(idx, jnp.ones((idx.n_users,), bool), 17)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ---------------------------------------------------------------------------
# One device-to-host copy per forward micro-batch: every answer is a
# read-only numpy row of it, on every path that serves through
# ``_flush_batch``.
# ---------------------------------------------------------------------------

# a zero-padded partial batch at every group length below the full batch
# (serve_batch_size 4): one stack-and-pad program each
_PARTIAL = {"partial1": 1, "partial2": 2, "partial": 3}


def _host_copy_server(case: str, items, policy=NO_SHARDING):
    """(server, tickets in one micro-batch) for one host-copy case."""
    cfg = _server_cfg()
    key = jax.random.PRNGKey(4)
    if case == "delta":
        art = IndexArtifact.build(items, None, key,
                                  config=cfg.replace(delta_capacity=8))
        # staged rows scaled up so they rank, and deletes on both sides
        art = art.insert_items(items[:3] * 1.5).delete_items(
            [0, 7, art.n_base + 1])
        return RetrievalServer.from_artifact(art), 3
    if case == "rung":
        return RetrievalServer(items, key, policy=policy,
                               config=cfg.replace(serve_buckets=(2,))), 2
    return (RetrievalServer(items, key, config=cfg, policy=policy),
            _PARTIAL.get(case, cfg.serve_batch_size))


def _check_host_answers(srv: RetrievalServer, queries, k: int) -> None:
    """``queries`` (one micro-batch) served by ``_flush_batch`` at their
    ladder rung, by the synchronous ``flush`` (full-batch padding), by
    ``kmips`` and by a ``ServingRuntime``: every answer's values and ids
    are read-only ``np.ndarray`` rows, bitwise the matching row of a
    direct ``_dispatch`` call (delta-merged where rows are staged)."""
    n = queries.shape[0]
    pad_to = srv.bucket_for(n)
    qs = jnp.concatenate([queries, jnp.zeros((pad_to - n, queries.shape[1]),
                                             queries.dtype)])
    state = srv.cache.get(srv.config)
    vals, ids = srv._dispatch(state.items, state.item_ids,
                              srv._masked_item_mask(state), state.codes,
                              state.proj_q, qs, k=k,
                              n_cand=srv.config.n_cand, scan=srv.config.scan)
    if srv._delta[0] is not None:
        vals, ids = srv._merge(vals, ids, qs, *srv._delta, k=k,
                               n_base=srv.artifact.n_base,
                               scan_precision=srv.config.scan_precision)
    want = (np.asarray(vals), np.asarray(ids))

    answers = {"rung": srv._flush_batch(list(queries), k, pad_to=pad_to)}
    srv.submit(queries)
    answers["flush"] = srv.flush(k)
    answers["kmips"] = [srv.kmips(queries[0], k)]
    rt = ServingRuntime(srv, k=k)
    try:
        answers["runtime"] = [t.result(timeout=60)
                              for t in rt.submit(queries)]
    finally:
        rt.close()
    for path, res in answers.items():
        assert len(res) == (1 if path == "kmips" else n), path
        for j, r in enumerate(res):
            assert r.k == k
            for got, ref in zip((r.values, r.ids), want):
                assert type(got) is np.ndarray, (path, type(got))
                assert not got.flags.writeable, path
                assert got.dtype == ref.dtype, path
                np.testing.assert_array_equal(got, ref[j], err_msg=path)


_MESH_HOST_COPY_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, "tests")
import jax
from repro.dist.policy import ShardingPolicy
import test_serving as ts

policy = ShardingPolicy(mesh=jax.make_mesh((2, 4), ("data", "model")),
                        rules={})
items, queries = ts._corpus()
srv, n = ts._host_copy_server("full", items, policy=policy)
assert srv.cache.get(srv.config).items.sharding.num_devices == 8
ts._check_host_answers(srv, queries[:n], 5)
print("MESH HOST COPY OK")
"""


@pytest.mark.parametrize("case", ["full", "partial1", "partial2", "partial",
                                  "rung", "delta", "mesh8"])
def test_forward_answers_are_host_rows_of_one_copy(corpus, case):
    """Forward answers are read-only host rows, bitwise the direct
    dispatch's, on a full batch, a zero-padded partial batch of every
    length below it, a ladder rung, an artifact with staged inserts and
    deletes, and the 8-device CPU mesh (subprocess: the device count is
    fixed at JAX start-up)."""
    if case == "mesh8":
        env = dict(os.environ, PYTHONPATH="src")
        out = subprocess.run([sys.executable, "-c", _MESH_HOST_COPY_SCRIPT],
                             env=env, capture_output=True, text=True,
                             timeout=600,
                             cwd=os.path.dirname(os.path.dirname(__file__)))
        assert out.returncode == 0, out.stdout + "\n" + out.stderr
        assert "MESH HOST COPY OK" in out.stdout
        return
    items, queries = corpus
    srv, n = _host_copy_server(case, items)
    if case == "delta":
        assert srv._delta[0] is not None and srv._deleted is not None
    _check_host_answers(srv, queries[:n], 5)


@pytest.mark.parametrize("n,buckets", [(1, ()), (3, ()), (4, ()),
                                       (2, (2,))],
                         ids=["1", "3", "full", "rung"])
def test_full_batch_flush_uploads_nothing(corpus, server_cfg, n, buckets):
    """A forward ``_flush_batch`` sends nothing from the host to the
    device, full batch, partial batch or ladder rung alike: no per-ticket
    device op, so no row index to upload, and the zero padding is a
    constant of the compiled stack-and-pad program."""
    items, queries = corpus
    srv = RetrievalServer(items, jax.random.PRNGKey(4),
                          config=server_cfg.replace(serve_buckets=buckets))
    group = [queries[j] for j in range(n)]
    pad_to = srv.bucket_for(n)
    srv._flush_batch(group, 5, pad_to=pad_to)   # compile outside the guard
    with jax.transfer_guard_host_to_device("disallow"):
        res = srv._flush_batch(group, 5, pad_to=pad_to)
    assert len(res) == n


def test_warm_flush_compiles_nothing_at_any_group_size(corpus, server_cfg,
                                                       fresh_compiles):
    """After ``warmup``, a forward ``_flush_batch`` of every group size
    1..batch_size runs only executables that exist: no backend compile,
    the stack-and-pad program's included."""
    items, queries = corpus
    srv = RetrievalServer(items, jax.random.PRNGKey(4), config=server_cfg)
    assert srv.warmup((5,)) == 1
    compiles = []

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        for n in range(1, srv.batch_size + 1):
            srv._flush_batch([queries[j] for j in range(n)], 5)
            assert not compiles, f"group of {n} compiled"
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


@pytest.mark.parametrize("n", [1, 3, 4])
@pytest.mark.parametrize("dtypes", [("float32",), ("bfloat16",),
                                    ("bfloat16", "float32")],
                         ids=["float32", "bfloat16", "mixed"])
def test_pad_batch_is_the_eager_stack_and_pad(corpus, n, dtypes):
    """``serving._pad_batch`` returns bitwise what the eager stack and
    zero pad gives — values, dtype (promotion included), shape and
    placement — for a partial and a full batch."""
    _, queries = corpus
    rows = [queries[j].astype(dtypes[j % len(dtypes)]) for j in range(n)]
    got = serving._pad_batch(tuple(rows), pad_to=4)
    stacked = jnp.stack(rows)
    want = jnp.concatenate(
        [stacked, jnp.zeros((4 - n, stacked.shape[1]), stacked.dtype)])
    assert got.dtype == want.dtype
    assert got.shape == want.shape == (4, queries.shape[1])
    assert got.sharding == want.sharding
    assert got.committed == want.committed
    np.testing.assert_array_equal(np.asarray(got).view(np.uint8),
                                  np.asarray(want).view(np.uint8))
