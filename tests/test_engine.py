"""repro.engine: registry parity, facade behaviour, sharded equivalence.

The engine is the only public (R)kMIPS surface; these tests pin its three
contracts: (1) every registry preset is *exactly* the raw core path with the
equivalent kwargs — bit for bit; (2) predictions come back in original
user-id space and match the exact oracle; (3) a mesh policy changes the
execution layout, never the answer (subprocess on an 8-device host mesh).
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine as engine_mod
from repro.core import exact, metrics, sah
from repro.data import synthetic
from repro.engine import EngineConfig, RkMIPSEngine, get_config


@pytest.fixture(scope="module")
def workload():
    key = jax.random.PRNGKey(5)
    ki, kq = jax.random.split(key)
    items, users = synthetic.recommendation_data(ki, 1024, 2048, 32)
    queries = synthetic.queries_from_items(kq, items, 4)
    return items, users, queries


def test_config_is_frozen_and_hashable():
    cfg = get_config("sah")
    with pytest.raises(Exception):
        cfg.scan = "exact"
    assert cfg == EngineConfig()
    assert len({get_config(m) for m in engine_mod.method_names()}) == 6
    assert cfg.replace(scan="exact") == get_config("exact")


def test_config_validation():
    with pytest.raises(ValueError):
        EngineConfig(transform="nope")
    with pytest.raises(ValueError):
        EngineConfig(blocking="tree")
    with pytest.raises(ValueError):
        EngineConfig(scan="hash")
    with pytest.raises(ValueError):
        EngineConfig(b=1.5)
    with pytest.raises(ValueError):
        EngineConfig(n_bits=100)
    with pytest.raises(ValueError):
        EngineConfig(n_top=10, k_max=50)
    with pytest.raises(KeyError):
        get_config("unknown-method")


def test_registry_matrix():
    """The registry encodes exactly the DESIGN.md SS3 baseline matrix."""
    rows = {m: (c.blocking, c.transform, c.scan)
            for m, c in ((m, get_config(m))
                         for m in engine_mod.PAPER_BASELINES)}
    assert rows == {
        "sah": ("cone", "sat", "sketch"),
        "sa-simpfer": ("norm", "sat", "sketch"),
        "h2-cone": ("cone", "qnf", "sketch"),
        "h2-simpfer": ("norm", "qnf", "sketch"),
        "simpfer": ("norm", "sat", "exact"),
    }
    assert engine_mod.display_name("h2-cone") == "H2-Cone"
    # display names round-trip through the case-insensitive lookup
    for m in engine_mod.method_names():
        assert get_config(engine_mod.display_name(m)) == get_config(m)


@pytest.mark.parametrize("method", ["sah", "sa-simpfer", "h2-cone",
                                    "h2-simpfer", "simpfer", "exact"])
def test_registry_parity_with_raw_core(workload, method):
    """Engine preset == sah.build + sah.rkmips_batch with the equivalent raw
    kwargs, bit for bit (same key, same knobs, same user-space mapping)."""
    items, users, queries = workload
    key = jax.random.PRNGKey(1)
    k = 10
    cfg = get_config(method).replace(tile=256, n_bits=64)

    eng = RkMIPSEngine(cfg).build(items, users, key)
    res = eng.query_batch(queries, k)

    idx = sah.build(items, users, key, **cfg.build_kwargs())
    pred, _ = sah.rkmips_batch(idx, queries, k, **cfg.query_kwargs())
    po = sah.predictions_to_original(idx, pred, users.shape[0])
    np.testing.assert_array_equal(np.asarray(res.predictions),
                                  np.asarray(po))


def test_engine_f1_vs_exact_smoke(workload):
    """Engine-level F1 against its own oracle on the synthetic workload."""
    items, users, queries = workload
    eng = RkMIPSEngine("sah").build(items, users, jax.random.PRNGKey(2))
    res = eng.query_batch(queries, 10)
    truth = eng.oracle(queries, 10)
    assert res.predictions.shape == truth.shape == (4, users.shape[0])
    f1 = float(jnp.mean(metrics.f1_score(res.predictions, truth)))
    assert f1 > 0.9, f1
    assert res.seconds > 0 and res.k == 10
    # the "exact" preset must reach F1 == 1 exactly (linear scan)
    eng_x = RkMIPSEngine("exact").build(items, users, jax.random.PRNGKey(2))
    rx = eng_x.query_batch(queries, 10)
    np.testing.assert_array_equal(np.asarray(rx.predictions),
                                  np.asarray(eng_x.oracle(queries, 10)))


def test_query_single_matches_batch(workload):
    items, users, queries = workload
    eng = RkMIPSEngine("sah").build(items, users, jax.random.PRNGKey(3))
    batch = eng.query_batch(queries, 5)
    single = eng.query(queries[0], 5)
    assert single.predictions.shape == (users.shape[0],)
    np.testing.assert_array_equal(np.asarray(single.predictions),
                                  np.asarray(batch.predictions[0]))


def test_k_and_lifecycle_guards(workload):
    items, users, queries = workload
    eng = RkMIPSEngine(get_config("sah").replace(k_max=20))
    with pytest.raises(RuntimeError):
        eng.query(queries[0], 5)        # not built
    with pytest.raises(RuntimeError):
        eng.oracle(queries, 5)
    eng.build(items, users, jax.random.PRNGKey(4))
    with pytest.raises(ValueError):
        eng.query(queries[0], 21)       # k > k_max
    with pytest.raises(ValueError):
        eng.query(queries[0], 0)
    # kMIPS-only engine: forward queries fine, reverse queries guarded
    eng_k = RkMIPSEngine("sah").build(items, None, jax.random.PRNGKey(4))
    assert eng_k.kmips(queries[0], 5).ids.shape == (5,)
    with pytest.raises(RuntimeError):
        eng_k.query(queries[0], 5)


def test_error_messages(workload):
    """Engine error paths raise actionable, message-stable exceptions:
    unknown preset, k outside [1, k_max], querying before build."""
    items, users, queries = workload
    with pytest.raises(KeyError,
                       match=r"unknown engine method 'no-such-method'; "
                             r"known: .*sah"):
        get_config("no-such-method")
    with pytest.raises(TypeError, match=r"config must be an EngineConfig "
                                        r"or a registry name"):
        RkMIPSEngine(42)

    eng = RkMIPSEngine(get_config("sah").replace(k_max=20))
    for call in (lambda: eng.query(queries[0], 5),
                 lambda: eng.query_batch(queries, 5)):
        with pytest.raises(RuntimeError,
                           match=r"engine not built for RkMIPS: call "
                                 r"build\(items, users, key\) first"):
            call()
    with pytest.raises(RuntimeError, match=r"engine not built for RkMIPS"):
        eng.oracle(queries, 5)
    for call in (lambda: eng.kmips(queries[0], 5), lambda: eng.server()):
        with pytest.raises(RuntimeError,
                           match=r"engine not built: call "
                                 r"build\(items, users, key\) first"):
            call()

    eng.build(items[:256], users[:256], jax.random.PRNGKey(10))
    with pytest.raises(ValueError,
                       match=r"k=21 outside \[1, k_max=20\] supported by "
                             r"this index; rebuild with a larger k_max"):
        eng.query(queries[0], 21)
    with pytest.raises(ValueError, match=r"k=0 outside \[1, k_max=20\]"):
        eng.query_batch(queries, 0)


def test_rebuild_resets_state(workload):
    """A second build() must drop every artifact of the first — serving a
    stale kMIPS index or user-side arrays would be silently wrong."""
    items, users, queries = workload
    eng = RkMIPSEngine("sah").build(items, users, jax.random.PRNGKey(8))
    eng.kmips(queries[0], 5)                  # materialize the lazy index
    first_kmips = eng.kmips_index
    eng.build(items[:512], users[:512], jax.random.PRNGKey(9))
    assert eng.n_users == 512
    assert eng.kmips_index is not first_kmips
    assert eng.kmips_index.item_mask.shape[0] >= 512
    assert eng.query(queries[0], 5).predictions.shape == (512,)
    # kMIPS-only rebuild drops the user side entirely
    eng.build(items, None, jax.random.PRNGKey(8))
    with pytest.raises(RuntimeError):
        eng.query(queries[0], 5)


def test_kmips_recall(workload):
    """Forward kMIPS through the facade: recall against the exact top-k."""
    items, users, queries = workload
    eng = RkMIPSEngine("sah").build(items, None, jax.random.PRNGKey(6))
    k = 10
    res = eng.kmips(queries, k, n_cand=128)
    _, ti = exact.kmips(items, queries, k)
    rec = float(jnp.mean(metrics.recall_at_k(res.ids, ti)))
    assert rec > 0.8, rec
    assert res.values.shape == (4, k)
    # values are the actual inner products of the returned ids, descending
    ips = jnp.take_along_axis(queries @ items.T, res.ids, axis=-1)
    np.testing.assert_allclose(np.asarray(res.values), np.asarray(ips),
                               rtol=1e-5)
    assert bool(jnp.all(res.values[:, :-1] >= res.values[:, 1:]))


def test_serving_codes_row_order():
    """Artifact serving_codes returns sketches in *input* row order: row
    i's code must equal the code the artifact's kMIPS index computed for
    the item that landed at original row i (the launch/serve.py contract);
    the legacy ``engine.serving_codes`` shim forwards to the same surface
    and warns."""
    key = jax.random.PRNGKey(7)
    items = jax.random.normal(key, (96, 16))
    cfg = get_config("sah").replace(n_bits=64)
    art = engine_mod.IndexArtifact.build(items, None, key, config=cfg)
    codes, proj_q = art.serving_codes()
    assert codes.shape == (96, 2) and codes.dtype == jnp.uint32
    assert proj_q.shape == (16, 64)
    idx = art.kmips_index                   # built eagerly for users=None
    ids = np.asarray(idx.item_ids)
    mask = np.asarray(idx.item_mask)
    np.testing.assert_array_equal(np.asarray(codes)[ids[mask]],
                                  np.asarray(idx.codes)[mask])
    np.testing.assert_array_equal(np.asarray(proj_q),
                                  np.asarray(idx.proj[:-1]))
    # the deprecated shim: same codes, same projection, plus a warning
    with pytest.warns(DeprecationWarning, match=r"serving_codes is "
                                                r"deprecated"):
        codes_shim, proj_shim = engine_mod.serving_codes(items, key,
                                                         n_bits=64)
    np.testing.assert_array_equal(np.asarray(codes_shim), np.asarray(codes))
    np.testing.assert_array_equal(np.asarray(proj_shim), np.asarray(proj_q))
    # launch/serve.py::build_candidate_index rides the artifact surface
    from repro.launch import serve as serve_mod
    codes_l, proj_l = serve_mod.build_candidate_index(items, key, n_bits=64)
    np.testing.assert_array_equal(np.asarray(codes_l), np.asarray(codes))


_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.engine import RkMIPSEngine, get_config
from repro.dist.policy import ShardingPolicy
from repro.data import synthetic
from repro.core import exact

key = jax.random.PRNGKey(0)
ki, kq, kb = jax.random.split(key, 3)
items, users = synthetic.recommendation_data(ki, 512, 1024, 32)
queries = synthetic.queries_from_items(kq, items, 3)

mesh = jax.make_mesh((2, 4), ("data", "model"))
policy = ShardingPolicy(mesh=mesh, rules={})

# RkMIPS: sharded predictions must be bitwise equal to single-device.
for method in ("sah", "simpfer"):
    cfg = get_config(method).replace(tile=128, n_bits=64)
    e0 = RkMIPSEngine(cfg).build(items, users, kb)
    e1 = RkMIPSEngine(cfg, policy=policy).build(items, users, kb)
    r0 = e0.query_batch(queries, 10)
    r1 = e1.query_batch(queries, 10)
    np.testing.assert_array_equal(np.asarray(r0.predictions),
                                  np.asarray(r1.predictions))
    # per-user counters are layout-independent (chunks/tiles are not)
    for f in ("blocks_alive", "users_alive", "n_no_lb", "n_yes_norm",
              "n_scan"):
        np.testing.assert_array_equal(np.asarray(getattr(r0.stats, f)),
                                      np.asarray(getattr(r1.stats, f)))
    s1 = e1.query(queries[0], 10)
    np.testing.assert_array_equal(np.asarray(s1.predictions),
                                  np.asarray(r1.predictions[0]))
    print(method, "rkmips sharded OK")

# The sharded path contains no Python-level loop over queries: one trace of
# the batched plan/execute body per shard_map dispatch, at any batch size
# (the per-query unroll is retired, DESIGN.md SS9).
from repro.core import sah as sah_mod
cfg = get_config("sah").replace(tile=128, n_bits=64)
e1 = RkMIPSEngine(cfg, policy=policy).build(items, users, kb)
calls = {"n": 0}
orig_impl = sah_mod.rkmips_batch_impl
def counting_impl(*a, **kw):
    calls["n"] += 1
    return orig_impl(*a, **kw)
sah_mod.rkmips_batch_impl = counting_impl
try:
    e1.query_batch(queries, 10)
finally:
    sah_mod.rkmips_batch_impl = orig_impl
assert calls["n"] == 1, f"sharded body traced {calls['n']} times for nq=3"
# engine-level compile accounting under a mesh: one per distinct batch shape
assert e1.rkmips_compile_count == 1, e1.rkmips_compile_count
e1.query_batch(queries, 10)
assert e1.rkmips_compile_count == 1, e1.rkmips_compile_count
e1.query_batch(queries[:2], 10)
assert e1.rkmips_compile_count == 2, e1.rkmips_compile_count
print("sharded single-trace OK")

# kMIPS: with full per-shard re-rank depth both layouts recover the exact
# top-k, so sharded and unsharded agree on the ids.
cfg = get_config("sah").replace(tile=128, n_bits=64)
e0 = RkMIPSEngine(cfg).build(items, None, kb)
e1 = RkMIPSEngine(cfg, policy=policy).build(items, None, kb)
_, ti = exact.kmips(items, queries, 5)
k0 = e0.kmips(queries, 5, n_cand=512)
k1 = e1.kmips(queries, 5, n_cand=512)
np.testing.assert_array_equal(np.asarray(k0.ids), np.asarray(ti))
np.testing.assert_array_equal(np.asarray(k1.ids), np.asarray(ti))
# the flat scan's single-device oracle agrees with its sharded body
from repro.dist.policy import NO_SHARDING
from repro.engine import sharding as eng_sharding
fv, fi = eng_sharding.kmips_flat(e1.kmips_index, queries, 5, NO_SHARDING,
                                 n_cand=512)
np.testing.assert_array_equal(np.asarray(fi), np.asarray(ti))
# exact-scan presets stay exact under a mesh regardless of n_cand
e1x = RkMIPSEngine(cfg.replace(scan="exact"), policy=policy).build(
    items, None, kb)
kx = e1x.kmips(queries, 5, n_cand=8)
np.testing.assert_array_equal(np.asarray(kx.ids), np.asarray(ti))
print("kmips sharded OK")

# Non-divisible counts shard via dead padding, bitwise equal to one device
# (DESIGN.md SS8): 1009 users -> 32 cone blocks padded to 36 over a
# 6-device (2, 3) mesh; 997 items -> 1024 padded rows -> 1026.
items_p, users_p = synthetic.recommendation_data(ki, 997, 1009, 32)
queries_p = synthetic.queries_from_items(kq, items_p, 2)
mesh6 = jax.sharding.Mesh(np.asarray(jax.devices()[:6]).reshape(2, 3),
                          ("data", "model"))
policy6 = ShardingPolicy(mesh=mesh6, rules={})
cfgp = get_config("sah").replace(tile=128, n_bits=64)
e0 = RkMIPSEngine(cfgp).build(items_p, users_p, kb)
e1 = RkMIPSEngine(cfgp, policy=policy6).build(items_p, users_p, kb)
assert e1.index.n_blocks % 6 == 0 and e1.index.n_blocks == 36
r0 = e0.query_batch(queries_p, 10)
r1 = e1.query_batch(queries_p, 10)
np.testing.assert_array_equal(np.asarray(r0.predictions),
                              np.asarray(r1.predictions))
for f in ("blocks_alive", "users_alive", "n_no_lb", "n_yes_norm", "n_scan"):
    np.testing.assert_array_equal(np.asarray(getattr(r0.stats, f)),
                                  np.asarray(getattr(r1.stats, f)))
k0 = e0.kmips(queries_p, 5, n_cand=1024)
k1 = e1.kmips(queries_p, 5, n_cand=1024)
_, tip = exact.kmips(items_p, queries_p, 5)
np.testing.assert_array_equal(np.asarray(k0.ids), np.asarray(tip))
np.testing.assert_array_equal(np.asarray(k1.ids), np.asarray(tip))
print("non-divisible padding OK")

# Fewer blocks than devices pads up too (96 users -> 4 blocks -> 8).
cfg3 = get_config("sah").replace(tile=128)
e0 = RkMIPSEngine(cfg3).build(items[:256], users[:96], kb)
e1 = RkMIPSEngine(cfg3, policy=policy).build(items[:256], users[:96], kb)
assert e1.index.n_blocks == 8
r0 = e0.query_batch(queries, 10)
r1 = e1.query_batch(queries, 10)
np.testing.assert_array_equal(np.asarray(r0.predictions),
                              np.asarray(r1.predictions))
print("small-block padding OK")
print("ALL ENGINE SHARDED OK")
"""


@pytest.mark.slow
def test_engine_sharded_equivalence():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "ALL ENGINE SHARDED OK" in out.stdout
    assert "sharded single-trace OK" in out.stdout
    assert "non-divisible padding OK" in out.stdout
    assert "small-block padding OK" in out.stdout
