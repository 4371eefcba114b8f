"""Multi-tenant gateway: N tenants, one worker pool, one trace cache.

    PYTHONPATH=src python examples/serve_multitenant.py

The walkthrough of DESIGN.md SS15:

1. build two artifact versions and stand up a ``ServingGateway``:
   ``register(name, artifact, policy=TenantPolicy(...))`` binds each
   tenant name to an artifact fingerprint plus admission limits — the
   tenants dispatch through per-tenant runtimes that SHARE one
   ``WorkerPool`` and (same config modulo ``scan_budget``) one compiled
   dispatch;
2. gateway-wide ``warmup()``: each shared signature traces once, then
   ``stats().traces_after_warmup == 0`` across ALL tenants — and stays 0
   under live traffic from every tenant;
3. a budgeted tenant (``TenantPolicy(scan_budget=...)``) gets its deep
   scans truncated *visibly*: the ticket comes back ``truncated=True``
   with a pruning-funnel snapshot, answers stay conservative (never a
   false positive vs. the unbudgeted answer), and
   ``stats().tenants[name].truncated`` attributes the count;
4. admission control: k above ``max_k`` and submits past
   ``max_in_flight`` are rejected with explicit messages, up front;
5. per-tenant lifecycle: churn + hot-swap on one tenant while the other
   keeps serving — the pool skips a locked tenant instead of queueing
   behind it, so maintenance never stalls a neighbor.
"""

import argparse

import jax
import numpy as np

from repro import IndexArtifact, compile_cache, get_config
from repro.data import synthetic
from repro.engine import ServingGateway, TenantPolicy


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-items", type=int, default=2048)
    ap.add_argument("--m-users", type=int, default=512)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--k", type=int, default=5)
    ap.add_argument("--queries", type=int, default=24)
    args = ap.parse_args()
    compile_cache.enable()

    key = jax.random.PRNGKey(0)
    ki, kq, kb = jax.random.split(key, 3)
    items, users = synthetic.recommendation_data(
        ki, args.n_items, args.m_users, args.dim)
    queries = synthetic.queries_from_items(kq, items, args.queries)

    # chunk small relative to the corpus so a scan budget has chunks to
    # truncate (see tests/test_gateway.py)
    cfg = get_config("sah").replace(delta_capacity=64, serve_batch_size=4,
                                    chunk=8)
    art = IndexArtifact.build(items, users, kb, config=cfg)
    print(f"built: {art.n_base} items, fingerprint "
          f"{art.fingerprint[:16]}...")

    with ServingGateway(pool_workers=2) as gw:
        # -- 1. two tenants, one pool, one trace cache -------------------
        gw.register("prod", art, k=args.k,
                    policy=TenantPolicy(max_k=args.k, max_in_flight=256))
        gw.register("trial", art, k=args.k,
                    policy=TenantPolicy(max_k=args.k, scan_budget=1))
        print(f"tenants: {gw.tenants}; trial routes to "
              f"{gw.route('trial')[:16]}...")

        # -- 2. gateway-wide warmup --------------------------------------
        cells = gw.warmup()
        print(f"warmup: {cells} cells compiled for the shared dispatch; "
              f"traces_after_warmup={gw.stats().traces_after_warmup}")

        # -- 3. traffic from both tenants: zero retraces, budget visible -
        # a few "promo blitz" probes — noisy top-norm items pushed onto
        # the corpus's max-norm shell — defeat the O(1) pruning and force
        # deep tile scans (benchmarks/bench_adversarial.py crafts these
        # systematically); the trial tenant's budget caps them
        it = np.asarray(items)
        norms = np.linalg.norm(it, axis=-1)
        rng = np.random.default_rng(7)
        picks = it[np.argsort(norms)[-4:]]
        blitz = picks + 0.05 * rng.normal(size=picks.shape) * \
            np.linalg.norm(picks, axis=-1, keepdims=True)
        blitz *= norms.max() / np.linalg.norm(blitz, axis=-1,
                                              keepdims=True)
        mixed = np.concatenate([np.asarray(queries),
                                blitz.astype(np.float32)])
        prod = [gw.submit("prod", mixed[i])
                for i in range(mixed.shape[0])]
        trial = [gw.submit("trial", mixed[i])
                 for i in range(mixed.shape[0])]
        prod = [t.result(timeout=120) for t in prod]
        trial = [t.result(timeout=120) for t in trial]
        n_trunc = sum(r.truncated for r in trial)
        for p, t in zip(prod, trial):
            full = np.asarray(p.predictions)
            got = np.asarray(t.predictions)
            assert not np.any(got & ~full), "budget must be conservative"
        st = gw.stats()
        print(f"prod: {st.tenants['prod'].completed} tickets, "
              f"truncated={st.tenants['prod'].truncated}")
        print(f"trial: {st.tenants['trial'].completed} tickets, "
              f"truncated={st.tenants['trial'].truncated} "
              f"({n_trunc} flagged on the tickets themselves)")
        print(f"traces_after_warmup={st.traces_after_warmup} "
              f"(both tenants, live traffic)")
        if n_trunc:
            f = next(r.funnel for r in trial if r.truncated)
            print(f"  a truncated ticket's funnel: {f.format()}")

        # -- 4. admission control ----------------------------------------
        for bad in (lambda: gw.submit("trial", queries[0], k=args.k + 3),
                    lambda: gw.submit("ghost", queries[0])):
            try:
                bad()
            except (ValueError, KeyError) as e:
                print(f"rejected: {e}")

        # -- 5. per-tenant churn while the neighbor serves ---------------
        art2 = gw.insert_items("prod", np.asarray(queries[:4]) * 1.01)
        r = gw.submit("trial", queries[0]).result(timeout=120)
        print(f"prod swapped to {gw.route('prod')[:16]}... "
              f"(v{art2.delta_used} staged rows); trial answered "
              f"meanwhile (k={r.k}, swaps seen by trial: "
              f"{gw.stats().tenants['trial'].swaps})")

    print("gateway closed; all tickets resolved")


if __name__ == "__main__":
    main()
