"""Quickstart: build a SAH engine and answer RkMIPS queries.

    PYTHONPATH=src python examples/quickstart.py

Generates an MF-like synthetic recommendation dataset (the paper's data
regime), builds the SAH engine from its registry preset (SAT + SRP sketches
+ cone blocking + Simpfer lower bounds), answers reverse queries for a
handful of promoted items, and reports F1 against the exact oracle plus
pruning statistics. Predictions and the oracle share one EngineConfig, so
the tie tolerance can never drift between the two.
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro import RkMIPSEngine, compile_cache, get_config
from repro.core import metrics
from repro.data import synthetic


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-items", type=int, default=8192)
    ap.add_argument("--m-users", type=int, default=16384)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--queries", type=int, default=8)
    ap.add_argument("--method", default="sah",
                    help="engine registry preset (sah, sa-simpfer, ...)")
    args = ap.parse_args()
    compile_cache.enable()

    key = jax.random.PRNGKey(0)
    ki, kq, kb = jax.random.split(key, 3)
    items, users = synthetic.recommendation_data(
        ki, args.n_items, args.m_users, args.dim)
    queries = synthetic.queries_from_items(kq, items, args.queries)

    print(f"items={args.n_items} users={args.m_users} d={args.dim} "
          f"k={args.k} method={args.method}")
    eng = RkMIPSEngine(get_config(args.method)).build(items, users, kb)
    print(f"SAH index built in {eng.build_seconds:.2f}s "
          f"(partitions={int(eng.index.alsh.n_parts)}, "
          f"cone blocks={eng.index.n_blocks})")
    # per-stage breakdown of the staged build pipeline (DESIGN.md SS11)
    print(eng.build_timings.format())

    res = eng.query_batch(queries, args.k)
    dt = res.seconds / args.queries

    truth = eng.oracle(queries, args.k)
    f1 = metrics.f1_score(res.predictions, truth)
    print(f"\nper-query time: {dt*1e3:.1f} ms   mean F1: "
          f"{float(jnp.mean(f1)):.3f}")
    # the aggregate pruning funnel the batched plan/execute driver recovers
    # per query: blocks -> users -> scan lanes -> tiles (DESIGN.md SS9)
    print(f"pruning funnel: {res.funnel.format()}")
    for i in range(min(4, args.queries)):
        res_i = np.where(np.asarray(res.predictions[i]))[0]
        print(f"query {i}: {len(res_i)} users would see this item in their "
              f"top-{args.k}: {res_i[:8].tolist()}"
              f"{'...' if len(res_i) > 8 else ''}")


if __name__ == "__main__":
    main()
