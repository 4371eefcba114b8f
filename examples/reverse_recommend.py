"""The paper's motivating use case, end to end: a service promotes an item
and asks "which users would actually see it?" -- RkMIPS over two-tower
embeddings.

    PYTHONPATH=src python examples/reverse_recommend.py

Pipeline: train two-tower (briefly) -> embed users and items -> build the
full SAH index (item partitions + cone-blocked users + lower bounds) ->
answer reverse queries for promoted items and compare against exact.
Contrast with forward kMIPS on the same queries (Table 2 of the paper:
the two problems' answers barely overlap).
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro import RkMIPSEngine, compile_cache
from repro.configs import base as cfg_base
from repro.core import metrics
from repro.models import recsys as rec_lib
from repro.train import optimizer as opt_lib
from repro.train.trainer import TrainState, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--n-items", type=int, default=4096)
    ap.add_argument("--m-users", type=int, default=8192)
    ap.add_argument("--k", type=int, default=10)
    args = ap.parse_args()
    compile_cache.enable()

    cfg = cfg_base.get("two-tower-retrieval").make_smoke_config()
    key = jax.random.PRNGKey(0)
    params = rec_lib.init_twotower_params(key, cfg)
    opt = opt_lib.adamw(1e-3)
    step = jax.jit(make_train_step(
        lambda p, b: rec_lib.twotower_loss(p, b, cfg), opt))
    state = TrainState(params, opt.init(params), jnp.zeros((), jnp.int32))
    for i in range(args.steps):
        kk = jax.random.fold_in(key, i)
        b = 256
        batch = {
            "user_feats": jnp.stack(
                [jax.random.randint(jax.random.fold_in(kk, j), (b,), 0, v)
                 for j, v in enumerate(cfg.user_embedding.vocab_sizes)], -1),
            "item_feats": jnp.stack(
                [jax.random.randint(jax.random.fold_in(kk, 7 + j), (b,), 0,
                                    v)
                 for j, v in enumerate(cfg.item_embedding.vocab_sizes)], -1),
            "log_q": jnp.zeros((b,))}
        state, m = step(state, batch)
    print(f"two-tower trained ({args.steps} steps, loss "
          f"{float(m['loss']):.3f})")

    ki, ku = jax.random.fold_in(key, 100), jax.random.fold_in(key, 200)
    item_feats = jnp.stack(
        [jax.random.randint(jax.random.fold_in(ki, j), (args.n_items,), 0, v)
         for j, v in enumerate(cfg.item_embedding.vocab_sizes)], -1)
    user_feats = jnp.stack(
        [jax.random.randint(jax.random.fold_in(ku, j), (args.m_users,), 0, v)
         for j, v in enumerate(cfg.user_embedding.vocab_sizes)], -1)
    items = rec_lib.item_tower(state.params, item_feats, cfg)
    users = rec_lib.user_tower(state.params, user_feats, cfg)

    eng = RkMIPSEngine("sah").build(items, users, jax.random.fold_in(key, 7))
    print(f"SAH index over embeddings built in {eng.build_seconds:.2f}s")

    # promote the 4 highest-norm items
    norms = jnp.linalg.norm(items, axis=-1)
    promoted = jnp.argsort(-norms)[:4]
    queries = items[promoted]

    res = eng.query_batch(queries, args.k)
    po = res.predictions
    truth = eng.oracle(queries, args.k)
    f1 = metrics.f1_score(po, truth)

    # forward kMIPS top-k users by raw inner product (the wrong tool)
    uu = users / jnp.linalg.norm(users, axis=-1, keepdims=True)
    fwd_scores = queries @ uu.T
    _, fwd_top = jax.lax.top_k(fwd_scores, args.k)
    for i, item_id in enumerate(np.asarray(promoted)):
        audience = np.where(np.asarray(po[i]))[0]
        fwd = set(np.asarray(fwd_top[i]).tolist())
        overlap = len(fwd & set(audience.tolist()))
        print(f"item {item_id}: RkMIPS audience={len(audience)} users "
              f"(F1 vs exact {float(f1[i]):.3f}); forward-kMIPS top-{args.k} "
              f"overlaps only {overlap}/{args.k} -- the reverse problem is "
              f"genuinely different")


if __name__ == "__main__":
    main()
