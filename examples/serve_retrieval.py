"""End-to-end serving driver: two-tower retrieval with SAH-indexed candidates.

    PYTHONPATH=src python examples/serve_retrieval.py --steps 30

1. trains the (smoke-scale) two-tower model on synthetic interactions
   (in-batch sampled softmax);
2. embeds the item corpus with the item tower, builds the SAH candidate
   index offline (SAT + SRP codes);
3. serves retrieval requests **online through the engine's serving
   subsystem** (repro.engine.serving.RetrievalServer, DESIGN.md SS8):
   requests arrive one at a time, are micro-batched into fixed-size
   dispatches of the sharded sketch scan, and compared against the exact
   fused ip_topk for recall@k + QPS.
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import RkMIPSEngine, compile_cache, get_config
from repro.configs import base as cfg_base
from repro.core import metrics
from repro.kernels import ops as kops
from repro.models import recsys as rec_lib
from repro.train import optimizer as opt_lib
from repro.train.trainer import TrainState, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--corpus", type=int, default=16384)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--k", type=int, default=20)
    args = ap.parse_args()
    compile_cache.enable()

    cfg = cfg_base.get("two-tower-retrieval").make_smoke_config()
    key = jax.random.PRNGKey(0)
    params = rec_lib.init_twotower_params(key, cfg)

    def batch_at(i):
        k = jax.random.fold_in(key, i)
        uf = jnp.stack([jax.random.randint(jax.random.fold_in(k, j),
                                           (args.batch,), 0, v)
                        for j, v in enumerate(cfg.user_embedding.vocab_sizes)
                        ], -1)
        itf = jnp.stack([jax.random.randint(jax.random.fold_in(k, 7 + j),
                                            (args.batch,), 0, v)
                         for j, v in
                         enumerate(cfg.item_embedding.vocab_sizes)], -1)
        return {"user_feats": uf, "item_feats": itf,
                "log_q": jnp.zeros((args.batch,))}

    opt = opt_lib.chain(opt_lib.clip_by_global_norm(1.0),
                        opt_lib.adamw(1e-3))
    step = jax.jit(make_train_step(
        lambda p, b: rec_lib.twotower_loss(p, b, cfg), opt))
    state = TrainState(params, opt.init(params), jnp.zeros((), jnp.int32))
    t0 = time.time()
    for i in range(args.steps):
        state, m = step(state, batch_at(i))
    print(f"trained {args.steps} steps in {time.time()-t0:.1f}s, "
          f"final loss {float(m['loss']):.3f}")

    # --- offline: embed corpus + build SAH index -------------------------
    kc = jax.random.fold_in(key, 999)
    corpus_feats = jnp.stack(
        [jax.random.randint(jax.random.fold_in(kc, j), (args.corpus,), 0, v)
         for j, v in enumerate(cfg.item_embedding.vocab_sizes)], -1)
    cand_vecs = rec_lib.item_tower(state.params, corpus_feats, cfg)
    eng = RkMIPSEngine(get_config("sah").replace(
        n_bits=256, serve_batch_size=min(16, args.requests)))
    eng.build(cand_vecs, None, jax.random.fold_in(key, 5))
    print(f"SAH candidate index built in {eng.build_seconds:.2f}s "
          f"({int(eng.kmips_index.n_parts)} norm partitions)")

    # --- online: batched requests ---------------------------------------
    kr = jax.random.fold_in(key, 1234)
    req_feats = jnp.stack(
        [jax.random.randint(jax.random.fold_in(kr, j), (args.requests,),
                            0, v)
         for j, v in enumerate(cfg.user_embedding.vocab_sizes)], -1)
    u = rec_lib.user_tower(state.params, req_feats, cfg)

    ev, ei = kops.ip_topk(u, cand_vecs, args.k)          # exact
    jax.block_until_ready(ev)
    t0 = time.time()
    ev, ei = kops.ip_topk(u, cand_vecs, args.k)
    jax.block_until_ready(ev)
    t_exact = time.time() - t0

    # Online serving: requests arrive one at a time; the server accumulates
    # them into fixed-size micro-batches (one compile per batch size) and
    # dispatches the sharded sketch scan (DESIGN.md SS8).
    server = eng.server()
    for i in range(args.requests):                       # warm (compile)
        server.submit(u[i])
    server.flush(args.k, n_cand=64)
    t0 = time.time()
    for i in range(args.requests):
        server.submit(u[i])
    results = server.flush(args.k, n_cand=64)      # answers on the host
    t_sah = time.time() - t0

    sids = jnp.stack([r.ids for r in results])
    rec = float(jnp.mean(metrics.recall_at_k(sids, ei)))
    print(f"\nexact : {args.requests/t_exact:8.0f} QPS")
    print(f"SAH   : {args.requests/t_sah:8.0f} QPS  recall@{args.k}={rec:.3f}"
          f"  (micro-batch {server.batch_size}, "
          f"{server.compile_count} compile)")


if __name__ == "__main__":
    main()
