"""LM training driver with checkpoint/restart.

    PYTHONPATH=src python examples/train_lm.py --model 100m --steps 200

--model 100m is a ~100M-parameter dense transformer (the task's end-to-end
training target); --model tiny runs in seconds for CI. Resumes automatically
from --ckpt-dir; --fail-at N simulates a worker crash to exercise recovery.
"""

import argparse

import jax
import jax.numpy as jnp

from repro import compile_cache
from repro.data import synthetic
from repro.models import transformer as tf_lib
from repro.train import checkpoint as ckpt_lib
from repro.train import optimizer as opt_lib
from repro.train.trainer import TrainState, make_train_step, train_loop

MODELS = {
    "tiny": tf_lib.LMConfig(
        name="tiny", n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
        d_head=32, d_ff=512, vocab=2048, dtype=jnp.float32, attn_chunk=64),
    # ~100M params: 12L x 640d, vocab 32k
    "100m": tf_lib.LMConfig(
        name="100m", n_layers=12, d_model=640, n_heads=10, n_kv_heads=5,
        d_head=64, d_ff=2560, vocab=32768, dtype=jnp.float32,
        attn_chunk=128),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=MODELS, default="tiny")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--fail-at", type=int, default=None)
    ap.add_argument("--grad-accum", type=int, default=1)
    args = ap.parse_args()
    compile_cache.enable()

    cfg = MODELS[args.model]
    print(f"model={cfg.name} params~{cfg.n_params/1e6:.1f}M")
    key = jax.random.PRNGKey(0)
    opt = opt_lib.chain(opt_lib.clip_by_global_norm(1.0),
                        opt_lib.adamw(opt_lib.cosine_schedule(
                            3e-4, warmup=20, total=args.steps)))
    step = make_train_step(lambda p, b: tf_lib.lm_loss(p, b, cfg), opt,
                           grad_accum=args.grad_accum)

    params = tf_lib.init_params(key, cfg)
    state = TrainState(params, opt.init(params), jnp.zeros((), jnp.int32))

    # resume if a checkpoint exists (deterministic, step-indexed data)
    if args.ckpt_dir:
        last = ckpt_lib.latest_step(args.ckpt_dir)
        if last is not None:
            state, _ = ckpt_lib.restore(args.ckpt_dir, last, state)
            print(f"resumed from step {last}")

    data = synthetic.lm_token_batches(jax.random.PRNGKey(1), args.batch,
                                      args.seq, cfg.vocab)
    state = train_loop(state, step, data, n_steps=args.steps,
                       ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                       log_every=10, fail_at_step=args.fail_at,
                       metadata={"model": cfg.name})
    print(f"done at step {int(state.step)}")


if __name__ == "__main__":
    main()
