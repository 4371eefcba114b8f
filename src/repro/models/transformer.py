"""Decoder-only LM transformer: dense (GQA/RoPE/qk-norm/QKV-bias/SwiGLU) and
MoE variants, scan-over-layers with configurable remat, train / prefill /
decode entry points.

Parameters are plain pytrees with a leading (L,) layer axis so the whole stack
is one lax.scan: HLO stays small (compile time at 512 devices) and XLA's
latency-hiding scheduler overlaps layer-i collectives with layer-i+1 compute.

Sharding is injected through a ShardingPolicy (repro/dist/policy.py); with
mesh=None the model is ordinary single-device JAX (smoke tests).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.dist.policy import NO_SHARDING, ShardingPolicy
from repro.models import attention as attn
from repro.models import moe as moe_lib


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: int | None = None
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    moe: moe_lib.MoEConfig | None = None
    dtype: Any = jnp.bfloat16
    attn_chunk: int = 512
    attn_impl: str = "chunked"   # "chunked" (pure JAX, dry-run path) |
    #                              "flash" (fused Pallas kernel: keeps score
    #                              tiles in VMEM; the TPU deployment path --
    #                              cannot lower in the CPU dry-run)
    remat: str = "full"          # "full" | "none"
    max_seq: int = 4096          # decode cache length
    aux_loss_weight: float = 0.01
    scan_layers: bool = True     # False: python-unrolled (cost analysis mode:
    #                              XLA cost_analysis counts a while body once,
    #                              so the dry-run extrapolates from unrolled
    #                              L=1/L=2 lowerings; see launch/dryrun.py)

    @property
    def head_dim(self) -> int:
        return self.d_head if self.d_head is not None else (
            self.d_model // self.n_heads)

    @property
    def n_params(self) -> int:
        """Total parameter count (embedding + head included)."""
        d, hd = self.d_model, self.head_dim
        attn_p = d * self.n_heads * hd * 2 + d * self.n_kv_heads * hd * 2
        if self.moe is not None:
            ffn = (d * self.moe.n_experts
                   + 3 * self.moe.n_experts * d * self.moe.d_ff_expert)
        else:
            ffn = 3 * d * self.d_ff
        per_layer = attn_p + ffn + 2 * d
        return (self.n_layers * per_layer + 2 * self.vocab * d + d)

    @property
    def n_active_params(self) -> int:
        """Active parameters per token (MoE: top_k experts only)."""
        if self.moe is None:
            return self.n_params
        d = self.d_model
        dense = self.n_params - self.n_layers * (
            3 * self.moe.n_experts * d * self.moe.d_ff_expert)
        return dense + self.n_layers * 3 * self.moe.top_k * d * \
            self.moe.d_ff_expert


def init_params(key: jax.Array, cfg: LMConfig) -> dict:
    """Stacked-layer parameter pytree."""
    d, hd = cfg.d_model, cfg.head_dim
    nh, nkv = cfg.n_heads, cfg.n_kv_heads
    keys = jax.random.split(key, 8)

    def norm(k, *shape, scale):
        return (jax.random.normal(k, shape) * scale).astype(cfg.dtype)

    def layer_init(k):
        ks = jax.random.split(k, 8)
        p = {
            "wq": norm(ks[0], d, nh * hd, scale=d ** -0.5),
            "wk": norm(ks[1], d, nkv * hd, scale=d ** -0.5),
            "wv": norm(ks[2], d, nkv * hd, scale=d ** -0.5),
            "wo": norm(ks[3], nh * hd, d, scale=(nh * hd) ** -0.5),
            "ln1": jnp.ones((d,), cfg.dtype),
            "ln2": jnp.ones((d,), cfg.dtype),
        }
        if cfg.qkv_bias:
            p["bq"] = jnp.zeros((nh * hd,), cfg.dtype)
            p["bk"] = jnp.zeros((nkv * hd,), cfg.dtype)
            p["bv"] = jnp.zeros((nkv * hd,), cfg.dtype)
        if cfg.qk_norm:
            p["q_norm"] = jnp.ones((hd,), cfg.dtype)
            p["k_norm"] = jnp.ones((hd,), cfg.dtype)
        if cfg.moe is not None:
            p["moe"] = moe_lib.init_moe_params(ks[4], d, cfg.moe, cfg.dtype)
        else:
            p["w_in"] = norm(ks[4], d, cfg.d_ff, scale=d ** -0.5)
            p["w_gate"] = norm(ks[5], d, cfg.d_ff, scale=d ** -0.5)
            p["w_out"] = norm(ks[6], cfg.d_ff, d, scale=cfg.d_ff ** -0.5)
        return p

    layers = jax.vmap(layer_init)(jax.random.split(keys[0], cfg.n_layers))
    return {
        "embed": norm(keys[1], cfg.vocab, d, scale=1.0),
        "head": norm(keys[2], d, cfg.vocab, scale=d ** -0.5),
        "final_norm": jnp.ones((d,), cfg.dtype),
        "layers": layers,
    }


def param_specs(cfg: LMConfig, policy: ShardingPolicy) -> dict:
    """PartitionSpec pytree matching init_params output."""
    r = policy.rules
    layer = {
        "wq": r["p_attn_in"], "wk": r["p_attn_in"], "wv": r["p_attn_in"],
        "wo": r["p_attn_out"], "ln1": r["p_norm"], "ln2": r["p_norm"],
    }
    if cfg.qkv_bias:
        bias = jax.sharding.PartitionSpec(None, None)
        layer.update({"bq": bias, "bk": bias, "bv": bias})
    if cfg.qk_norm:
        layer.update({"q_norm": r["p_norm"], "k_norm": r["p_norm"]})
    if cfg.moe is not None:
        layer["moe"] = {
            "router": r["p_router"],
            "w_in": r["p_expert_in"], "w_gate": r["p_expert_in"],
            "w_out": r["p_expert_out"],
        }
    else:
        layer.update({"w_in": r["p_mlp_in"], "w_gate": r["p_mlp_in"],
                      "w_out": r["p_mlp_out"]})
    return {
        "embed": r["p_embed"],
        "head": r["p_head"],
        "final_norm": jax.sharding.PartitionSpec(None),
        "layers": layer,
    }


def _rms_norm(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(
        x.dtype) * scale


def _rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x (..., S, Dh), positions (S,) -> rotated."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * freqs[None, :]  # (S, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _project_qkv(x, p, cfg: LMConfig, positions):
    """x (B, S, D) -> q (B,H,S,Dh), k/v (B,Hkv,S,Dh) with RoPE applied."""
    b, s, _ = x.shape
    hd = cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    if cfg.qk_norm:
        q = _rms_norm(q, p["q_norm"])
        k = _rms_norm(k, p["k_norm"])
    q = _rope(q, positions, cfg.rope_theta)
    k = _rope(k, positions, cfg.rope_theta)
    return q, k, v


def _layer(x, p, cfg: LMConfig, policy: ShardingPolicy, positions):
    """One transformer block. x (B, S, D) -> (x', aux_loss, (k, v))."""
    h = _rms_norm(x, p["ln1"])
    # SP->TP boundary: gather the sequence axis once here (one all-gather);
    # projections then emit head-sharded q/k/v natively instead of GSPMD
    # discovering the transition mid-chain (which degenerates to full remat).
    h = policy.constrain(h, "act_attn_in")
    q, k, v = _project_qkv(h, p, cfg, positions)
    q = policy.constrain(q, "act_bhsd")
    # Repeat KV to full head count and pin the head-sharded layout: without
    # the constraint GSPMD propagates the sequence-parallel sharding into the
    # repeat broadcast and falls back to full rematerialization at the SP->TP
    # boundary (seen as spmd_partitioner 'Involuntary full remat' warnings).
    kr = attn.repeat_kv(k, cfg.n_heads // cfg.n_kv_heads)
    vr = attn.repeat_kv(v, cfg.n_heads // cfg.n_kv_heads)
    kr = policy.constrain(kr, "act_bhsd")
    vr = policy.constrain(vr, "act_bhsd")
    if cfg.attn_impl == "flash":
        from repro.kernels import ops as kops
        o = kops.flash_attention(q, kr, vr, causal=True)
    else:
        o = attn.chunked_attention(q, kr, vr, chunk=min(cfg.attn_chunk,
                                                        x.shape[1]))
    b, s, _ = x.shape
    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.head_dim)
    x = x + (o @ p["wo"]).astype(x.dtype)
    x = policy.constrain(x, "act_btd")

    h = _rms_norm(x, p["ln2"])
    if cfg.moe is not None:
        f, aux = moe_lib.moe_ffn(h, p["moe"], cfg.moe, policy)
    else:
        gate = h @ p["w_gate"]
        up = h @ p["w_in"]
        gate = policy.constrain(gate, "act_btf")
        f = (jax.nn.silu(gate) * up) @ p["w_out"]
        aux = jnp.zeros((), jnp.float32)
    x = x + f.astype(x.dtype)
    x = policy.constrain(x, "act_btd")
    return x, aux, (k, v)


def forward(params, tokens: jnp.ndarray, cfg: LMConfig,
            policy: ShardingPolicy = NO_SHARDING, *,
            return_cache: bool = False):
    """tokens (B, S) int32 -> (hidden (B,S,D) post-final-norm, aux, cache).

    Returns hidden states, NOT logits: materializing (B, S, V) f32 logits is
    a multi-GiB allocation at vocab 152k; loss and serving project only what
    they need (chunked CE / last position).
    """
    b, s = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    x = policy.constrain(x, "act_btd")
    positions = jnp.arange(s)

    def body(x, lp):
        # barrier: stops XLA folding the rms-norm f32 upcast into the
        # scan-saved carry buffer (which would store residuals at 2x bytes)
        x = jax.lax.optimization_barrier(x)
        x2, aux, kv = _layer(x, lp, cfg, policy, positions)
        return x2, (aux, kv if return_cache else None)

    if cfg.remat == "full":
        body = jax.checkpoint(body)
    if cfg.scan_layers:
        x, (auxes, caches) = jax.lax.scan(body, x, params["layers"])
        aux_mean = jnp.mean(auxes)
    else:
        aux_sum = jnp.zeros((), jnp.float32)
        kvs = []
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            x, (aux, kv) = body(x, lp)
            aux_sum = aux_sum + aux
            if return_cache:
                kvs.append(kv)
        aux_mean = aux_sum / cfg.n_layers
        caches = (jax.tree.map(lambda *xs: jnp.stack(xs), *kvs)
                  if return_cache else None)
    x = _rms_norm(x, params["final_norm"])
    return x, aux_mean, caches


def full_logits(params, hidden: jnp.ndarray, cfg: LMConfig,
                policy: ShardingPolicy = NO_SHARDING) -> jnp.ndarray:
    """(B, S, D) -> (B, S, V) f32. Small-vocab / test use only."""
    logits = (hidden @ params["head"]).astype(jnp.float32)
    return policy.constrain(logits, "logits")


def lm_loss(params, batch, cfg: LMConfig,
            policy: ShardingPolicy = NO_SHARDING, *,
            loss_chunk: int = 512):
    """batch = {"tokens": (B,S), "labels": (B,S)} -> scalar loss.

    Cross-entropy is computed in *batch* chunks under jax.checkpoint so the
    (bc, S, V) logits are transient in both passes -- at vocab 152k the
    unchunked logits would be GiBs of f32. Chunking over batch (not sequence)
    keeps every chunk aligned with the DP sharding; sequence chunks would
    straddle sequence-parallel shards and force SPMD full-rematerializations.
    loss_chunk: target tokens per (chunk x device); chunk count is derived
    and clamped to divide B.
    """
    hidden, aux, _ = forward(params, batch["tokens"], cfg, policy)
    b, s, d = hidden.shape
    labels = batch["labels"]
    n_chunks = 8 if (b % 8 == 0 and loss_chunk < s * b) else 1
    bc = b // n_chunks
    h_r = hidden.reshape(n_chunks, bc, s, d)
    y_r = labels.reshape(n_chunks, bc, s)

    def chunk_nll(carry, xs):
        # CE = logsumexp(logits) - <h, head[:, y]>. Gathering label columns
        # from the (D, V) head (D x tokens bytes) instead of take_along_axis
        # on the V-sharded (bc, S, V) logits avoids a logits-sized all-gather
        # + backward all-reduce per chunk (~40 GB/step at vocab 152k).
        h_c, y_c = xs
        # bf16 inputs + f32 accumulation: no f32 copy of h_c materializes
        logits = jnp.dot(h_c, params["head"],
                         preferred_element_type=jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)       # (bc, S)
        w_y = jnp.take(params["head"], y_c, axis=1)              # (D, bc, S)
        correct = jnp.einsum("bsd,dbs->bs", h_c, w_y,
                             preferred_element_type=jnp.float32)
        return carry + jnp.sum(lse - correct), None

    if n_chunks == 1:
        total, _ = chunk_nll(jnp.zeros((), jnp.float32), (h_r[0], y_r[0]))
    else:
        total, _ = jax.lax.scan(jax.checkpoint(chunk_nll),
                                jnp.zeros((), jnp.float32), (h_r, y_r))
    return total / (b * s) + cfg.aux_loss_weight * aux


def init_cache(cfg: LMConfig, batch: int, dtype=None) -> dict:
    """Decode KV cache: (L, B, Hkv, Smax, Dh) k & v + length scalar."""
    dtype = dtype or cfg.dtype
    shape = (cfg.n_layers, batch, cfg.n_kv_heads, cfg.max_seq, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "length": jnp.zeros((), jnp.int32)}


def decode_step(params, cache: dict, tokens: jnp.ndarray, cfg: LMConfig,
                policy: ShardingPolicy = NO_SHARDING):
    """One decode step. tokens (B,) int32 -> (logits (B, V), new cache).

    The cache sequence axis may be sharded ('kv_cache' rule); the attention
    reductions then lower to the distributed flash-decode schedule
    (see models/attention.py).
    """
    b = tokens.shape[0]
    pos = cache["length"]
    x = jnp.take(params["embed"], tokens, axis=0)[:, None, :]   # (B, 1, D)
    positions = pos[None].astype(jnp.int32)

    def body(x, scanned):
        lp, kc, vc = scanned
        h = _rms_norm(x, lp["ln1"])
        q, k, v = _project_qkv(h, lp, cfg, positions)
        # Insert the new position into the cache.
        kc = jax.lax.dynamic_update_slice(kc, k, (0, 0, pos, 0))
        vc = jax.lax.dynamic_update_slice(vc, v, (0, 0, pos, 0))
        kc = policy.constrain(kc[None], "kv_cache")[0]
        vc = policy.constrain(vc[None], "kv_cache")[0]
        rep = cfg.n_heads // cfg.n_kv_heads
        o = attn.decode_attention(q[:, :, 0, :], attn.repeat_kv(kc, rep),
                                  attn.repeat_kv(vc, rep), pos + 1)
        x = x + (o.reshape(b, 1, -1) @ lp["wo"]).astype(x.dtype)
        h2 = _rms_norm(x, lp["ln2"])
        if cfg.moe is not None:
            f, _ = moe_lib.moe_ffn(h2, lp["moe"], cfg.moe, policy)
        else:
            f = (jax.nn.silu(h2 @ lp["w_gate"]) * (h2 @ lp["w_in"])
                 ) @ lp["w_out"]
        x = x + f.astype(x.dtype)
        return x, (kc, vc)

    if cfg.scan_layers:
        x, (k_new, v_new) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"]))
    else:
        ks, vs = [], []
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            x, (kc, vc) = body(x, (lp, cache["k"][i], cache["v"][i]))
            ks.append(kc)
            vs.append(vc)
        k_new, v_new = jnp.stack(ks), jnp.stack(vs)
    x = _rms_norm(x[:, 0, :], params["final_norm"])
    logits = (x @ params["head"]).astype(jnp.float32)
    logits = policy.constrain(logits[:, None, :], "logits")[:, 0, :]
    new_cache = {"k": k_new, "v": v_new, "length": pos + 1}
    return logits, new_cache


def prefill(params, tokens: jnp.ndarray, cfg: LMConfig,
            policy: ShardingPolicy = NO_SHARDING):
    """Prefill: full forward that also materializes the KV cache.

    Returns (last-position logits (B, V), cache dict).
    """
    b, s = tokens.shape
    hidden, _, caches = forward(params, tokens, cfg, policy,
                                return_cache=True)
    k, v = caches                                   # (L, B, Hkv, S, Dh)
    pad = cfg.max_seq - s
    if pad > 0:
        cfgp = [(0, 0)] * 3 + [(0, pad), (0, 0)]
        k, v = jnp.pad(k, cfgp), jnp.pad(v, cfgp)
    k = policy.constrain(k, "kv_cache")
    v = policy.constrain(v, "kv_cache")
    last = (hidden[:, -1, :] @ params["head"]).astype(jnp.float32)
    last = policy.constrain(last[:, None, :], "logits")[:, 0, :]
    return last, {"k": k, "v": v, "length": jnp.asarray(s, jnp.int32)}
