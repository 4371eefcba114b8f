"""Roofline extraction from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), all in seconds (DESIGN/EXPERIMENTS):

    compute    = HLO_FLOPs_per_device   / peak_flops      (197 TF/s bf16 v5e)
    memory     = HLO_bytes_per_device   / hbm_bw          (819 GB/s)
    collective = collective_bytes_per_device / link_bw    (~50 GB/s/link ICI)

cost_analysis() reports the per-device (post-SPMD) program, so no chip
division is needed. Collective bytes are not in cost_analysis: we parse the
compiled HLO and sum output-shape bytes of every all-reduce / all-gather /
reduce-scatter / all-to-all / collective-permute instruction (output bytes
are the standard proxy for wire bytes; ring all-reduce moves ~2x, which we
fold into the reported term via the 2x factor on all-reduce).

MODEL_FLOPS (the "useful work" yardstick): 6*N*D for dense training,
6*N_active*D for MoE, 2*N*D for forward-only serving; attention FLOPs are
added explicitly since 6ND ignores them.
"""

from __future__ import annotations

import dataclasses
import re

PEAK_FLOPS = 197e12          # bf16 / chip (TPU v5e-class target)
HBM_BW = 819e9               # B/s per chip
LINK_BW = 50e9               # B/s per ICI link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  %all-reduce.5 = f32[16,128]{1,0} all-reduce(...)
#       ROOT %x = (bf16[4,8]{...}, f32[]) all-to-all(...)
_INSTR_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-device bytes by collective kind (output-shape proxy)."""
    out = {k: 0 for k in _COLLECTIVES}
    for m in _INSTR_RE.finditer(hlo_text):
        shape_str, kind, phase = m.group(1), m.group(2), m.group(3)
        if phase == "-done":   # started ops counted at -start
            continue
        out[kind] += _shape_bytes(shape_str)
    return out


@dataclasses.dataclass
class Roofline:
    flops: float                 # per device
    bytes_accessed: float        # per device
    coll_bytes: dict             # per device, by kind
    peak_memory: float           # per device, bytes

    @property
    def compute_s(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def memory_s(self) -> float:
        return self.bytes_accessed / HBM_BW

    @property
    def collective_s(self) -> float:
        # ring all-reduce moves ~2x its payload (reduce-scatter+all-gather)
        b = sum(v * (2 if k == "all-reduce" else 1)
                for k, v in self.coll_bytes.items())
        return b / LINK_BW

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    def to_dict(self) -> dict:
        return {
            "flops_per_dev": self.flops,
            "bytes_per_dev": self.bytes_accessed,
            "coll_bytes_per_dev": self.coll_bytes,
            "peak_memory_per_dev": self.peak_memory,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
        }


def from_compiled(compiled) -> Roofline:
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    return Roofline(
        flops=float(cost.get("flops", 0.0)),
        bytes_accessed=float(cost.get("bytes accessed", 0.0)),
        coll_bytes=collective_bytes(hlo),
        peak_memory=float(getattr(mem, "temp_size_in_bytes", 0)
                          + getattr(mem, "argument_size_in_bytes", 0)
                          + getattr(mem, "output_size_in_bytes", 0)
                          - getattr(mem, "alias_size_in_bytes", 0)),
    )


def model_flops(arch_id: str, shape_name: str) -> float:
    """Analytic 'useful' FLOPs per step (see EXPERIMENTS.md SSRoofline)."""
    from repro.configs import base as cfg_base
    arch = cfg_base.get(arch_id)
    shape = arch.shape(shape_name)
    dims = shape.dims

    if arch.family == "lm":
        cfg = arch.make_config()
        n_act = cfg.n_active_params
        s, b = dims["seq_len"], dims["global_batch"]
        if shape.kind == "train":
            tokens = s * b
            attn = (6 * 2 * cfg.n_layers * cfg.n_heads * cfg.head_dim
                    * s * s // 2 * b)     # fwd+bwd causal attention
            return 6.0 * n_act * tokens + attn
        if shape.kind == "prefill":
            tokens = s * b
            attn = 2 * 2 * cfg.n_layers * cfg.n_heads * cfg.head_dim \
                * s * s // 2 * b
            return 2.0 * n_act * tokens + attn
        # decode: one token/seq; attention reads the whole cache
        attn = 2 * 2 * cfg.n_layers * cfg.n_heads * cfg.head_dim * s * b
        return 2.0 * n_act * b + attn

    if arch.family == "gnn":
        cfg = arch.make_config()
        e, d = dims["n_edges"], dims["d_feat"]
        n = dims["n_nodes"]
        h, dh = cfg.n_heads, cfg.d_hidden
        # per layer: projection 2*N*d_in*H*Dh + edge ops ~ 2*E*H*(Dh+2)
        l1 = 2 * n * d * h * dh + 4 * e * h * dh
        l2 = 2 * n * h * dh * dims["n_classes"] + 4 * e * dims["n_classes"]
        fwd = l1 + l2
        return 3.0 * fwd if shape.kind == "train" else fwd

    # recsys
    cfg = arch.make_config()
    b = dims.get("batch", dims.get("n_candidates", 1))
    if arch.arch_id in ("deepfm", "xdeepfm"):
        f, d = cfg.embedding.n_fields, cfg.embedding.dim
        mlp_dims = (f * d,) + cfg.mlp_dims + (1,)
        mlp = sum(2 * a * bb for a, bb in zip(mlp_dims[:-1], mlp_dims[1:]))
        inter = 2 * f * d
        if cfg.interaction == "cin":
            sizes = (f,) + cfg.cin_layers
            inter = sum(2 * sizes[i] * f * sizes[i + 1] * d
                        for i in range(len(cfg.cin_layers)))
        fwd = b * (mlp + inter)
    elif arch.arch_id == "din":
        d = cfg.embedding.dim
        attn_dims = (4 * d,) + cfg.attn_mlp + (1,)
        attn = cfg.seq_len * sum(2 * a * bb for a, bb in
                                 zip(attn_dims[:-1], attn_dims[1:]))
        mlp_in = (2 + cfg.embedding.n_fields - 1) * d
        mlp_dims = (mlp_in,) + cfg.mlp_dims + (1,)
        mlp = sum(2 * a * bb for a, bb in zip(mlp_dims[:-1], mlp_dims[1:]))
        fwd = b * (attn + mlp)
    else:  # two-tower
        du = cfg.user_embedding.n_fields * cfg.user_embedding.dim
        di = cfg.item_embedding.n_fields * cfg.item_embedding.dim
        dims_u = (du,) + cfg.tower_dims + (cfg.out_dim,)
        dims_i = (di,) + cfg.tower_dims + (cfg.out_dim,)
        tower = sum(2 * a * bb for a, bb in zip(dims_u[:-1], dims_u[1:])) + \
            sum(2 * a * bb for a, bb in zip(dims_i[:-1], dims_i[1:]))
        if shape.kind == "retrieval":
            n = dims["n_candidates"] if isinstance(dims, dict) else 0
            n = shape.dims["n_candidates"]
            du_only = sum(2 * a * bb for a, bb in
                          zip(dims_u[:-1], dims_u[1:]))
            return du_only + 2.0 * n * cfg.out_dim
        if shape.kind == "train":
            fwd = b * tower + 2 * b * b * cfg.out_dim
            return 3.0 * fwd
        fwd = b * tower + 2 * b * cfg.out_dim
        return fwd
    return 3.0 * fwd if shape.kind == "train" else fwd
