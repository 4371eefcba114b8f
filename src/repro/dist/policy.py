"""ShardingPolicy: the one object that carries "how is this run sharded".

Models never mention meshes or collectives directly (except inside their own
shard_map bodies); they take a ``ShardingPolicy`` and call
``policy.constrain(x, rule_name)`` at the layout boundaries DESIGN.md SS5
names. The policy is a mesh plus a dict of named PartitionSpec rules, so the
same model code runs:

  * single-device (``NO_SHARDING``): every constrain is a transparent no-op;
  * under any mesh: ``constrain`` applies ``with_sharding_constraint`` with a
    ``NamedSharding(mesh, rules[name])``; unknown rule names are no-ops, so a
    policy only needs to pin the boundaries it cares about.

Rule names are a closed vocabulary (see DESIGN.md SS5 for the full table):

  activations   act_btd (B,T,D) residual stream; act_attn_in (B,T,D) at the
                SP->TP boundary; act_bhsd (B,H,S,Dh) head-split attention;
                act_btf (B,T,F) FFN hidden; logits (B,T,V); kv_cache
                (L,B,Hkv,S,Dh)
  LM params     p_embed (V,D), p_head (D,V), p_norm, p_attn_in / p_attn_out,
                p_mlp_in / p_mlp_out, p_router, p_expert_in / p_expert_out
                -- stacked-layer leaves carry a leading (L,) axis, so the
                p_* specs for per-layer tensors start with None.

``lm_rules`` builds the standard TP/SP rule set (Megatron-style tensor
parallelism with sequence-parallel norm/residual regions) or, with
``pure_dp=True``, the ZeRO-1-style pure data-parallel set where every mesh
axis acts as batch and parameters are replicated.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

# Mesh axes that act as batch ("data-parallel") axes anywhere in the stack.
# launch/mesh.py builds ("data", "model") and ("pod", "data", "model").
DP_AXIS_NAMES = ("pod", "data")
TP_AXIS_NAME = "model"


@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    """A mesh + named PartitionSpec rules; the unit of sharding injection.

    mesh=None (or a rule name absent from ``rules``) makes every method a
    no-op / identity, so NO_SHARDING-path code is byte-identical to the
    sharded path minus the layout pins.
    """

    mesh: Mesh | None = None
    rules: Mapping[str, P] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        # ``jax.make_mesh`` types its axes Explicit, and eager ops that mix
        # arrays committed to such a mesh with unsharded ones (the engine's
        # host-side post-processing of sharded results) are rejected. The
        # stack shards by PartitionSpec rules and shard_map, which is the
        # Auto contract, so the policy holds the Auto view of the same
        # devices (Mesh objects are interned: equal meshes stay identical).
        if self.mesh is not None and any(
                t != AxisType.Auto for t in self.mesh.axis_types):
            object.__setattr__(self, "mesh", Mesh(
                self.mesh.devices, self.mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(self.mesh.axis_names)))

    # -- rule lookup -------------------------------------------------------

    def spec(self, name: str) -> P | None:
        """The PartitionSpec registered under ``name`` (None if absent)."""
        return self.rules.get(name)

    def sharding(self, name: str) -> NamedSharding | None:
        """NamedSharding for a rule, or None when unsharded/unknown."""
        spec = self.rules.get(name)
        if self.mesh is None or spec is None:
            return None
        return NamedSharding(self.mesh, spec)

    def constrain(self, x, name: str):
        """Pin ``x`` to the layout of rule ``name`` (identity if unknown)."""
        sh = self.sharding(name)
        if sh is None:
            return x
        return jax.lax.with_sharding_constraint(x, sh)

    # -- mesh geometry -----------------------------------------------------

    def dp_axes(self) -> tuple[str, ...]:
        """Mesh axes that shard the batch dimension, in mesh order."""
        if self.mesh is None:
            return ()
        return tuple(a for a in DP_AXIS_NAMES if a in self.mesh.shape)

    def axis_size(self, axis: str) -> int:
        if self.mesh is None or axis not in self.mesh.shape:
            return 1
        return int(self.mesh.shape[axis])

    @property
    def dp_size(self) -> int:
        size = 1
        for a in self.dp_axes():
            size *= self.axis_size(a)
        return size

    @property
    def model_axis_size(self) -> int:
        """Size of the tensor/model-parallel axis (1 without a mesh)."""
        return self.axis_size(TP_AXIS_NAME)

    @property
    def device_count(self) -> int:
        """Total device count of the mesh (1 without a mesh) — the shard
        count of anything row-sharded over every mesh axis (the RkMIPS
        engine's user/item rows, the staged build's row-parallel stages)."""
        if self.mesh is None:
            return 1
        return int(self.mesh.devices.size)


NO_SHARDING = ShardingPolicy(mesh=None, rules={})


def _axes_tuple(axes) -> tuple[str, ...]:
    if axes is None:
        return ()
    if isinstance(axes, str):
        return (axes,)
    return tuple(axes)


def lm_rules(dp_axes, tp_axis: str, *, pure_dp: bool = False) -> dict[str, P]:
    """The LM rule set launch/cells.py builds policies from.

    dp_axes: mesh axes sharding the batch (e.g. ("data",) or
    ("pod", "data")); tp_axis: the tensor-parallel axis ("model").

    pure_dp=True: ZeRO-1-style pure data parallelism -- every mesh axis
    (dp + tp) shards the batch, parameters are replicated (P() leaves;
    optimizer state is device-count-sharded separately by the cell builder).

    Default: TP/SP. Batch over dp. The residual stream (act_btd) is
    sequence-parallel (T over tp) between blocks; act_attn_in gathers the
    sequence axis once at the attention input (the SP->TP boundary), after
    which heads (act_bhsd), the FFN hidden (act_btf) and the vocab (logits)
    are tp-sharded. Parameter rules follow Megatron: column-parallel in
    (p_attn_in, p_mlp_in -> output-feature over tp), row-parallel out
    (p_attn_out, p_mlp_out -> input-feature over tp), vocab-sharded embedding
    and head, replicated norms and router, expert-sharded MoE weights
    (expert axis over tp == expert parallelism, models/moe.py). Per-layer
    p_* specs carry a leading None for the stacked (L,) layer axis.
    """
    dp = _axes_tuple(dp_axes)
    tp = tp_axis
    if pure_dp:
        batch = dp + (tp,)
        return {
            "act_btd": P(batch, None, None),
            "act_attn_in": P(batch, None, None),
            "act_bhsd": P(batch, None, None, None),
            "act_btf": P(batch, None, None),
            "logits": P(batch, None, None),
            "kv_cache": P(None, batch, None, None, None),
            "p_embed": P(), "p_head": P(), "p_norm": P(),
            "p_attn_in": P(), "p_attn_out": P(),
            "p_mlp_in": P(), "p_mlp_out": P(),
            "p_router": P(), "p_expert_in": P(), "p_expert_out": P(),
        }
    return {
        "act_btd": P(dp, tp, None),
        "act_attn_in": P(dp, None, None),
        "act_bhsd": P(dp, tp, None, None),
        "act_btf": P(dp, None, tp),
        "logits": P(dp, None, tp),
        "kv_cache": P(None, dp, None, None, None),
        "p_embed": P(tp, None),
        "p_head": P(None, tp),
        "p_norm": P(),
        "p_attn_in": P(None, None, tp),
        "p_attn_out": P(None, tp, None),
        "p_mlp_in": P(None, None, tp),
        "p_mlp_out": P(None, tp, None),
        "p_router": P(),
        "p_expert_in": P(None, tp, None, None),
        "p_expert_out": P(None, tp, None, None),
    }
