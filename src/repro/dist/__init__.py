"""Distribution layer: sharding policies. See DESIGN.md SS5."""

from repro.dist.policy import (
    NO_SHARDING,
    ShardingPolicy,
    lm_rules,
)

__all__ = ["NO_SHARDING", "ShardingPolicy", "lm_rules"]
