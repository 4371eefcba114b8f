"""Staged, mesh-parallel SAH index build (DESIGN.md SS11).

``core/sah.py::build`` is a composition of four pure stages (Algorithm 4):

  1. norm_split     -- item norm-sort + top-n_top split       (sequential)
  2. item_codes     -- SA-ALSH partition/transform/SRP codes  (rows: items)
  3. user_blocking  -- cone-tree / "norm" blocking of users   (sequential)
  4. lower_bounds   -- Simpfer L_u / L_B over P'              (rows: users)

``build_sah_index`` here composes the SAME stage functions, adding two
things the core composition does not have: a per-stage wall-time breakdown
(``BuildTimings``) and optional mesh parallelism for the row-parallel
steps. Stage 2's SRP hashing is independent per item row and stage 4's
lower-bound GEMM + top_k is independent per user row (the m x n_top GEMM
is the dominant build cost at scale), so both steps shard over every mesh
axis via ``shard_map`` with dead zero-row padding when the row count does
not divide the device count (the PR-3 convention). Row slicing is bitwise
equal to the full-array computation for both steps, so:

  **invariant: the sharded build on any mesh produces a fingerprint-
  identical ``IndexArtifact`` to the single-device build** (pinned by
  tests/test_build.py, including prime row counts and 1x8 vs 2x4 meshes).

The sequential stages (sort, partition scan, cone tree) always run
replicated/single-device; they are cheap relative to the GEMMs and their
output feeds every shard anyway.

Sharding is selected by ``EngineConfig.build_sharding``:

  "auto"    -- shard when the policy carries a multi-device mesh (default);
  "single"  -- always run today's single-device path, even under a mesh;
  "sharded" -- require a multi-device mesh (ValueError otherwise).

``shards`` is a testing seam: it simulates the shard_map row slicing
in-process (pad, per-slice compute, concatenate) so single-device tests
can pin the bitwise-equality invariant for arbitrary shard counts without
a mesh; real meshes are covered by the subprocess tests.
"""

from __future__ import annotations

import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import sa_alsh as _alsh
from repro.core import sah as _sah
from repro.core import simpfer as _simpfer
from repro.dist.policy import NO_SHARDING, ShardingPolicy
from repro.engine.config import EngineConfig
from repro.kernels import ops as kops

BUILD_SHARDING_MODES = ("auto", "single", "sharded")


class BuildTimings(NamedTuple):
    """Wall seconds per build stage (compile included on first build)."""

    norm_split: float      # stage 1: item sort + top-n_top split
    item_codes: float      # stage 2: SA-ALSH partitions/transform/codes
    user_blocking: float   # stage 3: cone / norm blocking of users
    lower_bounds: float    # stage 4: Simpfer L_u / L_B over P'
    sharded: bool          # whether stages 2b/4 ran under shard_map

    @property
    def total(self) -> float:
        return (self.norm_split + self.item_codes + self.user_blocking
                + self.lower_bounds)

    def format(self) -> str:
        """One human-readable breakdown line (examples/quickstart.py)."""
        mode = "sharded" if self.sharded else "single-device"
        return (f"build {self.total * 1e3:.1f} ms ({mode}): "
                f"norm-split {self.norm_split * 1e3:.1f} | "
                f"item-codes {self.item_codes * 1e3:.1f} | "
                f"user-blocking {self.user_blocking * 1e3:.1f} | "
                f"lower-bounds {self.lower_bounds * 1e3:.1f}")


def validate_build_knobs(config: EngineConfig) -> None:
    """Reject unusable build knobs before any tracing happens.

    ``EngineConfig.__post_init__`` validates at construction, but configs
    can reach a build without re-running it (``object.__setattr__`` on the
    frozen instance, unpickled/manually wired objects, subclasses that
    skip init). The build entry points re-check the knobs that would
    otherwise surface as shape errors deep inside jitted stage bodies.
    """
    for name in ("k_max", "leaf_size", "n_bits", "tile", "max_partitions"):
        v = getattr(config, name)
        if not isinstance(v, int) or isinstance(v, bool) or v < 1:
            raise ValueError(f"build knob {name} must be a positive int, "
                             f"got {v!r}")
    if config.n_bits % 32 != 0:
        raise ValueError(f"build knob n_bits must be a multiple of 32, "
                         f"got {config.n_bits}")
    if config.n_top is not None and config.n_top < config.k_max:
        raise ValueError(f"build knob n_top ({config.n_top}) must be >= "
                         f"k_max ({config.k_max})")
    if getattr(config, "build_sharding", "auto") not in BUILD_SHARDING_MODES:
        raise ValueError(f"build_sharding must be one of "
                         f"{BUILD_SHARDING_MODES}, "
                         f"got {config.build_sharding!r}")


def _want_sharded(config: EngineConfig, policy: ShardingPolicy,
                  shards: int | None) -> bool:
    mode = config.build_sharding
    have = policy.device_count > 1 or (shards is not None and shards > 1)
    if mode == "single":
        return False
    if mode == "sharded":
        if not have:
            raise ValueError(
                "build_sharding='sharded' requires a multi-device mesh "
                "policy (or the `shards` testing seam); pass a mesh "
                "ShardingPolicy or use build_sharding='auto'")
        return True
    return have


def _pad_rows_zero(rows: jnp.ndarray, n_pad: int) -> jnp.ndarray:
    if n_pad == rows.shape[0]:
        return rows
    return jnp.concatenate(
        [rows, jnp.zeros((n_pad - rows.shape[0],) + rows.shape[1:],
                         rows.dtype)])


def row_parallel(fn, rows: jnp.ndarray, consts: tuple = (), *,
                 policy: ShardingPolicy = NO_SHARDING,
                 shards: int | None = None) -> jnp.ndarray:
    """Run a per-row function over row shards; bitwise == ``fn(rows, ...)``.

    ``fn(rows_slice, *consts) -> (r, ...)`` must be independent per row
    (row i of the output depends only on row i of the input and the
    replicated ``consts``). Rows are padded with dead zero rows to the
    next shard multiple and the padding is sliced off the gathered result,
    so any row count runs on any mesh (the PR-3 convention).

    With a mesh policy: one eager ``shard_map`` over every mesh axis (an
    outer jit around shard_map once miscompiled the while-driver, DESIGN.md
    SS7; the bodies here are embarrassingly parallel, but the engine-wide
    convention is eager dispatch). With ``shards``: the
    mesh-free simulation — per-slice compute + concatenate — used by the
    tests to pin the invariant in-process. Otherwise: ``fn`` unchanged.
    """
    if policy.mesh is not None and policy.device_count > 1:
        s = policy.device_count
        n = rows.shape[0]
        padded = _pad_rows_zero(rows, -(-n // s) * s)
        axes = tuple(policy.mesh.axis_names)
        row_spec = P(axes, *([None] * (rows.ndim - 1)))
        out = jax.shard_map(
            fn, mesh=policy.mesh,
            in_specs=(row_spec,) + tuple(P() for _ in consts),
            out_specs=P(axes, None), check_vma=False)(padded, *consts)
        # Gather to host layout before anything downstream touches the
        # result: the artifact contract is mesh-agnostic leaves, and eager
        # ops on an array still committed to the mesh run through implicit
        # GSPMD partitioning, which was observed to miscompile (the same
        # family as the outer-jit shard_map bug, DESIGN.md SS7) — attach-
        # time pad_index on a committed block_lb corrupted real entries.
        return jnp.asarray(np.asarray(out)[:n])
    if shards is not None and shards > 1:
        n = rows.shape[0]
        padded = _pad_rows_zero(rows, -(-n // shards) * shards)
        per = padded.shape[0] // shards
        out = jnp.concatenate(
            [fn(padded[i * per:(i + 1) * per], *consts)
             for i in range(shards)])
        return out[:n]
    return fn(rows, *consts)


def build_sah_index(items: jnp.ndarray, users: jnp.ndarray,
                    key: jax.Array, *, config: EngineConfig,
                    policy: ShardingPolicy = NO_SHARDING,
                    shards: int | None = None
                    ) -> tuple[_sah.SAHIndex, BuildTimings]:
    """Algorithm 4 as the staged pipeline: (SAHIndex, BuildTimings).

    Composes the same stage functions as ``core/sah.py::build`` in the
    same order, so the single-device result is bitwise identical to
    ``sah.build(items, users, key, **config.build_kwargs())`` — and the
    sharded result is bitwise identical to the single-device one (module
    docstring). The returned index is host/mesh-agnostic; ``attach`` lays
    it out for a query mesh separately.
    """
    validate_build_knobs(config)
    sharded = _want_sharded(config, policy, shards)
    n_top = 2 * config.k_max if config.n_top is None else config.n_top
    k_idx, k_cone = _sah.build_keys(key)

    t0 = time.perf_counter()
    split = _sah.split_items_by_norm(items, n_top)
    jax.block_until_ready(split.rest)
    t1 = time.perf_counter()

    hash_rows = None
    if sharded:
        hash_rows = lambda rows, proj: row_parallel(
            kops.srp_hash, rows, (proj,), policy=policy, shards=shards)
    alsh = _alsh.build_index(split.rest, k_idx, b=config.b,
                             n_bits=config.n_bits, tile=config.tile,
                             max_partitions=config.max_partitions,
                             transform=config.transform,
                             hash_rows=hash_rows)
    alsh = _sah.shift_item_ids(alsh, split.order, n_top)
    jax.block_until_ready(alsh.codes)
    t2 = time.perf_counter()

    blocked = _sah.block_users(users, k_cone, leaf_size=config.leaf_size,
                               blocking=config.blocking)
    jax.block_until_ready(blocked.users)
    t3 = time.perf_counter()

    lb_rows = None
    if sharded:
        kmax = config.k_max
        lb_rows = lambda rows, top, _k: row_parallel(
            lambda r, t: _simpfer.user_lower_bounds_impl(r, t, kmax),
            rows, (top,), policy=policy, shards=shards)
    lb, block_lb = _sah.lower_bounds(blocked.users, blocked.user_mask,
                                     split.top_items, config.k_max,
                                     blocked.center.shape[0],
                                     lb_rows=lb_rows)
    jax.block_until_ready(lb)
    t4 = time.perf_counter()

    index = _sah.SAHIndex(alsh=alsh, users=blocked.users,
                          user_ids=blocked.user_ids,
                          user_mask=blocked.user_mask,
                          center=blocked.center, omega=blocked.omega,
                          theta=blocked.theta, user_lb=lb,
                          block_lb=block_lb, top_norms=split.top_norms,
                          top_items=split.top_items, top_ids=split.top_ids)
    timings = BuildTimings(norm_split=t1 - t0, item_codes=t2 - t1,
                           user_blocking=t3 - t2, lower_bounds=t4 - t3,
                           sharded=sharded)
    return index, timings
