"""Multi-tenant serving gateway: N tenants, one worker pool, one trace
cache (DESIGN.md SS15).

``engine/runtime.py`` gives the repo ONE threaded serving loop per index.
This module is the tier above it: a ``ServingGateway`` hosts many tenants,
each binding a name to an ``IndexArtifact`` version (forward and/or
reverse) plus a ``TenantPolicy`` — admission limits (max k, max in-flight
tickets), a per-ticket scan budget, a default deadline. ``submit(tenant,
q)`` routes by tenant name to the artifact *fingerprint* registered for
it, admission-validates against the policy, and dispatches through the
tenant's own ``ServingRuntime``.

What makes it a tier rather than a dict of runtimes:

  * **One worker pool.** Every tenant runtime is constructed with
    ``pool=`` (``runtime.WorkerPool``): a fixed set of threads round-robins
    across tenants with non-blocking dispatch-lock acquisition, so one
    tenant's hot-swap / compaction / slow flush never stalls another
    tenant's traffic (the pool docstring is the non-stall contract).
  * **One compiled-trace cache.** Tenants whose configs agree in every
    field except ``scan_budget`` (an execution-only knob threaded as a
    traced operand, never a static) adopt the first such tenant's
    dispatch via ``share_dispatch`` — engine-level for reverse tenants,
    server-level for forward ones. Two tenants with identical
    (rung, k, n_cand, scan) signatures therefore share one executable,
    and ``warmup()`` is gateway-wide: it warms one representative per
    share group and re-baselines every member, so
    ``stats().traces_after_warmup == 0`` holds across ALL tenants after
    one warmup pass (pinned by tests/test_gateway.py).
  * **Budgets that are visible, never silent.** A tenant's
    ``scan_budget`` caps how many index tiles the reverse execute scan
    may visit per query (core/sah.py): lanes of a budget-exhausted query
    resolve conservatively ("not in the audience"), the ticket comes
    back ``truncated=True`` with the batch's pruning-funnel snapshot,
    and ``RuntimeStats.truncated`` attributes the count per tenant.
  * **Per-tenant lifecycle.** ``swap`` / ``insert_items`` /
    ``delete_items`` / ``request_compaction`` address one tenant and ride
    that tenant's own locks; routing fingerprints follow the live
    version.

Answers are bitwise identical to a dedicated per-tenant runtime: the
gateway adds admission and routing, never a private dispatch path.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

from repro.dist.policy import NO_SHARDING, ShardingPolicy
from repro.engine import runtime as _runtime
from repro.engine import serving as _serving
from repro.engine.artifact import IndexArtifact
from repro.engine.engine import RkMIPSEngine


@dataclasses.dataclass(frozen=True)
class TenantPolicy:
    """Admission + execution limits for one gateway tenant.

    max_k          largest ``k`` a ticket may ask for (None: the artifact
                   config's own ``k_max`` is the only cap).
    max_in_flight  admission cap on unresolved tickets; a submit past it
                   is rejected up front (None: unbounded).
    scan_budget    per-query cap on reverse execute tile visits
                   (``EngineConfig.scan_budget``; 0 = uncapped). An
                   execution-only knob: it never enters artifact
                   fingerprints and budgeted tenants share unbudgeted
                   tenants' executables (the budget is a traced operand).
    deadline       default per-ticket wall-clock budget in seconds
                   (None: no deadline); ``submit(deadline=)`` overrides.
    """

    max_k: int | None = None
    max_in_flight: int | None = None
    scan_budget: int = 0
    deadline: float | None = None

    def __post_init__(self):
        if self.max_k is not None and self.max_k < 1:
            raise ValueError(f"max_k must be >= 1, got {self.max_k}")
        if self.max_in_flight is not None and self.max_in_flight < 1:
            raise ValueError(f"max_in_flight must be >= 1, got "
                             f"{self.max_in_flight}")
        if self.scan_budget < 0:
            raise ValueError(f"scan_budget must be >= 0 (0 = uncapped), "
                             f"got {self.scan_budget}")


class GatewayStats(NamedTuple):
    """``ServingGateway.stats()`` snapshot.

    tenants:              per-tenant ``RuntimeStats`` — counters are
                          attributed to the tenant whose runtime did the
                          work, never pooled (stats isolation is pinned
                          by tests/test_gateway.py); queue and linger
                          waits (``queue_wait_s``, ``linger_s``) too.
    traces_after_warmup:  gateway-wide traces since ``warmup()``, summed
                          over *distinct* share groups (a trace a shared
                          dispatch cost is counted once, not once per
                          sharer). 0 after a gateway-wide warmup until
                          something actually re-traces.
    """

    tenants: dict
    traces_after_warmup: int


class _Tenant(NamedTuple):
    runtime: object            # ServingRuntime
    policy: TenantPolicy
    mode: str                  # "forward" | "reverse"
    traces: object             # the share group's _TraceCount


class ServingGateway:
    """N tenants, one worker pool, one trace cache (module docstring).

    Parameters:
      pool_workers   dispatch threads shared by every tenant.
      poll_interval  pool idle wakeup (seconds); bounds pooled linger
                     latency (each tenant's ``RuntimeStats.linger_s``
                     measures what the linger really held).
    """

    def __init__(self, *, pool_workers: int = 1,
                 poll_interval: float = 0.01):
        self.pool = _runtime.WorkerPool(pool_workers,
                                        poll_interval=poll_interval)
        self._tenants: dict[str, _Tenant] = {}
        self._fingerprints: dict[str, str] = {}   # tenant -> live version
        self._group_base: dict[int, tuple[object, int]] = {}
        self._closed = False

    # -- registration ------------------------------------------------------

    def _share_donor(self, config, sharding: ShardingPolicy, mode: str):
        """The first registered tenant this one can adopt a dispatch
        from: same mode, same mesh, and (reverse) a config equal in every
        field except ``scan_budget``. Forward dispatch closures are
        config-free, so mesh identity alone suffices there."""
        for t in self._tenants.values():
            if t.mode != mode:
                continue
            if mode == "reverse":
                donor = t.runtime.server.engine
                if donor.policy.mesh is not sharding.mesh:
                    continue
                if donor.config.replace(scan_budget=config.scan_budget) \
                        != config:
                    continue
                return donor
            donor = t.runtime.server
            if donor.policy.mesh is not sharding.mesh:
                continue
            return donor
        return None

    def register(self, name: str, artifact: IndexArtifact, *,
                 policy: TenantPolicy | None = None, k: int | None = None,
                 sharding: ShardingPolicy = NO_SHARDING,
                 mode: str = "auto", **runtime_kwargs):
        """Bind ``name`` to an artifact version + policy; returns the
        tenant's ``ServingRuntime``.

        ``mode`` is "reverse" (RkMIPS, needs a user-side build),
        "forward" (kMIPS retrieval), or "auto" (reverse iff the artifact
        carries users). Extra keyword args go to ``ServingRuntime``
        (compaction, artifact_dir, batch_linger, ...). The runtime is
        pooled — never pass ``pool=``/``workers=`` here.
        """
        if self._closed:
            raise RuntimeError("gateway is closed: no new tenants")
        if name in self._tenants:
            raise ValueError(f"tenant {name!r} is already registered; "
                             f"swap(name, artifact) replaces its version")
        policy = TenantPolicy() if policy is None else policy
        if mode == "auto":
            mode = "reverse" if artifact.users is not None else "forward"
        if mode not in ("forward", "reverse"):
            raise ValueError(f"mode must be 'auto', 'forward' or "
                             f"'reverse', got {mode!r}")
        if mode == "reverse" and artifact.users is None:
            raise ValueError(
                f"tenant {name!r}: mode='reverse' needs an artifact built "
                f"for RkMIPS (users=None in this one)")
        for bad in ("pool", "workers", "deadline"):
            if bad in runtime_kwargs:
                raise ValueError(f"register() manages {bad!r} itself: the "
                                 f"pool is gateway-wide and the deadline "
                                 f"comes from TenantPolicy")

        cfg = artifact.config.replace(scan_budget=policy.scan_budget)
        donor = self._share_donor(cfg, sharding, mode)
        if mode == "reverse":
            engine = RkMIPSEngine(cfg, policy=sharding,
                                  share_dispatch=donor).attach(artifact)
            server = _serving.ReverseServer(engine)
            traces = engine._traces
        else:
            if policy.scan_budget:
                raise ValueError(
                    f"tenant {name!r}: scan_budget is a reverse-pipeline "
                    f"knob (the forward scan has no execute loop to cap)")
            server = _serving.RetrievalServer.from_artifact(
                artifact, policy=sharding, share_dispatch=donor)
            traces = server._traces
        rt = _runtime.ServingRuntime(server, k=k, pool=self.pool,
                                     deadline=policy.deadline,
                                     **runtime_kwargs)
        self._tenants[name] = _Tenant(rt, policy, mode, traces)
        self._fingerprints[name] = artifact.fingerprint
        return rt

    # -- routing + admission -----------------------------------------------

    def _entry(self, tenant: str) -> _Tenant:
        try:
            return self._tenants[tenant]
        except KeyError:
            raise KeyError(
                f"unknown tenant {tenant!r}: registered tenants are "
                f"{sorted(self._tenants)}") from None

    def route(self, tenant: str) -> str:
        """The artifact fingerprint ``tenant`` currently routes to (the
        live version's content hash — follows swaps and churn)."""
        self._entry(tenant)
        return self._fingerprints[tenant]

    def submit(self, tenant: str, q, *, k: int | None = None, **kwargs):
        """Admit a query for ``tenant`` -> ``ServeTicket`` (one per row
        for a block). Routing is by registered name; admission validates
        against the tenant's ``TenantPolicy`` with explicit rejection
        messages (never a silent drop):

          * unknown tenant            -> KeyError naming the known ones
          * k above ``max_k``         -> ValueError naming both numbers
          * ``max_in_flight`` reached -> RuntimeError naming the cap

        Everything else (dtype/shape validation, deadlines, signature
        batching) is the tenant runtime's own ``submit``.
        """
        t = self._entry(tenant)
        ask = t.runtime._default_k if k is None else k
        if t.policy.max_k is not None and ask is not None \
                and ask > t.policy.max_k:
            raise ValueError(f"tenant {tenant!r}: k={ask} exceeds policy "
                             f"max_k={t.policy.max_k}")
        if t.policy.max_in_flight is not None \
                and t.runtime.pending >= t.policy.max_in_flight:
            raise RuntimeError(
                f"tenant {tenant!r}: {t.runtime.pending} tickets in "
                f"flight >= policy max_in_flight="
                f"{t.policy.max_in_flight}; resolve or drain first")
        return t.runtime.submit(q, k=k, **kwargs)

    # -- gateway-wide warmup + stats ---------------------------------------

    def warmup(self, ks=None) -> int:
        """Gateway-wide AOT warmup (DESIGN.md SS14/SS15): for each
        *share group* (tenants adopting one compiled dispatch), warm one
        representative at the union of the group's default ks (plus
        ``ks``), then re-baseline every tenant — warming N tenants that
        share a signature traces it once, and afterwards
        ``stats().traces_after_warmup == 0`` across all tenants. Returns
        the number of (bucket, k) cells compiled."""
        groups: dict[int, tuple[_Tenant, set]] = {}
        for t in self._tenants.values():
            rep, want = groups.setdefault(id(t.traces), (t, set()))
            if t.runtime._default_k is not None:
                want.add(t.runtime._default_k)
            if ks is not None:
                want.update(ks)
        cells = 0
        for rep, want in groups.values():
            if want:
                cells += rep.runtime.warmup(sorted(want))
        self._group_base = {
            gid: (rep.traces, rep.traces.n)
            for gid, (rep, _) in groups.items()}
        for t in self._tenants.values():
            t.runtime.rebaseline_traces()
        return cells

    def stats(self) -> GatewayStats:
        """Per-tenant ``RuntimeStats`` + gateway-wide traces since the
        last ``warmup()`` (summed over distinct share groups; before any
        warmup it counts every trace the gateway's tenants have cost)."""
        if self._group_base:
            traces = sum(tc.n - base
                         for tc, base in self._group_base.values())
        else:
            seen: dict[int, int] = {}
            for t in self._tenants.values():
                seen[id(t.traces)] = t.traces.n
            traces = sum(seen.values())
        return GatewayStats(
            tenants={name: t.runtime.stats
                     for name, t in self._tenants.items()},
            traces_after_warmup=traces)

    # -- per-tenant lifecycle ----------------------------------------------

    def runtime(self, tenant: str):
        """The tenant's ``ServingRuntime`` (escape hatch: drain one
        tenant, read ``last_compaction_seconds``, ...)."""
        return self._entry(tenant).runtime

    def swap(self, tenant: str, artifact: IndexArtifact) -> None:
        """Hot-swap ``tenant``'s live version (between that tenant's
        flushes — other tenants' dispatch never waits on it: the pool
        skips a locked tenant). Routing follows: ``route(tenant)`` is the
        new fingerprint."""
        t = self._entry(tenant)
        t.runtime.swap(artifact)
        self._fingerprints[tenant] = artifact.fingerprint

    def insert_items(self, tenant: str, rows) -> IndexArtifact:
        """Stage rows into ``tenant``'s delta buffer; returns (and
        routes to) the new version."""
        t = self._entry(tenant)
        art = t.runtime.insert_items(rows)
        self._fingerprints[tenant] = art.fingerprint
        return art

    def delete_items(self, tenant: str, ids) -> IndexArtifact:
        """Retire rows on ``tenant``'s live version; returns (and routes
        to) the new version."""
        t = self._entry(tenant)
        art = t.runtime.delete_items(ids)
        self._fingerprints[tenant] = art.fingerprint
        return art

    def request_compaction(self, tenant: str) -> None:
        """Ask ``tenant``'s maintenance thread for a compaction now
        (requires that tenant registered with ``compaction=True``)."""
        self._entry(tenant).runtime.request_compaction()

    # -- lifecycle ---------------------------------------------------------

    @property
    def tenants(self) -> tuple[str, ...]:
        return tuple(self._tenants)

    def drain(self, timeout: float | None = None) -> bool:
        """Block until every tenant's admitted tickets have resolved."""
        ok = True
        for t in self._tenants.values():
            ok = t.runtime.drain(timeout) and ok
        return ok

    def close(self, *, drain: bool = True,
              timeout: float | None = None) -> None:
        """Close every tenant runtime (optionally draining), then stop
        the shared pool. Idempotent."""
        if self._closed:
            return
        self._closed = True
        for t in self._tenants.values():
            t.runtime.close(drain=drain, timeout=timeout)
        self.pool.close()

    def __enter__(self) -> "ServingGateway":
        return self

    def __exit__(self, *exc) -> None:
        self.close(drain=exc == (None, None, None))
