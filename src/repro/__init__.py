"""repro: a production-grade JAX framework reproducing and extending

SAH: Shifting-aware Asymmetric Hashing for Reverse k-Maximum Inner Product
Search (Huang, Wang, Tung; AAAI 2023).

Layers:
  core/     the paper's contribution (SAT, SA-ALSH, cone blocking, SAH engine)
  kernels/  Pallas TPU kernels for the compute hot spots (hamming scan, srp hash,
            fused ip+topk) with jnp oracles
  models/   LM transformers (dense + MoE), GAT, recsys models
  data/     synthetic data pipelines, graph sampler
  train/    optimizer, trainer, checkpointing, compression
  dist/     sharding policies, distributed decode, collective helpers
  configs/  assigned architecture configs
  launch/   mesh, dry-run, train/serve drivers
"""

__version__ = "1.0.0"

# The engine registry is the package's front door (DESIGN.md SS7): every
# paper baseline is a named preset config of one RkMIPSEngine. Re-exported
# lazily (PEP 562): the engine pulls in repro.core, whose module-level jnp
# constants initialize the jax backend — and `python -m repro.launch.dryrun`
# runs this package init BEFORE it can set the fake-device-count flag, so
# `import repro` must stay backend-free (SS1).
__all__ = [
    "EngineConfig",
    "IndexArtifact",
    "PAPER_BASELINES",
    "RkMIPSEngine",
    "ServingRuntime",
    "TicketExpired",
    "display_name",
    "get_config",
    "load_artifact",
    "method_names",
    "register",
]


def __getattr__(name):
    if name in __all__:
        from repro import engine as _engine
        return getattr(_engine, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(__all__))
