"""Chip smoke: the RkMIPS serving path once, end to end, on one TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the user-sharded path on four

Deployment: the Netflix Prize rating matrix, 17,770 items x 480,189 users
(the Netflix Prize dataset description), with d = 64 latent factors
(assumed: the source fixes no width; 64 is the repo's default) and
embeddings generated from ``--seed`` by ``data/synthetic.py``. The index is
the ``sah`` preset with k_max = 50; the whole user side lives on the chip.

One process owns the chip and runs, in order: device check, build,
serving through a ``ServingGateway`` (reverse tenants at f32 and int8 scan
precision, one forward tenant), answer checks against the exact reference
(``core/exact.py`` at ``Precision.HIGHEST``), and a check that the query
programs carry the Pallas kernels as ``tpu_custom_call``s. Any failed
phase raises, so the process exits non-zero. The last line of standard
output is one JSON object: ``{"ok": true, "device": {...}}``.

``--four-chips`` runs only the user-sharded RkMIPS path over a 4-device
``("data",)`` mesh, against the same reference and a one-chip run.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import re
import sys
import time

NETFLIX = dict(n_items=17_770, m_users=480_189, d=64)
KS = (10, 50)
F1_FLOOR = 0.90          # the paper's reported F1 floor
RECALL_FLOOR = 0.90
REVERSE_TENANTS = {"reverse-f32": "f32", "reverse-int8": "int8"}
QUERY_RANKS = (16, 24, 32, 48, 64, 96, 128, 192)
# The forward tenant's re-rank depth: its single-pass scan re-ranks the
# n_cand nearest codes of the whole slab, and 1,024 of 17,770 holds
# recall@50 above the floor at 128 bits (64, the reverse scan's per-tile
# depth, gave 0.60 on the CPU at this corpus).
FORWARD_N_CAND = 1024
# kernels each served program must carry (the stable pallas_call names)
PROGRAM_KERNELS = {
    "reverse-f32": ("srp_hash", "hamming_scores"),
    "reverse-int8": ("srp_hash", "fused_scan"),
    "forward": ("srp_hash", "hamming_scores"),
}


class SmokeFailure(RuntimeError):
    """A phase of the smoke found something wrong."""


def _import_repo():
    """Put the checkout's ``src`` on the path; refuse to run without it."""
    src = pathlib.Path(__file__).resolve().parent / "src"
    if not (src / "repro" / "__init__.py").is_file():
        raise SmokeFailure(f"no repro package under {src}: run chip_smoke.py "
                           f"from a checkout of the repository")
    sys.path.insert(0, str(src))


# -- phase 1: device --------------------------------------------------------

def device_check(platform: str = "tpu") -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if info["platform"] != platform:
        raise SmokeFailure(f"expected a {platform} device, JAX found "
                           f"{info['platform']}")
    return info


# -- phase 2: build ---------------------------------------------------------

def make_corpus(seed: int, n_items: int, m_users: int, d: int):
    """(items, users, build key), all from ``seed``."""
    import jax
    from repro.data import synthetic
    k_data, k_build = jax.random.split(jax.random.PRNGKey(seed))
    items, users = synthetic.recommendation_data(k_data, n_items, m_users, d)
    return items, users, k_build


def build(items, users, key, *, config=None, policy=None):
    from repro import RkMIPSEngine, get_config
    from repro.dist import NO_SHARDING
    cfg = config or get_config("sah").replace(k_max=50)
    eng = RkMIPSEngine(cfg, policy=policy or NO_SHARDING).build(
        items, users, key)
    print(f"build: {eng.build_seconds:.3f} s ({items.shape[0]} items x "
          f"{users.shape[0]} users, d={items.shape[1]}); "
          f"{eng.build_timings.format()}", flush=True)
    return eng


def query_sets(items, ranks=QUERY_RANKS) -> dict:
    """Reverse query sets: ``ranked`` promotes the items at these
    descending-norm ranks. The synthetic corpus has a sharp norm head:
    items past rank ~100 reach no user's top-50, items in the first few
    dozen reach tens of thousands, so the ranks span audiences from empty
    to large and the larger ones reach the execute-phase tile scan."""
    import jax.numpy as jnp
    order = jnp.argsort(-jnp.linalg.norm(items, axis=-1))
    return {"ranked": items[order[jnp.asarray(ranks)]]}


# -- phase 3: serve ---------------------------------------------------------

def _tenant_artifact(artifact, **overrides):
    """The same built index under execution-only config changes (scan
    precision is excluded from the fingerprint, DESIGN.md SS13)."""
    from repro import IndexArtifact
    return IndexArtifact(
        config=artifact.config.replace(**overrides), key=artifact.key,
        items=artifact.items, users=artifact.users, index=artifact.index,
        kmips_index=artifact.kmips_index, deleted=artifact.deleted,
        delta_items=artifact.delta_items, delta_mask=artifact.delta_mask,
        delta_used=artifact.delta_used)


def _submit_all(gw, tenant, queries, k, timeout):
    tickets = gw.submit(tenant, queries, k=k)
    results = [t.result(timeout=timeout) for t in tickets]
    return results, [t.latency for t in tickets]


def serve(eng, reverse_sets: dict, forward_queries, ks=KS,
          timeout: float = 600.0) -> dict:
    """Serve every query set through one gateway; returns answers keyed
    (tenant, set, k) plus latencies."""
    import numpy as np
    from repro.engine import ServingGateway
    art = eng.artifact
    out = {"answers": {}, "latency": {}}
    with ServingGateway(pool_workers=2) as gw:
        for name, prec in REVERSE_TENANTS.items():
            gw.register(name, _tenant_artifact(art, scan_precision=prec),
                        mode="reverse")
        gw.register("forward",
                    _tenant_artifact(art, n_cand=FORWARD_N_CAND),
                    mode="forward")
        t0 = time.perf_counter()
        cells = gw.warmup(ks=ks)
        out["warmup_seconds"] = time.perf_counter() - t0
        print(f"warmup: {cells} cells compiled in "
              f"{out['warmup_seconds']:.3f} s", flush=True)
        sets = {("reverse", s): q for s, q in reverse_sets.items()}
        sets[("forward", "users")] = forward_queries
        for tenant in (*REVERSE_TENANTS, "forward"):
            mode = "forward" if tenant == "forward" else "reverse"
            # first ticket after warmup, then the same shape warm
            first = gw.submit(tenant, forward_queries[0] if mode == "forward"
                              else next(iter(reverse_sets.values()))[0],
                              k=ks[0])
            first.result(timeout=timeout)
            lat = [first.latency]
            for (m, s), qs in sets.items():
                if m != mode:
                    continue
                for k in ks:
                    res, lt = _submit_all(gw, tenant, qs, k, timeout)
                    out["answers"][(tenant, s, k)] = res
                    lat += lt
            out["latency"][tenant] = lat
            print(f"serve {tenant}: first ticket {lat[0] * 1e3:.3f} ms, "
                  f"warm tickets p50 {np.median(lat[1:]) * 1e3:.3f} ms "
                  f"over {len(lat) - 1}", flush=True)
        stats = gw.stats()
        print(f"gateway: traces_after_warmup={stats.traces_after_warmup}",
              flush=True)
    return out


# -- phase 4: answers -------------------------------------------------------

def reverse_f1(pred, truth) -> float:
    """Mean F1 of (nq, m) audience predictions against the reference."""
    import jax.numpy as jnp
    from repro.core import metrics
    return float(jnp.mean(metrics.f1_score(jnp.asarray(pred), truth)))


def forward_recall(results, truth_ids) -> float:
    import jax.numpy as jnp
    from repro.core import metrics
    ids = jnp.stack([r.ids for r in results])
    return float(jnp.mean(metrics.recall_at_k(ids, truth_ids)))


def tiles_scanned(results) -> int:
    return int(sum(int(r.stats.tiles_scanned) for r in results))


def check_floor(what: str, value: float, floor: float) -> None:
    if not value >= floor:
        raise SmokeFailure(f"{what} = {value!r} is below the floor {floor}")


def check_tiles(total: int) -> None:
    if total <= 0:
        raise SmokeFailure("no reverse ticket reached the execute-phase tile "
                           "scan: tiles_scanned = 0")


def check_answers(eng, items, reverse_sets, forward_queries, served,
                  ks=KS) -> dict:
    """F1 per reverse tenant, recall of the forward tenant, tiles scanned;
    raises below a floor."""
    import jax.numpy as jnp
    from repro.core import exact
    summary = {}
    truths = {(s, k): eng.oracle(q, k)
              for s, q in reverse_sets.items() for k in ks}
    tiles = 0
    for tenant in REVERSE_TENANTS:
        f1s = []
        for (s, k), truth in truths.items():
            res = served["answers"][(tenant, s, k)]
            f1 = reverse_f1(jnp.stack([r.predictions for r in res]), truth)
            t = tiles_scanned(res)
            tiles += t
            f1s.append(f1)
            print(f"answers {tenant} {s} k={k}: F1 {f1:.6f}, "
                  f"tiles_scanned {t}", flush=True)
        mean = sum(f1s) / len(f1s)
        summary[f"f1_{tenant}"] = mean
        print(f"answers {tenant}: mean F1 {mean:.6f}", flush=True)
        check_floor(f"{tenant} mean F1", mean, F1_FLOOR)
    recalls = []
    for k in ks:
        _, truth_ids = exact.kmips(items, forward_queries, k)
        r = forward_recall(served["answers"][("forward", "users", k)],
                           truth_ids)
        recalls.append(r)
        print(f"answers forward k={k}: recall@k {r:.6f}", flush=True)
    summary["recall_forward"] = sum(recalls) / len(recalls)
    check_floor("forward mean recall@k", summary["recall_forward"],
                RECALL_FLOOR)
    summary["tiles_scanned"] = tiles
    print(f"answers: tiles_scanned {tiles}", flush=True)
    check_tiles(tiles)
    return summary


# -- phase 5: kernels -------------------------------------------------------

def tpu_kernels(lowered_text: str) -> set:
    """Kernel names of the ``tpu_custom_call``s in a lowered program's
    StableHLO text (``pallas_call(name=...)`` becomes ``kernel_name``)."""
    return {m.group(1) for line in lowered_text.splitlines()
            if "@tpu_custom_call" in line
            for m in [re.search(r'kernel_name = "([\w\-]+)"', line)] if m}


def served_programs(eng, batch: int, k: int) -> dict:
    """Lowered StableHLO text of the programs the tenants dispatch: the
    batched reverse pipeline at each scan precision (what the engine's
    dispatch inlines) and the forward sketch scan (what the retrieval
    server runs)."""
    import functools
    import jax
    import jax.numpy as jnp
    from repro.core import sah
    from repro.dist import NO_SHARDING
    from repro.engine import sharding
    cfg = eng.config
    qs = jnp.zeros((batch, eng.index.users.shape[1]), jnp.float32)
    texts = {}
    for name, prec in REVERSE_TENANTS.items():
        kw = cfg.replace(scan_precision=prec).query_kwargs()
        texts[name] = sah.rkmips_batch.lower(
            eng.index, qs, k, scan_budget=jnp.int32(0), **kw).as_text()
    fwd = jax.jit(functools.partial(sharding.kmips_flat, k=k,
                                    policy=NO_SHARDING,
                                    n_cand=FORWARD_N_CAND, scan=cfg.scan))
    texts["forward"] = fwd.lower(eng.kmips_index, qs).as_text()
    return texts


def check_kernels(texts: dict) -> None:
    for program, want in PROGRAM_KERNELS.items():
        have = tpu_kernels(texts[program])
        print(f"kernels {program}: tpu_custom_call {sorted(have)}",
              flush=True)
        missing = set(want) - have
        if missing:
            raise SmokeFailure(f"{program}: kernels {sorted(missing)} are "
                               f"not tpu_custom_calls")


# -- drivers ----------------------------------------------------------------

def run_one_chip(seed: int, *, n_items: int, m_users: int, d: int,
                 ks=KS, ranks=QUERY_RANKS, kernels: bool = True) -> dict:
    items, users, k_build = make_corpus(seed, n_items, m_users, d)
    eng = build(items, users, k_build)
    reverse_sets = query_sets(items, ranks)
    forward_queries = users[:len(ranks)]
    t0 = time.perf_counter()
    served = serve(eng, reverse_sets, forward_queries, ks=ks)
    print(f"serve: {time.perf_counter() - t0:.3f} s", flush=True)
    summary = check_answers(eng, items, reverse_sets, forward_queries,
                            served, ks=ks)
    if kernels:
        t0 = time.perf_counter()
        check_kernels(served_programs(eng, eng.config.serve_batch_size,
                                      ks[0]))
        print(f"kernels: programs lowered in "
              f"{time.perf_counter() - t0:.3f} s", flush=True)
    return summary


def run_four_chips(seed: int, *, n_items: int, m_users: int, d: int,
                   ks=KS, ranks=QUERY_RANKS) -> dict:
    """User-sharded RkMIPS over a 4-device mesh vs the reference and the
    same artifact on one chip."""
    import jax
    import numpy as np
    from repro import RkMIPSEngine
    from repro.dist import ShardingPolicy
    devs = jax.devices()
    if len(devs) < 4:
        raise SmokeFailure(f"--four-chips needs 4 devices, found "
                           f"{len(devs)}")
    mesh = jax.make_mesh((4,), ("data",), devices=devs[:4])
    policy = ShardingPolicy(mesh=mesh)
    items, users, k_build = make_corpus(seed, n_items, m_users, d)
    eng4 = build(items, users, k_build, policy=policy)
    placed = sorted({str(s.device) for s in
                     eng4.index.users.addressable_shards})
    print(f"shards: users {eng4.index.users.shape} on {placed}", flush=True)
    if len(placed) != 4:
        raise SmokeFailure(f"user shards landed on {len(placed)} devices, "
                           f"not 4")
    eng1 = RkMIPSEngine(eng4.config).attach(eng4.artifact)
    summary = {"shard_devices": placed}
    f1s, differ = [], 0
    for s, q in query_sets(items, ranks).items():
        for k in ks:
            t0 = time.perf_counter()
            r4 = eng4.query_batch(q, k)
            t4 = time.perf_counter() - t0
            r1 = eng1.query_batch(q, k)
            truth = eng4.oracle(q, k)
            f1 = reverse_f1(r4.predictions, truth)
            n_diff = int(np.sum(np.asarray(r4.predictions)
                                != np.asarray(r1.predictions)))
            f1s.append(f1)
            differ += n_diff
            print(f"four-chips {s} k={k}: F1 {f1:.6f}, {n_diff} predictions "
                  f"differ from one chip, tiles_scanned "
                  f"{r4.funnel.tiles_scanned}, {t4:.3f} s (first call "
                  f"compiles)", flush=True)
    summary["f1_sharded"] = sum(f1s) / len(f1s)
    summary["predictions_differing"] = differ
    print(f"four-chips: mean F1 {summary['f1_sharded']:.6f}; "
          f"{differ} predictions differ from one chip", flush=True)
    check_floor("four-chip mean F1", summary["f1_sharded"], F1_FLOOR)
    return summary


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the user-sharded path on 4 devices")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    _import_repo()
    from repro import compile_cache
    print(f"compile cache: {compile_cache.enable()}", flush=True)
    device = device_check("tpu")
    if args.four_chips:
        run_four_chips(args.seed, **NETFLIX)
    else:
        run_one_chip(args.seed, **NETFLIX)
    print(f"total: {time.perf_counter() - t_start:.3f} s", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
