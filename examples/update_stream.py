"""Streaming corpus updates through the index-artifact lifecycle.

    PYTHONPATH=src python examples/update_stream.py

The walkthrough of DESIGN.md SS10, insert -> serve -> compact:

1. build an ``IndexArtifact`` over a synthetic catalogue and stand up a
   live ``ReverseServer`` ("which users would see this item in their
   top-k?") from it;
2. a batch of trending items lands: ``insert_items`` stages them in the
   fixed-capacity delta buffer and ``swap`` makes the new version live
   between flushes — pending tickets survive, answers reflect the new
   rows immediately, and the engine pays at most ONE extra compile ever
   (the buffer's capacity is a static shape);
3. retire a few items with ``delete_items`` — the swap reuses every
   compiled executable (delete-only churn rides the plain pipeline);
4. ``compact()`` folds the stream into fresh norm-ordered partitions: the
   compacted artifact answers bitwise like a cold build on the mutated
   catalogue, and ``save``/``load`` round-trips it for the next process
   (on any mesh — attach does the placement).
"""

import argparse
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro import IndexArtifact, RkMIPSEngine, compile_cache, get_config
from repro.data import synthetic


def audience(result) -> int:
    return int(np.asarray(result.predictions).sum())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-items", type=int, default=4096)
    ap.add_argument("--m-users", type=int, default=8192)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--inserts", type=int, default=24)
    args = ap.parse_args()
    compile_cache.enable()

    key = jax.random.PRNGKey(0)
    ki, kq, kb, kn = jax.random.split(key, 4)
    items, users = synthetic.recommendation_data(
        ki, args.n_items, args.m_users, args.dim)
    promoted = synthetic.queries_from_items(kq, items, 4)

    cfg = get_config("sah").replace(delta_capacity=max(64, args.inserts),
                                    serve_batch_size=4)
    art = IndexArtifact.build(items, users, kb, config=cfg)
    eng = RkMIPSEngine.from_artifact(art)
    server = eng.reverse_server()
    print(f"built v1: {art.n_base} items x {art.n_users} users, "
          f"fingerprint {art.fingerprint[:16]}...")

    # -- serve against the base version -----------------------------------
    server.submit(promoted)
    base = server.flush(args.k)
    print(f"v1: audiences {[audience(r) for r in base]} "
          f"(compiles={server.compile_count})")

    # -- trending items arrive: stage + hot swap --------------------------
    # make them compete: in-distribution blends of catalogue rows, boosted
    pick = jax.random.randint(kn, (2, args.inserts), 0, args.n_items)
    trending = 0.65 * (items[pick[0]] + items[pick[1]])
    art_v2 = art.insert_items(trending)
    server.submit(promoted)                      # tickets before the swap
    server.swap(art_v2)                          # ...survive it
    v2 = server.flush(args.k)
    print(f"v2 (+{args.inserts} staged rows): audiences "
          f"{[audience(r) for r in v2]} (compiles={server.compile_count}, "
          f"delta buffer {int(np.asarray(art_v2.delta_mask).sum())}"
          f"/{art_v2.delta_capacity})")
    shrink = sum(audience(a) < audience(b) for a, b in zip(v2, base))
    print(f"    {shrink}/4 promoted items lost audience to the staged "
          f"rows — inserts are live before any rebuild")

    # -- retire the weakest catalogue rows: delete-only churn is free -----
    norms = np.asarray(jnp.linalg.norm(items, axis=-1))
    retired = np.argsort(norms)[:8].tolist()
    art_v3 = art_v2.delete_items(retired)
    server.swap(art_v3)
    server.submit(promoted[0])
    one = server.flush(args.k)[0]
    print(f"v3 (-{len(retired)} retired): audience {audience(one)} "
          f"(compiles={server.compile_count})")

    # -- compact: fold the stream into fresh partitions -------------------
    art_v4 = art_v3.compact()
    server.swap(art_v4)
    ref = RkMIPSEngine(cfg).build(art_v3.effective_items(), users, kb)
    check = RkMIPSEngine.from_artifact(art_v4).query_batch(promoted, args.k)
    truth = ref.query_batch(promoted, args.k)
    assert np.array_equal(np.asarray(check.predictions),
                          np.asarray(truth.predictions))
    print(f"v4 compacted: {art_v4.n_base} rows, bitwise equal to a cold "
          f"build on the mutated catalogue")

    # -- ship it ----------------------------------------------------------
    with tempfile.TemporaryDirectory() as d:
        art_v4.save(d)
        back = IndexArtifact.load(d)
        assert back.fingerprint == art_v4.fingerprint
        print(f"saved + loaded, fingerprint {back.fingerprint[:16]}... "
              f"verified — attach it to any engine, on any mesh")


if __name__ == "__main__":
    main()
