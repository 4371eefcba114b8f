"""Async serving: the threaded ticket pipeline with background compaction.

    PYTHONPATH=src python examples/serve_async.py

The walkthrough of DESIGN.md SS12, submit -> future -> compact-in-flight:

1. build an ``IndexArtifact`` and stand up a ``ServingRuntime`` over the
   forward retrieval server (``engine.async_server``): ``submit`` returns
   a future (``ServeTicket``) immediately, worker threads micro-batch the
   queue through the server's own flush path — answers are bitwise the
   synchronous ``flush`` on the same stream, and compile counts stay at
   one trace per batch shape;
2. stream mutations while traffic flows: ``insert_items`` /
   ``delete_items`` stage deltas and hot-swap the new version between
   flushes — pending tickets survive every swap;
3. the delta buffer fills past ``compact_fill``: the maintenance thread
   rebuilds the next base OFF-THREAD (tickets keep resolving while it
   runs), re-stages whatever churn raced the build
   (``reconcile_compaction``), swaps the merged version live, and
   persists it under the ``keep=`` GC policy;
4. deadlines: a ticket that waits past its budget fails with
   ``TicketExpired`` before dispatch instead of wedging the queue;
5. ``close()`` drains — every future resolves, then ``submit`` refuses.
"""

import argparse
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import IndexArtifact, RkMIPSEngine, compile_cache, get_config
from repro.engine import RetrievalServer, TicketExpired
from repro.data import synthetic


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-items", type=int, default=4096)
    ap.add_argument("--m-users", type=int, default=512)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--queries", type=int, default=64)
    args = ap.parse_args()
    compile_cache.enable()

    key = jax.random.PRNGKey(0)
    ki, kq, kb, kn = jax.random.split(key, 4)
    items, users = synthetic.recommendation_data(
        ki, args.n_items, args.m_users, args.dim)
    queries = synthetic.queries_from_items(kq, items, args.queries)

    cfg = get_config("sah").replace(delta_capacity=64, serve_batch_size=8)
    art = IndexArtifact.build(items, users, kb, config=cfg)
    eng = RkMIPSEngine.from_artifact(art)
    print(f"built v1: {art.n_base} items, fingerprint "
          f"{art.fingerprint[:16]}...")

    with tempfile.TemporaryDirectory() as versions:
        with eng.async_server(k=args.k, compaction=True, compact_fill=0.5,
                              poll_interval=0.01, artifact_dir=versions,
                              keep=3) as rt:
            # -- 1. tickets are futures; answers == synchronous flush -----
            tickets = rt.submit(queries)         # returns immediately
            answers = [t.result(timeout=60) for t in tickets]
            lat = sorted(t.latency for t in tickets)
            sync = RetrievalServer.from_artifact(art)
            sync.submit(queries)
            ref = sync.flush(args.k)
            assert all(np.array_equal(np.asarray(a.ids), np.asarray(r.ids))
                       for a, r in zip(answers, ref))
            print(f"{len(tickets)} tickets answered async, bitwise == "
                  f"sync flush (p50 latency {lat[len(lat) // 2] * 1e3:.1f}"
                  f" ms, compiles={rt.server.compile_count})")

            # -- 2. mutations hot-swap between flushes ---------------------
            pick = jax.random.randint(kn, (2, 40), 0, args.n_items)
            trending = 0.65 * (items[pick[0]] + items[pick[1]])
            inflight = rt.submit(queries[:16])   # tickets before the swaps
            rt.insert_items(trending)            # 40/64 slots: past the fill
            rt.delete_items([0, 7])
            for t in inflight:                   # ...survive them
                t.result(timeout=60)

            # -- 3. compaction lands in the background ---------------------
            deadline = time.monotonic() + 120
            while rt.stats.compactions < 1:
                rt.submit(queries[0]).result(timeout=60)  # traffic flows
                if time.monotonic() > deadline:
                    raise SystemExit("compaction never landed")
                time.sleep(0.02)
            merged = rt.artifact
            print(f"compacted off-thread in "
                  f"{rt.last_compaction_seconds:.2f}s: new base "
                  f"{merged.n_base} rows, churn re-staged = "
                  f"{merged.delta_used} (tickets kept resolving)")
            back = IndexArtifact.load(versions)
            assert back.fingerprint == merged.fingerprint
            print(f"merged version persisted + verified under keep=3 GC "
                  f"({back.fingerprint[:16]}...)")

            # -- 4. deadlines fail fast, pre-dispatch ----------------------
            doomed = rt.submit(queries[1], deadline=0.0)
            try:
                doomed.result(timeout=30)
            except TicketExpired as e:
                print(f"deadline honored: {e}")

            st = rt.stats
            print(f"stats: {st.completed} completed / {st.expired} expired "
                  f"over {st.batches} batches, {st.swaps} swaps, "
                  f"{st.compactions} compaction")
        # -- 5. the context manager drained and closed the runtime --------
        try:
            rt.submit(queries[0])
        except RuntimeError as e:
            print(f"closed: {e}")


if __name__ == "__main__":
    main()
