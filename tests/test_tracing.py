"""Spans, counters and scopes along the serving path (engine/runtime.py,
engine/serving.py, engine/sharding.py).

Pins what a profile of the serving path can rely on: (1) every ``rk.*``
host span appears in a ``jax.profiler`` trace, the ``rk.flush.*``
sub-spans nested in their ``rk.flush`` on the same thread, and the
``seq0``/``n`` arguments of one batch agree across the threads that
form, flush and resolve it; (2) ``RuntimeStats.queue_wait_s`` is the sum
of the tickets' ``formed_at - submitted_at``, and ``linger_s`` counts
only the time a partial batch was held; (3) every rung's compiled scan
carries the four forward ``kmips.*`` scopes, and ``op_scopes`` leaves an
instruction that two executables place differently unattributed.

Threading discipline as in tests/test_runtime.py: every blocking wait
carries a timeout.
"""

import collections
import glob
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.core import sah
from repro.data import synthetic
from repro.engine import (IndexArtifact, RetrievalServer, RkMIPSEngine,
                          ServingGateway, ServingRuntime, get_config,
                          serving)

D = 16
FLUSH_PARTS = ("rk.flush.pad", "rk.flush.launch", "rk.flush.merge",
               "rk.flush.split")
SPANS = ("rk.submit", "rk.form", "rk.flush", "rk.resolve") + FLUSH_PARTS
FORWARD_SCOPES = {"kmips.hash", "kmips.scan", "kmips.select",
                  "kmips.rerank"}


def _cfg(**kw):
    return get_config("sah").replace(tile=32, n_bits=32, k_max=8, n_top=8,
                                     leaf_size=8, n_cand=16, scan="sketch",
                                     delta_capacity=8, serve_batch_size=4,
                                     **kw)


@pytest.fixture(scope="module")
def workload():
    ki, kq = jax.random.split(jax.random.PRNGKey(41))
    items, users = synthetic.recommendation_data(ki, 120, 64, D)
    queries = synthetic.queries_from_items(kq, items, 12)
    return items, users, queries


@pytest.fixture(scope="module")
def artifact(workload):
    items, _, _ = workload
    return IndexArtifact.build(items, None, jax.random.PRNGKey(5),
                               config=_cfg(serve_buckets=(1, 2)))


def _host_events(trace_dir):
    """{name: [(thread line, start, end, {arg: value})]} of the host
    plane of the newest trace under ``trace_dir``."""
    path = max(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    out = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line_no, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("rk."):
                    out[ev.name].append((line_no, ev.start_ns, ev.end_ns,
                                         dict(ev.stats)))
    return out


# -- (1) host spans ------------------------------------------------------


def test_profile_holds_every_span_nested_with_batch_ids(tmp_path, workload,
                                                        artifact):
    items, _, queries = workload
    with ServingGateway(pool_workers=1) as gw:
        gw.register("t", artifact, k=3, mode="forward")
        # staged rows, so the flush also merges the delta buffer
        gw.insert_items("t", items[:2] * 1.5)
        gw.warmup(ks=(3,))
        jax.profiler.start_trace(str(tmp_path))
        try:
            tickets = [gw.submit("t", queries[i])
                       for i in range(queries.shape[0])]
            for t in tickets:
                t.result(timeout=60)
            assert gw.drain(timeout=60)
            time.sleep(0.2)      # the last rk.resolve closes after drain
        finally:
            jax.profiler.stop_trace()
    ev = _host_events(tmp_path)
    assert set(SPANS) <= set(ev), sorted(ev)

    # one rk.submit per ticket, carrying its admission seq
    assert sorted(a["seq0"] for *_, a in ev["rk.submit"]) == \
        sorted(t.seq for t in tickets)
    assert all(a["n"] == 1 for *_, a in ev["rk.submit"])

    # form, flush and resolve of one batch share (seq0, n), across threads
    def batches(name):
        return sorted((a["seq0"], a["n"]) for *_, a in ev[name] if "n" in a)
    formed = batches("rk.form")
    assert formed == batches("rk.flush") == batches("rk.resolve")
    covered = sorted(s for s0, n in formed for s in range(s0, s0 + n))
    assert covered == sorted(t.seq for t in tickets)
    assert all(a["pad_to"] >= a["n"] for *_, a in ev["rk.flush"])

    # each flush holds exactly one of each part, on its own thread
    for line, start, end, _ in ev["rk.flush"]:
        for part in FLUSH_PARTS:
            inside = [e for e in ev[part] if e[0] == line
                      and start <= e[1] and e[2] <= end]
            assert len(inside) == 1, (part, start)
    for part in FLUSH_PARTS:
        assert len(ev[part]) == len(ev["rk.flush"])


def test_reverse_flush_opens_its_parts(tmp_path, workload):
    items, users, queries = workload
    art = IndexArtifact.build(items, users, jax.random.PRNGKey(6),
                              config=_cfg())
    with RkMIPSEngine.from_artifact(art).async_reverse_server(k=3) as rt:
        jax.profiler.start_trace(str(tmp_path))
        try:
            for t in [rt.submit(queries[i]) for i in range(4)]:
                t.result(timeout=120)
            time.sleep(0.2)
        finally:
            jax.profiler.stop_trace()
    ev = _host_events(tmp_path)
    for name in ("rk.flush", "rk.flush.pad", "rk.flush.launch",
                 "rk.flush.split"):
        assert ev[name], name
    assert "rk.flush.merge" not in ev


# -- (2) counters of the waits that cross threads ------------------------


def test_queue_wait_is_the_sum_of_ticket_waits(workload, artifact):
    _, _, queries = workload
    with ServingGateway(pool_workers=1) as gw:
        gw.register("t", artifact, k=3, mode="forward")
        tickets = [gw.submit("t", queries[i])
                   for i in range(queries.shape[0])]
        for t in tickets:
            t.result(timeout=60)
        assert gw.drain(timeout=60)
        st = gw.stats().tenants["t"]
    assert all(t.submitted_at <= t.formed_at <= t.done_at for t in tickets)
    waits = sum(t.formed_at - t.submitted_at for t in tickets)
    assert st.queue_wait_s == pytest.approx(waits, rel=1e-9)
    assert 0 <= st.linger_s


@pytest.mark.parametrize("pooled", [False, True])
def test_linger_counts_only_a_held_partial_batch(workload, artifact,
                                                 pooled):
    _, _, queries = workload

    def runtime(linger):
        if pooled:
            gw = ServingGateway(pool_workers=1)
            return gw, gw.register("t", artifact, k=3, mode="forward",
                                   batch_linger=linger)
        return None, ServingRuntime(RetrievalServer.from_artifact(artifact),
                                    k=3, batch_linger=linger)

    def partial(linger):
        # three tickets at once: under the batch of 4 and off the (1, 2,
        # 4) ladder, so a positive linger holds them
        gw, rt = runtime(linger)
        try:
            tickets = rt.submit(queries[:3])
            for t in tickets:
                t.result(timeout=60)
            assert rt.drain(timeout=60)
            return tickets, rt.stats
        finally:
            (gw or rt).close()

    _, st = partial(0.0)
    assert st.linger_s == 0.0 and st.batches == 1
    tickets, st = partial(0.05)
    assert st.batches == 1
    waits = [t.formed_at - t.submitted_at for t in tickets]
    assert 0 < st.linger_s <= min(waits)
    assert st.queue_wait_s == pytest.approx(sum(waits), rel=1e-9)

    # a full batch admitted at once dispatches without a linger
    gw, rt = runtime(0.05)
    try:
        for t in rt.submit(queries[:4]):
            t.result(timeout=60)
        assert rt.drain(timeout=60)
        assert rt.stats.linger_s == 0.0 and rt.stats.batches == 1
    finally:
        (gw or rt).close()


# -- (3) device scopes ---------------------------------------------------


def test_every_rung_carries_the_forward_scopes(monkeypatch, artifact,
                                               fresh_compiles):
    monkeypatch.setattr(serving, "_SCOPES_SEEN", {})
    srv = RetrievalServer.from_artifact(artifact.insert_items(
        jnp.ones((1, D))))
    assert srv.warmup((3,)) == 2 * len(srv.config.bucket_ladder())
    state = srv.cache.get(srv.config)
    per_rung = []
    for b in srv.config.bucket_ladder():
        text = srv._dispatch.lower(
            state.items, state.item_ids, state.item_mask, state.codes,
            state.proj_q, jnp.zeros((b, D)), k=3, n_cand=16,
            scan="sketch").compile().as_text()
        module, scopes = serving.instruction_scopes(text)
        assert module == "jit__scan"
        assert FORWARD_SCOPES <= set(scopes.values()), b
        per_rung.append(scopes)
    seen = serving.op_scopes()
    assert FORWARD_SCOPES <= {s for (m, _), s in seen.items()
                              if m == "jit__scan"}
    assert "kmips.merge" in {s for (m, _), s in seen.items()
                             if m == "jit__merge"}
    # an instruction the rungs place differently is left unattributed
    for name in set().union(*per_rung):
        placed = {r.get(name, "") for r in per_rung if name in r}
        if len(placed) > 1:
            assert seen[("jit__scan", name)] is None


def test_exact_scan_is_one_rerank_scope(artifact, fresh_compiles):
    srv = RetrievalServer.from_artifact(artifact)
    state = srv.cache.get(srv.config)
    text = srv._dispatch.lower(
        state.items, state.item_ids, state.item_mask, state.codes,
        state.proj_q, jnp.zeros((4, D)), k=3, n_cand=16,
        scan="exact").compile().as_text()
    scopes = set(serving.instruction_scopes(text)[1].values()) - {""}
    # the exact scan ranks every row, and needs no query codes
    assert scopes == {"kmips.rerank"}


def test_scope_map_flags_ambiguity(monkeypatch):
    text = "\n".join([
        "HloModule jit__scan, entry_computation_layout={()->f32[]}",
        "",
        "ENTRY %main {",
        '  %sort.1 = s32[8]{0} sort(%a), metadata={op_name="jit(_scan)/'
        'while/body/kmips.select/top_k"}',
        '  %fusion.2 = f32[8]{0} fusion(%b), metadata={op_name="jit(_scan)'
        '/kmips.hash/kmips.scan/xor" source_file="x.py"}',
        "  ROOT %copy.3 = f32[8]{0} copy(%c)",
        "}"])
    module, scopes = serving.instruction_scopes(text)
    assert module == "jit__scan"
    assert scopes == {"sort.1": "kmips.select", "fusion.2": "kmips.scan",
                      "copy.3": ""}
    monkeypatch.setattr(serving, "_SCOPES_SEEN", {
        ("m", "a"): {"kmips.scan"},
        ("m", "b"): {"kmips.scan", "kmips.select"},
        ("m", "c"): {""},
        ("m", "d"): {"", "kmips.rerank"}})
    assert serving.op_scopes() == {("m", "a"): "kmips.scan",
                                   ("m", "b"): None, ("m", "d"): None}
    # an executable whose text carries no scope at all (a cache entry
    # compiled before the scopes) adds nothing, not ambiguity
    serving._record_scopes(SimpleNamespace(as_text=lambda: text.replace(
        "kmips.", "other.")))
    assert serving.op_scopes()[("m", "a")] == "kmips.scan"
    assert ("jit__scan", "sort.1") not in serving.op_scopes()


def test_reverse_phases_are_scoped(workload):
    items, users, queries = workload
    eng = RkMIPSEngine(_cfg()).build(items, users, jax.random.PRNGKey(7))
    text = sah.rkmips_batch.lower(eng.index, queries[:2], k=3,
                                  n_cand=16).as_text(debug_info=True)
    assert "sah.plan" in text and "sah.execute" in text
