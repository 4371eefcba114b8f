"""The plain reference against the program's exact oracle, the pinned
corpus, and the control: the reference one precision step below the
configuration's must come out as not correct (the program's sound runs
come out correct in test_rkbench_harness.py)."""

import hashlib
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[2] / "src"))

from rkbench import corpus, reference, spec  # noqa: E402

TIE_EPS = 1e-5


def _corpus(seed, n=300, m=700, d=24):
    return corpus.make(corpus.root_key(seed), n_items=n, m_users=m, d=d)


@pytest.mark.parametrize("k", [1, 10, 50])
def test_reverse_reference_matches_program_oracle(k):
    from repro.core import exact
    items, users = _corpus(4)
    qs = items[jnp.argsort(-jnp.linalg.norm(items, axis=-1))[:12]]
    s_k = reference.kth_scores(items, users, k, block=256)
    ours = reference.reverse_answers(users, s_k, qs, TIE_EPS)
    theirs = exact.rkmips_batch(items, users, qs, k, tie_eps=TIE_EPS)
    assert np.array_equal(np.asarray(ours), np.asarray(theirs))
    assert 0 < int(np.asarray(ours).sum()) < ours.size


def test_forward_reference_matches_program_oracle():
    from repro.core import exact
    items, users = _corpus(5)
    qs = users[:9]
    vals, ids = reference.forward_topk(items, qs, k=20)
    tv, ti = exact.kmips(items, qs, 20)
    assert np.array_equal(np.asarray(ids), np.asarray(ti))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(tv), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(reference.exact_scores(items, qs, ids)), np.asarray(tv),
        rtol=1e-6)


def test_corpus_checksum_pinned():
    items, users = _corpus(2**32 + 17, n=16, m=24, d=8)
    digest = hashlib.sha256()
    for a in (items, users):
        digest.update(np.round(np.asarray(a, np.float64), 5).tobytes())
    want = json.loads((HERE / "data" / "corpus_checksum.json").read_text())
    assert digest.hexdigest() == want["sha256"]


def test_seed_changes_corpus():
    a, _ = _corpus(1, n=8, m=8, d=4)
    b, _ = _corpus(2**31 + 1, n=8, m=8, d=4)
    assert not np.array_equal(np.asarray(a), np.asarray(b))


CELLS = [w["name"] for w in spec.benchmark()["workloads"]] + [
    w["name"] for w in json.loads(
        (HERE / "data" / "parked_cells.json").read_text())["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_is_not_correct(cell, seed):
    """The reference one precision step below the configuration's, in the
    program's place at the rehearsal size and over 100 tickets of the
    cell's mix, fails the cell's check."""
    from rkbench import harness
    seconds = 100 / spec.cell(cell)["traffic"]["rate"]   # 100 tickets
    line = harness.run(cell, seed, seconds, False, t_start=0.0,
                       rehearse=True, control=True)
    assert not line["correct"], line["checks"]
    failed = [c for c, v in line["checks"].items()
              if (v["value"] > v["limit"] if v["op"] == "<="
                  else v["value"] < v["limit"])]
    limited = json.loads((spec.BENCH_DIR / "limits" /
                          f"{cell}.json").read_text())
    assert set(failed) & set(limited), line["checks"]
