"""The numbers that decide ``correct``, each beside its limit.

A cell's ``limits/<cell>.json`` names the numbers it compares, each held
at most to its ``limit``, with the readings the limit was set from: the
program's (``lower``) and the control's (``upper``), the reference one
precision step below the configuration's. The configuration's stated
floor and ``missing`` (tickets failed or never answered, limit 0) are
held in every cell.

Reverse numbers, over every answered ticket:
  missed_margin  the widest margin by which a user the float32 reference
                 puts in the audience was left out (0 with no miss).
  miss_share     misses over the reference's audience, pooled
                 (FN / (TP + FN)).
  f1             pooled F1 of all audiences (2TP / (2TP + FP + FN)),
                 held to the floor the configuration states.
Forward numbers:
  score_err      the largest relative error of a returned score against
                 the float32 score of the returned item.
  recall         mean recall@k of the returned ids, held to the floor the
                 configuration states.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from . import reference

CHUNK = 64
# the numbers of a run that answered nothing: every check fails
NOTHING = {"reverse": {"missed_margin": 1e30, "miss_share": 1.0,
                       "f1": 0.0},
           "forward": {"score_err": 1e30, "recall": 0.0}}


def check(name: str, value: float, limit: float, op: str) -> dict:
    ok = value <= limit if op == "<=" else value >= limit
    return {"name": name, "value": float(value), "limit": float(limit),
            "op": op, "ok": bool(ok)}


def _padded(rows) -> jnp.ndarray:
    """``rows`` padded with copies of its first row to ``CHUNK`` rows, so
    every chunk runs one compiled program."""
    rows = np.asarray(rows)
    pad = CHUNK - rows.shape[0]
    return jnp.asarray(np.concatenate([rows, np.repeat(rows[:1], pad, 0)])
                       if pad else rows)


def reverse_numbers(items, users, k: int, tie_eps: float, qs_host,
                    bits: list) -> dict:
    """``qs_host`` (n, d) the tickets' queries, ``bits`` their answers as
    packed bits (``reference.pack``) -> counts and the two numbers."""
    m = users.shape[0]
    s_k = reference.kth_scores(items, users, k)
    tp = fp = fn = 0
    widest = 0.0
    for lo in range(0, len(bits), CHUNK):
        hi = min(lo + CHUNK, len(bits))
        preds = np.zeros((CHUNK, m), bool)   # one program for every chunk
        preds[:hi - lo] = [reference.unpack(b, m) for b in bits[lo:hi]]
        qs = _padded(qs_host[lo:hi])
        t, f, n, w = reference.reverse_tally(
            users, s_k, qs, tie_eps, jnp.asarray(preds),
            jnp.arange(CHUNK) < hi - lo)
        tp, fp, fn = tp + int(t), fp + int(f), fn + int(n)
        widest = max(widest, float(w))
    denom = 2 * tp + fp + fn
    return {"tp": tp, "fp": fp, "fn": fn, "missed_margin": widest,
            "miss_share": fn / max(tp + fn, 1),
            "f1": 1.0 if denom == 0 else 2 * tp / denom}


def forward_numbers(items, k: int, qs_host, ids, vals) -> dict:
    """``ids``/``vals`` (n, k) returned per ticket -> the two numbers."""
    recalls, err = [], 0.0
    for lo in range(0, len(ids), CHUNK):
        n = min(CHUNK, len(ids) - lo)
        qs = _padded(qs_host[lo:lo + n])
        got_ids = _padded(ids[lo:lo + n])
        got = np.asarray(vals[lo:lo + n], np.float32)
        _, true_ids = reference.forward_topk(items, qs, k=k)
        hit = np.any(np.asarray(got_ids)[:n, :, None]
                     == np.asarray(true_ids)[:n, None, :], axis=-1)
        recalls.append(hit.mean(axis=-1))
        exact = np.asarray(reference.exact_scores(items, qs, got_ids))[:n]
        rel = np.abs(got - exact) / np.maximum(np.abs(exact), 1e-30)
        err = max(err, float(rel.max()))
    return {"recall": float(np.mean(np.concatenate(recalls))),
            "score_err": err}


def checks(direction: str, numbers: dict, limits: dict, floors: dict,
           missing: int) -> list:
    """``missing``, then each number the cell's limits file names (at most
    its limit), then the configuration's stated floor."""
    out = [check("missing", missing, 0, "<=")]
    out += [check(name, numbers[name], lim["limit"], "<=")
            for name, lim in limits.items()]
    if direction == "reverse":
        out.append(check("f1", numbers["f1"], floors["reverse_f1"], ">="))
    else:
        out.append(check("recall", numbers["recall"],
                         floors["forward_recall"], ">="))
    return out
