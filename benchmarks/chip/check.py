"""The comparison that decides `correct`.

Each sampled answer (scores (k,), ids (k,)) is judged against the plain
reference's scores of every page for its query:

  * the served ids are the reference's candidates: every page whose
    first-stage score lies above the n_cand-th best by more than the
    tolerance is served, and every served page lies within the tolerance
    of that cut or above it (a tie at the cut may go either way);
  * each served score is the reference's score of that page, and the
    served list is in non-increasing score order;
  * the program's codebook quantises the seeded pages about as well as
    a plain k-means with the configuration's K and restarts (the scores
    above are computed with the program's codebook, so this is its own
    check).

The numbers compared, each with its limit:

  wrong_ids        ids missing, extra, repeated or out of order     0
  score_gap        widest |served - reference| / |reference|        the
                                                                    reference's
  codebook_excess  distortion of the program's codebook over the    the
                   plain k-means's, less 1, on unseen patches       reference's
  unanswered       requests due in the window never answered        0

The score tolerance is also the width of a tie at the candidate cut.
"""
from __future__ import annotations

import numpy as np


def judge(scores, ids, cand, final, n_cand: int, *, rtol: float):
    """(wrong ids, widest relative score gap) of one answer. cand/final:
    (N,) reference scores of every page; rtol 0 compares exactly."""
    scores, ids = np.asarray(scores), np.asarray(ids)
    cand = np.asarray(cand, np.float64)
    final = np.asarray(final, np.float64)
    n, k = cand.shape[0], ids.shape[0]
    if k != n_cand:
        raise ValueError(f"{k} answers for {n_cand} candidates: the check "
                         "covers only top_k == candidates")
    valid = (ids >= 0) & (ids < n)
    wrong = int(np.sum(~valid)) + (k - len(set(ids[valid].tolist())))
    cut = np.partition(cand, n - n_cand)[n - n_cand]
    tol = rtol * abs(cut)
    must = np.flatnonzero(cand > cut + tol)
    allowed = cand >= cut - tol
    served = ids[valid]
    wrong += int(np.sum(~allowed[served]))
    wrong += len(set(must.tolist()) - set(served.tolist()))
    s = np.asarray(scores, np.float64)
    ref = np.full(k, np.nan)
    ref[valid] = final[ids[valid]]
    gap = np.abs(s - ref) / np.maximum(np.abs(ref), 1e-30)
    gap = float(np.max(np.where(valid, gap, np.inf)))
    # order: a later rank may exceed an earlier one only within tolerance
    wrong += int(np.sum(np.diff(s) > rtol * np.abs(s[1:])))
    return wrong, gap


def compare(answers, ref: dict, rows, *, limits: dict, unanswered: int,
            codebook_excess: float):
    """Judge each answer (scores, ids) against row `rows[i]` of the
    reference arrays; returns {name: {"value", "limit"}} and whether all
    are within their limits."""
    rtol = limits["score_gap"]
    wrong, gap = 0, 0.0
    for (s, i), row in zip(answers, rows):
        w, g = judge(s, i, ref["candidates"][row], ref["final"][row],
                     ref["n_cand"], rtol=rtol)
        wrong, gap = wrong + w, max(gap, g)
    numbers = {
        "wrong_ids": {"value": wrong, "limit": 0},
        "score_gap": {"value": gap, "limit": rtol},
        "codebook_excess": {"value": codebook_excess,
                            "limit": limits["codebook_excess"]},
        "unanswered": {"value": unanswered, "limit": 0},
    }
    ok = bool(answers) and all(v["value"] <= v["limit"]
                               for v in numbers.values())
    return numbers, ok


def reference_answers(ref: dict, k: int):
    """The reference put in the program's place: per query row, the
    top n_cand pages by first-stage score (ties to the lower id), then
    ordered by final score. Returns [(scores (k,), ids (k,))]."""
    out = []
    for cand, final in zip(ref["candidates"], ref["final"]):
        top = np.argsort(-cand, kind="stable")[:ref["n_cand"]]
        order = top[np.argsort(-final[top], kind="stable")][:k]
        out.append((final[order], order))
    return out
