"""Plain reference for the benchmark's `correct`: exact top-k in NumPy.

Imports nothing of the program and takes nothing it made but its answers.
The comparison holds each answer to the guarantee the configuration states,
(c, p0, k):

- ``invalid``: answers in the window whose ids are not k distinct rows of
  the corpus, whose scores are not finite, or whose scores rise from one
  rank to the next by more than ``ORDER_LIMIT`` of the row's top score
  (the API returns scores descending per row). Limit 0.
- ``order_gap``: the widest such rise over the window's answers, as a
  share of the row's top score. Float32 products ranked and rescored in
  two accumulation orders differ by a few float32 steps; a ranking made
  from bfloat16 products reads about 1e-3.
- ``score_err``: over the sampled answers, the widest gap between a
  returned score and the float64 inner product of its returned row, as a
  share of the query's largest exact score. The program rescores in float32
  (about 1e-7); a float32 config answered in bfloat16 reads about 1e-3.
- ``success``: share of sampled queries whose every rank meets the
  c-approximation against the exact top-k (the Theorem-2 event; a rank whose
  exact score is not positive is met). Limit: the binomial floor
  ``p0 - 3 sqrt(p0 (1 - p0) / n)`` of the stated probability p0.

``recall`` (mean recall@k over the sample) is reported beside them as an
end-to-end metric and is not a check.
"""
from __future__ import annotations

import math

import numpy as np

# Limit of score_err, set from chip readings (PERF.md section 2): the
# program's float32 rescore read at most 2.9e-7 over its seeds, the
# bfloat16 control at least 8.5e-4; the limit sits 100x above the one and
# 28x below the other.
SCORE_ERR_LIMIT = 3e-5
# Limit of order_gap, set from chip readings (PERF.md section 2): the
# program with float32 products read 0 on every run, with the default
# precision's bfloat16 passes at least 3.2e-4; the limit is some 80 float32
# steps of the top score above the one and 32x below the other.
ORDER_LIMIT = 1e-5
BLOCK = 64          # queries per exact-score block: (BLOCK, n) f32 at a time


def exact_topk(x: np.ndarray, q: np.ndarray, k: int):
    """(ids (B, k) int64, scores (B, k) float64), descending, ties to the
    lower row. Scores are float64 inner products of the float32 rows; the
    candidates are taken from a float32 scan, widened by k rows so that a
    float32 rounding cannot drop a row of the float64 top-k."""
    ids = np.empty((len(q), k), np.int64)
    scores = np.empty((len(q), k), np.float64)
    wide = min(2 * k, x.shape[0])
    for lo in range(0, len(q), BLOCK):
        qb = q[lo:lo + BLOCK]
        s32 = qb @ x.T                                       # (b, n) f32
        cand = np.argpartition(-s32, wide - 1, axis=1)[:, :wide]
        s64 = np.einsum("bkd,bd->bk", x[cand].astype(np.float64),
                        qb.astype(np.float64))
        order = np.lexsort((cand, -s64), axis=1)[:, :k]
        ids[lo:lo + len(qb)] = np.take_along_axis(cand, order, axis=1)
        scores[lo:lo + len(qb)] = np.take_along_axis(s64, order, axis=1)
    return ids, scores


def inversions(scores: np.ndarray) -> np.ndarray:
    """(B,) widest rise from a returned score to the next one in its row,
    as a share of the row's largest |score|; 0 for a descending row, NaN
    for a row with a score that is not finite."""
    s = np.asarray(scores, np.float64)
    with np.errstate(invalid="ignore", divide="ignore"):
        rise = np.maximum(np.diff(s, axis=1).max(axis=1, initial=0.0), 0.0)
        out = rise / np.abs(s).max(axis=1)
    return np.where(np.isfinite(s).all(axis=1), out, np.nan)


def invalid_rows(ids: np.ndarray, scores: np.ndarray, n: int) -> np.ndarray:
    """(B,) bool: the row's answer is not k distinct corpus rows with
    finite scores in descending order."""
    ids = np.asarray(ids)
    bad = ((ids < 0) | (ids >= n)).any(axis=1)
    inv = inversions(scores)
    bad |= ~(inv <= ORDER_LIMIT)                 # NaN: a score not finite
    srt = np.sort(ids, axis=1)
    bad |= (np.diff(srt, axis=1) == 0).any(axis=1)
    return bad


def success_floor(p0: float, n: int) -> float:
    return p0 - 3.0 * math.sqrt(p0 * (1.0 - p0) / n)


def compare(x: np.ndarray, q: np.ndarray, all_ids: np.ndarray,
            all_scores: np.ndarray, pick: np.ndarray,
            guarantee: dict) -> dict:
    """Checks and recall. ``all_ids`` / ``all_scores`` are every answer of
    the window, for ``invalid`` and ``order_gap``; rows ``pick`` of them
    answer the sampled queries ``q``, for the rest. Returns {"checks":
    {name: {value, limit, ok}}, "recall"}."""
    c, p0, k = guarantee["c"], guarantee["p0"], guarantee["k"]
    n = x.shape[0]
    invalid = int(invalid_rows(all_ids, all_scores, n).sum())
    inv = inversions(all_scores)
    order_gap = float(np.nanmax(inv, initial=0.0))
    ids, scores = all_ids[pick], all_scores[pick]
    eids, escores = exact_topk(x, q, k)
    rows = np.clip(ids, 0, n - 1)
    ref = np.einsum("bkd,bd->bk", x[rows].astype(np.float64),
                    q.astype(np.float64))
    scale = np.maximum(np.abs(escores).max(axis=1), np.finfo(np.float32).tiny)
    gap = np.abs(np.asarray(scores, np.float64) - ref) / scale[:, None]
    gap = np.where(np.isfinite(gap), gap, np.inf)
    score_err = float(gap.max())
    s = np.asarray(scores, np.float64)
    met = (s >= c * escores) | (escores <= 0.0)
    success = float(met.all(axis=1).mean())
    recall = float(np.mean([len(set(ids[i].tolist()) & set(eids[i].tolist()))
                            / k for i in range(len(q))]))
    floor = success_floor(p0, len(q))
    checks = {
        "invalid": {"value": invalid, "limit": 0, "ok": invalid <= 0},
        "order_gap": {"value": order_gap, "limit": ORDER_LIMIT,
                      "ok": order_gap <= ORDER_LIMIT},
        "score_err": {"value": score_err, "limit": SCORE_ERR_LIMIT,
                      "ok": score_err <= SCORE_ERR_LIMIT},
        "success": {"value": success, "limit": floor, "ok": success >= floor},
    }
    return {"checks": checks, "recall": recall}
