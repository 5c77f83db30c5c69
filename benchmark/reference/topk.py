"""Plain reference: exact cosine top-k in numpy, and how far a reply is
from it.  The corpus changes while it is queried, so a reply is judged
against what was surely live and surely gone when it was computed."""

from __future__ import annotations

import numpy as np


def judge_reply(ref_scores: np.ndarray, hits: list, surely_live: np.ndarray,
                surely_gone: np.ndarray) -> dict:
    """``ref_scores[i]``: the reference cosine of document ``i`` (an index
    into the reference matrix) with the query; ``hits``: the reply's
    ``(i, reported score)``.  Returns the widest score error, the margin by
    which a surely-live document outranks a returned one while missing from
    the reply (0 when the reply is the exact top-k), and how many surely
    deleted documents came back."""
    idx = np.asarray([h[0] for h in hits], np.int64)
    got = np.asarray([h[1] for h in hits], np.float64)
    score_err = float(np.max(np.abs(got - ref_scores[idx]))) if len(idx) \
        else 0.0
    kth = float(ref_scores[idx].min()) if len(idx) else -np.inf
    cand = surely_live.copy()
    cand[idx] = False
    best_missing = float(ref_scores[cand].max()) if cand.any() else -np.inf
    return {"score_err": score_err,
            "rank_violation": max(best_missing - kth, 0.0),
            "gone_returned": int(surely_gone[idx].sum())}
