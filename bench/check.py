"""The comparison that decides ``correct``.

Every compared answer is a ``POST /v1/ppr`` response body from the timed
window, checked against the plain reference (``reference.py``) of its query
vertex at the precision the cell states.

A fixed-point cell states bit-exact Qm.f arithmetic, so its answers are
compared exactly with ``FixedReference``: the reference's top-k vertices
(query vertex left out, equal scores by ascending id) and their raw scores
over 2^f.  A float32 cell is compared with the float64 ``Reference``: for
each of an answer's k positions j,

    e_j = max(|s_j - r(v_j)|, |s_j - r_(j)|)

where s_j and v_j are the served score and vertex, r(v) the reference score
of v and r_(j) the reference's j-th best score with the query vertex left
out.  The first term catches a wrong vertex or score, the second a wrong
ranking (sorting is 1-Lipschitz, so a sound answer keeps it within the
scores' own error).  The numbers compared, each against its limit in
``limits/<workload>.json`` (a cell's file names the ones it holds):

    unanswered   requests with no 200 answer by the end of the run: none,
                 a 5xx, or a 4xx such as admission's 429 (a shed request is
                 a request not served)
    malformed    answers of the wrong length or echo, with the query vertex,
                 a repeated or out-of-range vertex, or rising scores
    mismatched   (fixed point) answers not equal to the reference's
    err_max      (float32) the largest e_j
    err_mean     (float32) the mean e_j
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from bench import traffic
from bench.reference import FixedReference, Reference

NUMBERS = ("unanswered", "malformed", "mismatched", "err_max", "err_mean")


def _malformed(req, graph: str, precision_key: str, k: int,
               num_vertices: int) -> bool:
    p = req.payload
    recs = p.get("recommendations") if isinstance(p, dict) else None
    if not isinstance(recs, list) or len(recs) != k:
        return True
    if (p.get("graph"), p.get("vertex"), p.get("k"), p.get("precision")) != \
            (graph, req.vertex, k, precision_key):
        return True
    ids = [r.get("vertex") for r in recs]
    if any(not isinstance(v, int) or not 0 <= v < num_vertices for v in ids):
        return True
    if any(not isinstance(r.get("score"), (int, float)) for r in recs):
        return True
    scores = np.asarray([r["score"] for r in recs], np.float64)
    if req.vertex in ids or len(set(ids)) != k:
        return True
    return bool(not np.isfinite(scores).all() or (np.diff(scores) > 0).any())


def sample(answers: Sequence, seed: int, n: int) -> List:
    """At most ``n`` answers, drawn from the seed."""
    if len(answers) <= n:
        return list(answers)
    pick = traffic.rng(seed, traffic.CHECK_STREAM).choice(
        len(answers), n, replace=False)
    return [answers[i] for i in sorted(pick)]


def served(answers: Sequence) -> List[Tuple[int, np.ndarray, np.ndarray]]:
    """(query vertex, served ids, served scores) of each answer."""
    out = []
    for req in answers:
        recs = req.payload["recommendations"]
        out.append((req.vertex,
                    np.asarray([r["vertex"] for r in recs], np.int64),
                    np.asarray([r["score"] for r in recs], np.float64)))
    return out


def errors(ref: Reference, answers: Sequence[Tuple[int, np.ndarray, np.ndarray]]
           ) -> np.ndarray:
    """[len(answers), k] array of e_j (module docstring)."""
    if not answers:
        return np.zeros((0, 0))
    queries = sorted({q for q, _, _ in answers})
    k = len(answers[0][1])
    wanted = {q: [] for q in queries}
    for q, ids, _ in answers:
        wanted[q].append(ids)

    def reduce(q: int, col: np.ndarray):
        col = col.copy()
        col[q] = -np.inf
        best = np.sort(np.partition(col, col.shape[0] - k)[-k:])[::-1]
        return best, [col[ids] for ids in wanted[q]]

    reduced = dict(zip(queries, ref.map_columns(queries, reduce)))
    out, seen = [], {q: 0 for q in queries}
    for q, ids, scores in answers:
        best, at_ids = reduced[q]
        r_ids = at_ids[seen[q]]
        seen[q] += 1
        out.append(np.maximum(np.abs(scores - r_ids), np.abs(scores - best)))
    return np.asarray(out)


def mismatches(ref: FixedReference,
               answers: Sequence[Tuple[int, np.ndarray, np.ndarray]]) -> int:
    """How many answers differ from the fixed-point reference's."""
    if not answers:
        return 0
    k = len(answers[0][1])

    def expected(q: int, col: np.ndarray):
        col = col.copy()
        col[q] = -1
        top = np.lexsort((np.arange(col.shape[0]), -col))[:k]
        return top, col[top].astype(np.float64) / ref.scale

    queries = sorted({q for q, _, _ in answers})
    want = dict(zip(queries, ref.map_columns(queries, expected)))
    return sum(1 for q, ids, scores in answers
               if not (np.array_equal(ids, want[q][0])
                       and np.array_equal(scores, want[q][1])))


def compare(requests: Sequence, ref, *, graph: str, precision_key: str,
            k: int, seed: int, max_answers: int) -> Dict[str, float]:
    """The numbers compared (module docstring) over ``requests``: exact
    against a ``FixedReference``, by e_j against a float64 ``Reference``."""
    unanswered = sum(1 for r in requests if not r.ok)
    answered = [r for r in requests if r.ok]
    bad = {id(r) for r in answered
           if _malformed(r, graph, precision_key, k, ref.n)}
    good = served(sample([r for r in answered if id(r) not in bad], seed,
                         max_answers))
    out = {"unanswered": unanswered, "malformed": len(bad),
           "compared": len(good)}
    if isinstance(ref, FixedReference):
        out["mismatched"] = mismatches(ref, good)
    else:
        e = errors(ref, good)
        out["err_max"] = float(e.max()) if e.size else 0.0
        out["err_mean"] = float(e.mean()) if e.size else 0.0
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """``(correct, {name: {"value", "limit"}})`` for every number the
    cell's limits name; a run that compared no answer is not correct."""
    shown = {name: {"value": numbers[name], "limit": limits[name]}
             for name in NUMBERS if name in limits}
    ok = all(v["value"] <= v["limit"] for v in shown.values())
    return ok and numbers["compared"] > 0, shown
