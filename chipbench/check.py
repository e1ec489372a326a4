"""The comparison that decides ``correct``, and recall.

Every answer the timed path returned in the window is judged against the
float64 reference (``reference.exact``) by what it says:

``dist_err``  the widest gap between a distance the program returned and
              the exact float64 distance of the id it returned with it, as
              a share of the query's exact k-th distance.  Float32 at full
              precision keeps it near 1e-6; a scan at a lower precision, an
              altered id or distance, or a missing answer (id -1, +inf)
              pushes it far above.  Its limit is in the configuration's
              ``check`` block, set from readings (``readings.py``).
``dup_ids``   answers that name one id twice: exactly 0.
``recall``    ANN-Benchmarks' recall (``get_recall_values``): a returned
              id counts when its exact euclidean distance is at most the
              exact k-th one plus ``recall_epsilon`` (1e-3).  Per group of
              query parameters it must reach the configuration's
              ``recall_floor`` for that group (1.0 for exact search).
``unanswered``  requests with no answer (failed, timed out, rejected):
              exactly 0.

Answers repeat in closed loops (the queries cycle), so each distinct
(query, answer) pair is rescored once and counted as often as it came.
"""

from __future__ import annotations

import numpy as np

from chipbench import reference


def group_key(query_params: dict) -> str:
    """The name of a group of query parameters: ``default`` when empty,
    else ``key=value`` pairs joined by ``,`` (``n_probes=8``)."""
    if not query_params:
        return "default"
    return ",".join(f"{k}={query_params[k]}" for k in sorted(query_params))


def judge(X_host: np.ndarray, X_dev, Q: np.ndarray, answers: dict,
          k: int, guarantees: dict, limits: dict, unanswered: int) -> dict:
    """Compare the window's answers with the reference.

    ``answers``: ``qidx [A]``, ``ids [A, k]``, ``dists [A, k]`` and
    ``group [A]`` (an index into ``answers["groups"]``, the group keys).
    Returns ``{"correct": bool, "recall": float, "checks": {name: [value,
    limit]}}``; a check passes when its value is at most its limit, or for
    recall at least its floor.
    """
    qidx = np.asarray(answers["qidx"], np.int64)
    ids = np.asarray(answers["ids"], np.int64)
    dists = np.asarray(answers["dists"], np.float64)
    group = np.asarray(answers["group"], np.int64)
    if ids.shape != (len(qidx), k):
        raise ValueError(f"answers must be [A, {k}], got {ids.shape}")
    # distinct (query, answer) pairs, rescored once each
    key = np.concatenate([qidx[:, None], ids, dists.view(np.int64)], axis=1)
    uniq, inverse = np.unique(key, axis=0, return_inverse=True)
    inverse = inverse.reshape(-1)
    u_q = uniq[:, 0]
    u_ids = uniq[:, 1:k + 1]
    u_d = uniq[:, k + 1:].copy().view(np.float64)
    queries = np.unique(u_q)
    ref = reference.exact(X_host, X_dev, Q, queries, k)       # [nq, k]
    ref_kth = ref[np.searchsorted(queries, u_q), k - 1]       # [U]
    d64 = reference.sq_dists64(X_host, Q, u_q, u_ids)          # [U, k]
    with np.errstate(invalid="ignore"):
        err = np.abs(u_d - d64) / np.maximum(ref_kth, 1e-30)[:, None]
    err = np.where(np.isfinite(err), err, np.inf)
    dist_err = float(err.max()) if err.size else 0.0
    srt = np.sort(u_ids, axis=1)
    dup_rows = np.any(srt[:, 1:] == srt[:, :-1], axis=1)
    dup_ids = int(dup_rows[inverse].sum())
    eps = float(guarantees.get("recall_epsilon", 1e-3))
    hits_u = np.sum(np.sqrt(d64) <= np.sqrt(ref_kth)[:, None] + eps, axis=1)
    hits = hits_u[inverse]
    checks = {"dist_err": [dist_err, float(limits["dist_err"])],
              "dup_ids": [int(dup_ids), 0], "unanswered": [int(unanswered), 0]}
    ok = dist_err <= limits["dist_err"] and dup_ids == 0 and unanswered == 0
    floors = guarantees["recall_floor"]
    for g, name in enumerate(answers["groups"]):
        sel = group == g
        if not sel.any():
            continue
        r = float(hits[sel].sum()) / (sel.sum() * k)
        floor = float(floors[name])
        checks[f"recall.{name}"] = [float(r), floor]
        ok = ok and r >= floor
    recall = float(hits.sum()) / (len(hits) * k) if len(hits) else 0.0
    return {"correct": bool(ok and len(hits) > 0), "recall": recall,
            "checks": checks}
