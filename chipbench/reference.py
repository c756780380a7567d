"""The comparison that decides ``correct``: the program's outputs against
the plain host reference (NumPy float64 over the same float32 inputs).

Every number compared is a gap, so that a larger value is worse, and is
printed beside its limit.  Distances are compared as squared L2, the
program's l2 kernel form, relative to the norms that the norm expansion
``|a|^2 + |b|^2 - 2 a.b`` cancels: that is the scale on which a dot at a
lower precision shows (a bfloat16 pass rounds each product to ~2^-9 of
``|a||b|``).  Nothing here imports the program.
"""
from __future__ import annotations

import numpy as np

from data import recall

INVALID = -1


def _sqdist_rows(a, b):
    """Exact squared L2 between paired rows a[i], b[i] (float64)."""
    diff = np.asarray(a, np.float64) - np.asarray(b, np.float64)
    return (diff * diff).sum(-1)


def _rel_err(got, a, b) -> np.ndarray:
    a64, b64 = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = (a64 * a64).sum(-1) + (b64 * b64).sum(-1)
    return np.abs(np.asarray(got, np.float64) - _sqdist_rows(a64, b64)) / \
        np.maximum(scale, 1e-30)


def pools(queries, data, ids, dists, k: int, block: int = 4096) -> dict:
    """Check top-k search pools (nq, k) against the host reference.

    ``pool_bad`` counts slots that no exact answer can hold: an INVALID or
    out-of-range id, an id repeated in its row, or a row not ascending by
    the distance it reports.  ``pool_dist_err`` is the widest relative gap
    between a reported distance and the exact one.
    """
    ids = np.asarray(ids)[:, :k]
    dists = np.asarray(dists, np.float64)[:, :k]
    n = np.asarray(data).shape[0]
    ok = (ids >= 0) & (ids < n)
    srt = np.sort(ids, axis=1)
    dup = np.zeros_like(ok)
    dup[:, 1:] = (srt[:, 1:] == srt[:, :-1]) & (srt[:, 1:] >= 0)
    descending = np.zeros_like(ok)
    descending[:, 1:] = dists[:, 1:] < dists[:, :-1]
    bad = int((~ok).sum() + dup.sum() + descending.sum())
    err, total, count = 0.0, 0.0, 0
    q = np.asarray(queries)
    for off in range(0, ids.shape[0], block):
        i = ids[off:off + block]
        m = ok[off:off + block]
        rows = np.broadcast_to(np.arange(i.shape[0])[:, None], i.shape)[m]
        e = _rel_err(dists[off:off + block][m], q[off:off + block][rows],
                     np.asarray(data)[i[m]])
        err = max(err, float(e.max(initial=0.0)))
        total, count = total + float(e.sum()), count + e.size
    return {"pool_bad": bad, "pool_dist_err": err,
            "pool_dist_err_mean": total / max(count, 1)}


def graphs(data, ids, dists, degrees, nodes) -> dict:
    """Check m built graphs (m, n, M_max) against the host reference.

    ``edge_bad`` counts, over every node, edges no Vamana graph can hold:
    an out-of-range id, a self loop, a repeated neighbour, a slot past the
    graph's degree limit, or an empty slot whose distance is not +inf.
    ``edge_dist_err`` is the widest relative gap between a stored edge
    distance and the exact one, over the sampled ``nodes``;
    ``edge_dist_err_mean`` the mean gap.
    """
    ids = np.asarray(ids)
    dists = np.asarray(dists, np.float64)
    x = np.asarray(data)
    m, n, mx = ids.shape
    bad = 0
    err, total, count = 0.0, 0.0, 0
    for g in range(m):
        gi, gd = ids[g], dists[g]
        valid = gi != INVALID
        bad += int((valid & ((gi < 0) | (gi >= n))).sum())
        bad += int((gi == np.arange(n)[:, None]).sum())
        srt = np.sort(np.where(valid, gi, -1 - np.arange(mx)), axis=1)
        bad += int((srt[:, 1:] == srt[:, :-1]).sum())
        bad += int(valid[:, degrees[g]:].sum())
        bad += int((~valid & ~np.isposinf(gd)).sum())
        vi, vd, vm = gi[nodes], gd[nodes], valid[nodes]
        rows = np.broadcast_to(np.asarray(nodes)[:, None], vi.shape)[vm]
        e = _rel_err(vd[vm], x[rows], x[np.clip(vi[vm], 0, n - 1)])
        err = max(err, float(e.max(initial=0.0)))
        total, count = total + float(e.sum()), count + e.size
    return {"edge_bad": bad, "edge_dist_err": err,
            "edge_dist_err_mean": total / max(count, 1)}


def recall_gap(found, truth, k: int) -> float:
    """1 - recall@k against the exact neighbours."""
    return 1.0 - recall(found, truth, k)


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: [value, limit]}) over the numbers that have a
    limit: every one at or under it.  A limit whose number is missing is
    not correct; a number without a limit is a reading, not compared."""
    table = {k: [numbers.get(k), limits[k]] for k in sorted(limits)}
    ok = all(v is not None and lim is not None and np.isfinite(v)
             and v <= lim for v, lim in table.values())
    return bool(ok), table
