"""Corpora and the plain host reference, made from the configuration and
``--seed`` alone.

Copied from ``chip_smoke.py`` (``sift_shaped``, ``host_sqdist``,
``host_knn``, ``recall``) and from ``estimator.make_dataset``, so that the
yardstick does not move when the program's own copies do.  Nothing here
imports the program.

A corpus is an ann-benchmarks deployment's shape: a clustered corpus drawn
in ``intrinsic_d`` dimensions and embedded in the published width ``d`` by
a seeded orthonormal map.  Distances are preserved, so neighbours are the
low-dimensional corpus's while every distance the program computes is
``d`` wide.
"""
from __future__ import annotations

import numpy as np


def corpus(cfg: dict, n: int, nq: int, seed: int):
    """(data f32[n, d], queries f32[nq, d]) as NumPy arrays.

    ``cfg`` is a configuration file with its ``generator`` group and ``d``.
    The rows are the deployment's one fixed corpus, drawn from the
    generator's ``data_seed`` and kept in its order, as a published
    dataset's base file is; ``seed`` chooses the order in which its
    queries are sent.  The order in which rows are inserted decides how
    many hops each insertion batch's searches take, so a seeded row order
    would change the work from seed to seed; a seeded query order does not.
    """
    x, q = _draw(cfg, n, nq, cfg["generator"]["data_seed"])
    return x, q[np.random.default_rng([seed, 2]).permutation(nq)]


def _draw(cfg: dict, n: int, nq: int, seed: int):
    gen = cfg["generator"]
    d, idim = cfg["d"], gen["intrinsic_d"]
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(gen["clusters"], idim)) * gen["spread"]
    data = (centers[rng.integers(0, gen["clusters"], n)]
            + rng.normal(size=(n, idim)))
    qs = (centers[rng.integers(0, gen["clusters"], nq)]
          + rng.normal(size=(nq, idim)))
    g = np.random.default_rng([seed, d]).normal(size=(d, idim))
    basis = np.linalg.qr(g)[0]                          # (d, idim)
    return tuple((a @ basis.T).astype(np.float32) for a in (data, qs))


def host_sqdist(q, x):
    """Exact squared L2 distances (nq, nx) in float64."""
    q = np.asarray(q, np.float64)
    x = np.asarray(x, np.float64)
    return ((q * q).sum(1)[:, None] + (x * x).sum(1)[None, :]
            - 2.0 * q @ x.T)


def host_knn(data, queries, k: int, block: int = 8192):
    """Exact top-k ids (nq, k) by squared L2, scanned in corpus blocks."""
    x, q = np.asarray(data), np.asarray(queries)
    best_d = np.empty((q.shape[0], 0))
    best_i = np.empty((q.shape[0], 0), np.int64)
    for off in range(0, x.shape[0], block):
        dd = np.concatenate([best_d, host_sqdist(q, x[off:off + block])], 1)
        ii = np.concatenate([best_i, np.broadcast_to(
            np.arange(off, min(off + block, x.shape[0])),
            (q.shape[0], dd.shape[1] - best_i.shape[1]))], 1)
        top = np.argpartition(dd, k - 1, axis=1)[:, :k]
        best_d = np.take_along_axis(dd, top, 1)
        best_i = np.take_along_axis(ii, top, 1)
    return best_i


def recall(found, truth, k: int) -> float:
    """Mean |found[:, :k] ∩ truth| / k over the queries."""
    found = np.asarray(found)[:, :k]
    hits = [len(set(f.tolist()) & set(t.tolist()))
            for f, t in zip(found, np.asarray(truth))]
    return float(np.mean(hits) / k)
