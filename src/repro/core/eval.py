"""k-ANNS evaluation: Recall@k, QPS, and the tuning objective.

Parameter *estimation* in the paper = build the PG, then measure the
(QPS, Recall@k) frontier over the query set.  ``ef`` is a search-time knob
tuned jointly with the construction parameters (VDTuner's setup): an
evaluation sweeps ef values and reports the best QPS meeting each recall
target plus the raw (QPS, Recall) points for the MOBO tuner.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import knng, obs, search
from repro.core.graph import INVALID, MultiGraph


@dataclasses.dataclass
class EvalPoint:
    ef: int
    recall: float
    qps: float
    n_dist: int


def recall_at_k(found_ids: jax.Array, gt_ids: jax.Array) -> float:
    """Mean |found ∩ gt| / |valid gt| over the query batch.

    Both sides may be INVALID-padded (short pools, dead shards, n < k
    ground truth).  An INVALID ground-truth slot is *padding*, not a
    neighbor: matching it against an INVALID found slot must not count
    as a hit, so matches are masked to valid gt entries and each query
    normalizes by its own valid-gt count (floored at 1 — an all-padding
    gt row contributes 0, not NaN).
    """
    valid = gt_ids != INVALID
    match = (found_ids[:, :, None] == gt_ids[:, None, :]) & valid[:, None, :]
    hits = match.any(-1)
    denom = jnp.maximum(jnp.sum(valid, axis=-1), 1)
    return float(jnp.mean(jnp.sum(hits, axis=-1) / denom))


def ground_truth(data, queries, k: int, metric: str = "l2") -> jax.Array:
    """Metric-correct exact top-k ids (recall denominators must match the
    metric the index ranks by, or cross-metric frontiers aren't comparable)."""
    ids, _ = knng.exact_knn(data, queries, k, metric=metric)
    return ids


def evaluate_search_fn(
    search_fn: Callable[[jax.Array, int], search.SearchResult],
    queries: jax.Array,
    gt_ids: jax.Array,
    k: int,
    ef_grid: list[int],
    *,
    timing_reps: int = 2,
) -> list[EvalPoint]:
    """Sweep ef, returning (recall, QPS) per point.

    ``search_fn(queries, ef)`` must return a SearchResult whose pool prefix
    holds k ids.  QPS is measured wall-clock (CPU here; DESIGN.md §8 —
    *ratios* across configurations are the reproduced quantity).
    """
    points = []
    nq = queries.shape[0]
    for ef in ef_grid:
        with obs.span(obs.EVAL_POINT):
            with obs.span(obs.RECALL_SEARCH):
                res = search_fn(queries, ef)
                jax.block_until_ready(res.pool_ids)
            with obs.span(obs.TIMED_SEARCH):
                t0 = time.perf_counter()
                for _ in range(timing_reps):
                    r2 = search_fn(queries, ef)
                    jax.block_until_ready(r2.pool_ids)
                dt = (time.perf_counter() - t0) / timing_reps
            rec = recall_at_k(res.pool_ids[:, :k], gt_ids)
            points.append(EvalPoint(ef=ef, recall=rec,
                                    qps=nq / max(dt, 1e-9),
                                    n_dist=int(res.n_computed)))
    return points


def flat_graph_search_fn(g: MultiGraph, graph_idx: int, data, entry: int,
                         k: int, metric: str = "l2",
                         visited_impl: str = "dense",
                         expand_width: int = 1):
    """Search closure for single-layer graphs (Vamana/NSG)."""
    def fn(queries, ef):
        return search.knn_search(
            g.ids[graph_idx], data, queries, k, ef, entry, metric=metric,
            visited_impl=visited_impl, expand_width=expand_width)
    return fn


def best_qps_at_recall(points: list[EvalPoint], target: float) -> float:
    """Best QPS among eval points meeting Recall@k >= target (0 if none)."""
    ok = [p.qps for p in points if p.recall >= target]
    return max(ok) if ok else 0.0


def frontier_objectives(points: list[EvalPoint]) -> tuple[float, float]:
    """(best QPS, best recall) summary pair used as the MOBO observation."""
    if not points:
        return 0.0, 0.0
    # VDTuner observes one (QPS, Recall) per configuration; we follow its
    # protocol of reporting the knee point: maximize qps * recall.
    best = max(points, key=lambda p: p.qps * max(p.recall, 1e-6))
    return best.qps, best.recall


def pareto_points(points: list[EvalPoint]) -> list[EvalPoint]:
    """Non-dominated subset of the (QPS, recall) sweep."""
    out = []
    for p in points:
        if not any((q.qps >= p.qps and q.recall >= p.recall and
                    (q.qps > p.qps or q.recall > p.recall)) for q in points):
            out.append(p)
    return sorted(out, key=lambda p: p.recall)
