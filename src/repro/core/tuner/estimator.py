"""Parameter estimation — the cost the paper attacks (§IV-C..F).

``estimate`` builds the PGs for a batch of recommended configurations and
measures each graph's (QPS, Recall@k) frontier.  ``group_size`` controls the
paper's sharing: 1 = the baseline estimation every prior tuner uses (each PG
built independently); >1 = FastPGT's simultaneous multi-PG construction with
ESO/EPO.  Wall time and logical #dist are accounted per phase so Table I/IV
and the ablation (Table V) read straight off the returned record.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any

import numpy as np

from repro.core import build as build_lib
from repro.core import eval as evallib
from repro.core import hnsw as hnswlib
from repro.core import metric as metric_lib
from repro.core import obs
from repro.core.counters import BuildCounters
from repro.core.tuner import params as pspace


@dataclasses.dataclass
class Estimate:
    cfg: dict[str, Any]
    qps: float
    recall: float
    points: list          # full (ef, recall, qps) sweep
    def objectives(self) -> tuple[float, float]:
        return self.qps, self.recall


@dataclasses.dataclass
class EstimationRecord:
    estimates: list[Estimate]
    counters: BuildCounters
    build_seconds: float
    eval_seconds: float
    n_dist_eval: int = 0

    @property
    def seconds(self) -> float:
        return self.build_seconds + self.eval_seconds


def resolve_ef_grid(k: int, ef_grid: list[int] | None) -> list[int]:
    """Default + validate the evaluation ef grid BEFORE any build runs.

    Every ef in the grid must hold at least k candidates (search pools
    return only ef ids); an undersized ef would otherwise surface as the
    ``knn_search`` k>ef error in the middle of estimation, with the
    multi-PG builds for the group already paid for.
    """
    ef_grid = ef_grid or [max(10, k), 2 * k, 4 * k, 8 * k]
    if k > min(ef_grid):
        raise ValueError(
            f"k={k} > min(ef_grid)={min(ef_grid)}: every ef in the grid "
            f"must be >= k (a search pool holds only ef candidates), and "
            f"this is checked before any PG is built so an undersized grid "
            f"cannot waste a build; raise the offending ef or lower k")
    return ef_grid


def _eval_one(pg, build_res, gi, data, queries, gt, k, ef_grid, timing_reps,
              visited_impl="dense", expand_width=1):
    metric = build_res.metric     # search under the metric the graph records
    if pg == "hnsw":
        def fn(q, ef):
            return hnswlib.hnsw_search(build_res.g, gi, data, q, k, ef,
                                       metric=metric,
                                       visited_impl=visited_impl,
                                       expand_width=expand_width)
    else:
        def fn(q, ef):
            return evallib.flat_graph_search_fn(
                build_res.g, gi, data, build_res.entry, k, metric,
                visited_impl, expand_width)(q, ef)
    return evallib.evaluate_search_fn(fn, queries, gt, k, ef_grid,
                                      timing_reps=timing_reps)


def estimate(
    pg: str,
    data,
    queries,
    gt,
    cfgs: list[dict[str, Any]],
    *,
    k: int = 10,
    ef_grid: list[int] | None = None,
    group_size: int = 1,
    use_eso: bool = True,
    use_epo: bool = True,
    seed: int = 0,
    build_batch_size: int = 256,
    timing_reps: int = 1,
    metric: str = "l2",
    visited_impl: str = "dense",
    expand_width: int = 1,
    build_impl: str = "fused",
) -> EstimationRecord:
    """Estimate the quality of each configuration in ``cfgs``.

    ``gt`` must be metric-correct ground truth (eval.ground_truth(...,
    metric=metric)) so (QPS, Recall) frontiers are comparable across metrics.
    ``visited_impl`` selects the search visit-state representation for both
    build and evaluation searches; "dense" (default) keeps the paper-exact
    #dist counters the tables report, "hash" estimates with the O(ef)
    serving memory profile (DESIGN.md §9).  ``expand_width`` likewise
    applies to both (DESIGN.md §10); the default 1 keeps estimation
    paper-exact, while W > 1 estimates with the multi-expansion schedule
    serving will actually run (and speeds the measured QPS sweeps up).
    Each group's build runs its batch steps as one compiled dispatch
    (DESIGN.md §12).  ``build_impl`` accepts only "fused" and is not passed
    on: it stays only until the benchmark's mixes stop naming it.  Each call
    also appends its counters and the compilations it caused to
    ``obs.ESTIMATES``.
    """
    build_lib.resolve_build_impl(build_impl)
    ef_grid = resolve_ef_grid(k, ef_grid)
    # Prepare the data ONCE and hand the kernel form down: otherwise every
    # timed cosine search renormalizes the full (n, d) matrix in-jit,
    # deflating cosine QPS relative to l2/ip and skewing the frontiers the
    # tuner compares.
    met = metric_lib.resolve(metric)
    data = met.prepare(data)
    queries = met.prepare(queries)
    metric = met.kernel
    ctr = BuildCounters()
    compiles = obs.compiles()
    estimates: list[Estimate] = []
    t_build = 0.0
    t_eval = 0.0
    n_dist_eval = 0
    group_size = max(1, group_size)

    for goff in range(0, len(cfgs), group_size):
        group = cfgs[goff:goff + group_size]
        bps = [pspace.to_build_params(pg, c) for c in group]
        with obs.span(obs.GROUP):
            t0 = time.perf_counter()
            res = pspace.build_many(
                pg, data, bps, seed=seed,
                use_eso=use_eso and len(group) > 1,
                use_epo=use_epo and len(group) > 1,
                batch_size=build_batch_size, metric=metric,
                visited_impl=visited_impl, expand_width=expand_width)
            t_build += time.perf_counter() - t0
            ctr = ctr.add(res.counters)
            t0 = time.perf_counter()
            for gi, cfg in enumerate(group):
                points = _eval_one(pg, res, gi, data, queries, gt, k,
                                   ef_grid, timing_reps, visited_impl,
                                   expand_width)
                qps, recall = evallib.frontier_objectives(points)
                n_dist_eval += sum(p.n_dist for p in points)
                estimates.append(Estimate(cfg=cfg, qps=qps, recall=recall,
                                          points=points))
            t_eval += time.perf_counter() - t0
    obs.ESTIMATES.append(dict(ctr.as_dict(), configs=len(cfgs),
                              compiles=obs.compiles() - compiles))
    return EstimationRecord(estimates=estimates, counters=ctr,
                            build_seconds=t_build, eval_seconds=t_eval,
                            n_dist_eval=n_dist_eval)


def make_dataset(n: int, d: int, nq: int, *, seed: int = 0,
                 n_clusters: int = 32, spread: float = 4.0):
    """Synthetic clustered dataset (Sift/Glove-like geometry, DESIGN.md §8)."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(n_clusters, d)) * spread
    data = centers[rng.integers(0, n_clusters, n)] + rng.normal(size=(n, d))
    qs = centers[rng.integers(0, n_clusters, nq)] + rng.normal(size=(nq, d))
    return (jnp.asarray(data, jnp.float32), jnp.asarray(qs, jnp.float32))
