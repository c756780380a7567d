"""Traffic kind ``estimate``: an index operator's tuning loop.

One caller calls ``estimator.estimate`` on a fixed schedule of
configurations, again and again, until the window's deadline has passed;
the call in which it passes runs to its end, so that every configuration
counted was built and swept in full.  ``tune_cfgs_per_s`` is the
configurations estimated over the time those calls took.

``estimate`` returns recall and QPS, not the graphs and pools behind
them, so two pass-through taps record them: ``params.build_many`` (the
group's multi-build) and ``eval.evaluate_search_fn`` (each configuration's
ef sweep).  The taps keep references and add the harness's spans
``estimate.build`` and ``estimate.eval``; they change no argument and no
result.
"""
from __future__ import annotations

import time

import numpy as np

import data as datalib
import reference


class Cell:
    def __init__(self, cfg: dict, mix: dict, seed: int, span):
        self.cfg, self.mix, self.seed, self.span = cfg, mix, seed, span
        self.n = cfg["n"]["estimate"]
        self.nq = cfg["queries"]["estimate"]
        self.k = mix["k"]
        self.calls: list[dict] = []

    # -- taps ---------------------------------------------------------------
    def _install_taps(self):
        from repro.core import eval as evallib
        from repro.core.tuner import params as pspace
        # unwrap an earlier cell's taps: one process may run several cells
        build_many = getattr(pspace.build_many, "__wrapped__",
                             pspace.build_many)
        evaluate = getattr(evallib.evaluate_search_fn, "__wrapped__",
                           evallib.evaluate_search_fn)
        cell = self

        def tapped_build_many(*a, **kw):
            with cell.span("estimate.build"):
                res = build_many(*a, **kw)
            cell._current["builds"].append(res)
            return res

        def tapped_evaluate(search_fn, *a, **kw):
            first, all_counts = {}, []

            def fn(q, ef):
                r = search_fn(q, ef)
                first.setdefault(ef, r)
                all_counts.append(r.n_computed)
                return r
            with cell.span("estimate.eval"):
                points = evaluate(fn, *a, **kw)
            cell._current["evals"].append((points, first))
            cell._current["eval_counts"] += all_counts
            return points

        tapped_build_many.__wrapped__ = build_many
        tapped_evaluate.__wrapped__ = evaluate
        pspace.build_many = tapped_build_many
        evallib.evaluate_search_fn = tapped_evaluate

    # -- phases -------------------------------------------------------------
    def setup(self, warm: bool = True):
        import jax.numpy as jnp
        from repro.core.tuner import estimator
        self.estimator = estimator
        x, q = datalib.corpus(self.cfg, self.n, self.nq, self.seed)
        self.x_host, self.q_host = x, q
        # the tuner's input: exact neighbours, as an operator supplies them
        self.truth = datalib.host_knn(x, q, self.k)
        self.x, self.q = jnp.asarray(x), jnp.asarray(q)
        self.gt = jnp.asarray(self.truth, jnp.int32)
        self._install_taps()
        if warm:
            # warm-up: the same call on NaN data of the same shapes compiles
            # (or loads) every program the window runs, while each search
            # ends at its first hop, since no NaN distance enters a pool
            x_nan = jnp.full(self.x.shape, jnp.nan, self.x.dtype)
            q_nan = jnp.full(self.q.shape, jnp.nan, self.q.dtype)
            self._call(x_nan, q_nan)
            self.calls.clear()

    def _call(self, x=None, q=None):
        m = self.mix
        self._current = {"builds": [], "evals": [], "eval_counts": []}
        t0 = time.perf_counter()
        rec = self.estimator.estimate(
            m["pg"], self.x if x is None else x, self.q if q is None else q,
            self.gt, m["cfgs"], k=self.k,
            ef_grid=m["ef_grid"], group_size=m["group_size"],
            use_eso=m["use_eso"], use_epo=m["use_epo"],
            timing_reps=m["timing_reps"], metric=self.cfg["metric"],
            build_impl=m["build_impl"])
        self._current.update(record=rec, seconds=time.perf_counter() - t0)
        self.calls.append(self._current)

    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        while True:
            self._call()
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        n_cfg = len(self.mix["cfgs"]) * len(self.calls)
        recs = [c["record"] for c in self.calls]
        ctr_search = sum(r.counters.search for r in recs)
        eval_dist = sum(int(c) for call in self.calls
                        for c in call["eval_counts"])
        return {
            "metrics": {"tune_cfgs_per_s": n_cfg / elapsed},
            "attempted": n_cfg,
            "failed": 0,
            "records": {
                "d": self.cfg["d"], "n": self.n, "configs": n_cfg,
                "build_seconds": sum(r.build_seconds for r in recs),
                "eval_seconds": sum(r.eval_seconds for r in recs),
                "build_dist": sum(r.counters.total for r in recs),
                "search_dist": ctr_search + eval_dist,
                "call_seconds": [c["seconds"] for c in self.calls],
            },
        }

    def release(self):
        """Fetch one call's outputs to the host, drawn from the seed, and
        drop every device reference."""
        rng = np.random.default_rng([self.seed, 1])
        call = self.calls[int(rng.integers(len(self.calls)))]
        self.out = {
            "graphs": [(np.asarray(b.g.ids), np.asarray(b.g.dist),
                        [p.M for p in b.params]) for b in call["builds"]],
            "pools": [[(ef, np.asarray(r.pool_ids), np.asarray(r.pool_dist))
                       for ef, r in sorted(first.items())]
                      for _, first in call["evals"]],
            "reported": [[(p.ef, p.recall) for p in points]
                         for points, _ in call["evals"]],
        }
        self.nodes = rng.choice(self.n, min(self.mix["sample_nodes"], self.n),
                                replace=False)
        self.calls.clear()
        del self.x, self.q, self.gt, self._current

    def compare(self) -> dict:
        out, k = self.out, self.k
        n_cfg = len(self.mix["cfgs"])
        numbers = {"edge_bad": 0, "edge_dist_err": 0.0, "pool_bad": 0,
                   "pool_dist_err": 0.0}
        if len(out["pools"]) != n_cfg or not out["graphs"]:
            # the taps saw no build or not every sweep: nothing to compare
            return {"outputs_missing": 1}
        for ids, dist, degrees in out["graphs"]:
            g = reference.graphs(self.x_host, ids, dist, degrees, self.nodes)
            numbers["edge_bad"] += g["edge_bad"]
            numbers["edge_dist_err"] = max(numbers["edge_dist_err"],
                                           g["edge_dist_err"])
        top_gaps, report_bad = [], 0
        for pools, reported in zip(out["pools"], out["reported"]):
            for ef, ids, dist in pools:
                p = reference.pools(self.q_host, self.x_host, ids, dist, k)
                numbers["pool_bad"] += p["pool_bad"]
                numbers["pool_dist_err"] = max(numbers["pool_dist_err"],
                                               p["pool_dist_err"])
            gaps = {ef: reference.recall_gap(ids, self.truth, k)
                    for ef, ids, _ in pools}
            top_gaps.append(gaps[max(gaps)])
            report_bad += sum(abs((1.0 - gaps[ef]) - r) > 1e-6
                              for ef, r in reported)
        # 1 - recall@k at the grid's widest ef: the mean over the
        # configurations, and the best and worst configuration's
        numbers["recall_gap"] = float(np.mean(top_gaps))
        numbers["recall_gap_best"] = min(top_gaps)
        numbers["recall_gap_worst"] = max(top_gaps)
        numbers["recall_report_bad"] = report_bad
        numbers["outputs_missing"] = 0
        return numbers
