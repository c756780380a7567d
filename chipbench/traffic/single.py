"""Traffic kind ``single``: one query per call, closed loop
(ann-benchmarks' non-batch mode).

Same index and query pool as ``batched``.  The window sends one query at
a time from the host through ``retrieval.retrieval_attention`` and waits for its answer;
``query_p95_ms`` is the 95th percentile of every query's latency in the
window.  A seeded sample of the answers is compared with the host
reference once the window has closed.
"""
from __future__ import annotations

import time

import numpy as np

import data as datalib
import reference
from batched import build_index


class Cell:
    kind = "single"

    def __init__(self, cfg: dict, mix: dict, seed: int, span):
        self.cfg, self.mix, self.seed, self.span = cfg, mix, seed, span
        self.n = cfg["n"][self.kind]
        self.nq = cfg["queries"][self.kind]

    def setup(self):
        import jax
        import jax.numpy as jnp
        from repro.serve import retrieval
        self.jax, self.retrieval = jax, retrieval
        x, q = datalib.corpus(self.cfg, self.n, self.nq, self.seed)
        self.x_host, self.q_host = x, q
        self.index = build_index(self.cfg, jnp.asarray(x))
        self.order = np.random.default_rng([self.seed, 3]).permutation(self.nq)
        self._serve(0)                 # warm-up: the window's one shape

    def _serve(self, i: int):
        m = self.mix
        row = int(self.order[i % self.nq])
        with self.span("serve.call"):
            out, res = self.retrieval.retrieval_attention(
                self.index, self.q_host[row:row + 1], top_k=m["top_k"],
                ef=m["ef"])
            self.jax.block_until_ready((out, res.pool_ids, res.pool_dist))
        return row, res

    def window(self, seconds: float) -> dict:
        lat, kept, counts = [], [], []
        t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < seconds:
            t = time.perf_counter()
            row, res = self._serve(i)
            lat.append(time.perf_counter() - t)
            kept.append((row, res.pool_ids, res.pool_dist))
            counts.append(res.n_computed)
            i += 1
        p95 = float(np.percentile(np.asarray(lat) * 1e3, 95))
        pick = np.random.default_rng([self.seed, 4]).choice(
            len(kept), min(self.mix["sample_queries"], len(kept)),
            replace=False)
        self.kept = [kept[j] for j in np.sort(pick)]
        return {
            "metrics": {"query_p95_ms": p95},
            "attempted": len(lat),
            "failed": 0,
            "records": {"d": self.cfg["d"], "n": self.n, "queries": len(lat),
                        "search_dist": sum(int(c) for c in counts)},
        }

    def release(self):
        self.out = [(row, np.asarray(ids), np.asarray(dist))
                    for row, ids, dist in self.kept]
        self.kept = []
        del self.index

    def compare(self) -> dict:
        if not self.out:
            return {"outputs_missing": 1}
        rows = np.asarray([r for r, _, _ in self.out])
        ids = np.concatenate([i for _, i, _ in self.out])
        dist = np.concatenate([d for _, _, d in self.out])
        q = self.q_host[rows]
        truth = datalib.host_knn(self.x_host, q, self.mix["top_k"])
        numbers = reference.pools(q, self.x_host, ids, dist,
                                  self.mix["top_k"])
        numbers["recall_gap"] = reference.recall_gap(ids, truth,
                                                     self.mix["top_k"])
        numbers["outputs_missing"] = 0
        return numbers
