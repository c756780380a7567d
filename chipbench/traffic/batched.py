"""Traffic kind ``batched``: one serving caller, closed loop.

Set-up builds the configuration's serving index with
``retrieval.build_index`` and draws a query pool from the seed.  The
window sends batches of ``batch`` queries from the host, drawn from the
pool by a seeded permutation, through ``retrieval.retrieval_attention_batched`` (serving
defaults: hash visited set, W=4, the mix's block size) and waits for each
answer before sending the next.  ``serve_qps`` is the queries answered
over the whole window.  A seeded sample of the window's batches is kept
and compared with the host reference once the window has closed.
"""
from __future__ import annotations

import time

import numpy as np

import data as datalib
import reference


def build_index(cfg: dict, x):
    from repro.core import vamana
    from repro.serve import retrieval
    b = cfg["build"]
    params = vamana.VamanaParams(L=b["L"], M=b["M"], alpha=b["alpha"])
    return retrieval.build_index(x, x, params, metric=cfg["metric"],
                                 build_impl=b["build_impl"],
                                 batch_size=b["batch_size"], seed=b["seed"])


class Cell:
    kind = "batched"

    def __init__(self, cfg: dict, mix: dict, seed: int, span):
        self.cfg, self.mix, self.seed, self.span = cfg, mix, seed, span
        self.n = cfg["n"][self.kind]
        self.nq = cfg["queries"][self.kind]
        self.kept: list = []

    def setup(self):
        import jax
        import jax.numpy as jnp
        from repro.serve import retrieval
        self.jax, self.retrieval = jax, retrieval
        x, q = datalib.corpus(self.cfg, self.n, self.nq, self.seed)
        self.x_host, self.q_host = x, q
        self.index = build_index(self.cfg, jnp.asarray(x))
        self.rng = np.random.default_rng([self.seed, 2])
        self._serve(self._draw())      # warm-up: the window's one shape

    def _draw(self) -> np.ndarray:
        return self.rng.permutation(self.nq)[:self.mix["batch"]]

    def _serve(self, rows):
        m = self.mix
        with self.span("serve.call"):
            out, res = self.retrieval.retrieval_attention_batched(
                self.index, self.q_host[rows], top_k=m["top_k"], ef=m["ef"],
                block_size=m["block_size"])
            self.jax.block_until_ready((out, res.pool_ids, res.pool_dist))
        return res

    def window(self, seconds: float) -> dict:
        keep = self.mix["sample_batches"]
        sent, counts = 0, []
        t0 = time.perf_counter()
        while True:
            rows = self._draw()
            res = self._serve(rows)
            sent += 1
            counts.append(res.n_computed)
            # reservoir sample of the window's batches, drawn from the seed
            slot = (sent - 1 if sent <= keep
                    else int(self.rng.integers(sent)))
            if slot < keep:
                entry = (rows, res.pool_ids, res.pool_dist)
                if slot < len(self.kept):
                    self.kept[slot] = entry
                else:
                    self.kept.append(entry)
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        queries = sent * self.mix["batch"]
        return {
            "metrics": {"serve_qps": queries / elapsed},
            "attempted": queries,
            "failed": 0,
            "records": {"d": self.cfg["d"], "n": self.n, "queries": queries,
                        "search_dist": sum(int(c) for c in counts)},
        }

    def release(self):
        self.out = [(rows, np.asarray(ids), np.asarray(dist))
                    for rows, ids, dist in self.kept]
        self.kept.clear()
        del self.index

    def compare(self) -> dict:
        if not self.out:
            return {"outputs_missing": 1}
        rows = np.concatenate([r for r, _, _ in self.out])
        ids = np.concatenate([i for _, i, _ in self.out])
        dist = np.concatenate([d for _, _, d in self.out])
        q = self.q_host[rows]
        with self.span("host.reference"):
            truth = datalib.host_knn(self.x_host, q, self.mix["top_k"])
            numbers = reference.pools(q, self.x_host, ids, dist,
                                      self.mix["top_k"])
        numbers["recall_gap"] = reference.recall_gap(ids, truth,
                                                     self.mix["top_k"])
        numbers["outputs_missing"] = 0
        return numbers
