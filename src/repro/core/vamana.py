"""BuildMultiVamana — Algorithm 6, batched TPU adaptation.

m Vamana graphs with parameters {(L_i, M_i, alpha_i)} are built in one pass
over the dataset (R = L per Theorem 1).  Each insertion batch searches the
graph frozen at batch start (standard GPU/TPU relaxation, DESIGN.md §3),
shares one V_delta across the m per-node searches (ESO), chains the m prunes
through mPrune (EPO, group sorted ascending by alpha for soundness), and
commits forward + reverse edges with overflow re-prune.  ``expand_width``
stays 1 by default so construction follows the paper's sequential
best-first schedule exactly (DESIGN.md §10).

``visited_impl`` selects the search's visit-state representation; builds
default to "dense" so graph outputs and #dist counters stay bit-identical
to the paper's accounting (DESIGN.md §2.1, §9) — "hash" trades exact
counters for O(ef)-memory search state.

The main pass is ONE device-resident compiled dispatch
(``core/build.fused_vamana_pass``, DESIGN.md §12): a ``lax.fori_loop`` over
every insertion batch, with per-batch counter rows logged on device and
synced to the host once at the end.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core import build as build_lib
from repro.core import graph, obs, search
from repro.core import metric as metric_lib
from repro.core.counters import BuildCounters, CounterTape
from repro.core.graph import INVALID, MultiGraph


@dataclasses.dataclass(frozen=True)
class VamanaParams:
    L: int          # search pool size (= R per Theorem 1)
    M: int          # out-degree limit
    alpha: float    # pruning parameter

    def clamped(self, n: int) -> "VamanaParams":
        return VamanaParams(min(self.L, n - 1), min(self.M, n - 1), self.alpha)


@dataclasses.dataclass
class BuildResult:
    g: MultiGraph               # in the *original* parameter order
    entry: int
    counters: BuildCounters
    params: list
    metric: str = "l2"          # metric the graph was built (and ranks) under


def build_multi_vamana(
    data,
    params: list[VamanaParams],
    *,
    seed: int = 0,
    batch_size: int = 128,
    use_eso: bool = True,
    use_epo: bool = True,
    k_in: int = 16,
    max_hops: int | None = None,
    metric: str = "l2",
    visited_impl: str = "dense",
    expand_width: int = 1,
) -> BuildResult:
    met = metric_lib.resolve(metric)
    data = met.prepare(data)      # normalize ONCE for cosine (no-op otherwise)
    kform = met.kernel            # hot loops see only the kernel form
    n, _ = data.shape
    params = [p.clamped(n) for p in params]
    m = len(params)
    order = sorted(range(m), key=lambda i: params[i].alpha)   # EPO soundness
    inv_order = np.argsort(order)
    ps = [params[i] for i in order]
    L = jnp.array([p.L for p in ps], jnp.int32)
    M = jnp.array([p.M for p in ps], jnp.int32)
    alpha = jnp.array([p.alpha for p in ps], jnp.float32)
    # Static shape maxima rounded to buckets: per-graph masks enforce the
    # true L_i/M_i, so padding never changes results — it keeps compiled
    # shapes identical across tuning iterations (one XLA compile, reused).
    L_max = graph.bucket(max(p.L for p in ps), 16)
    M_max = graph.bucket(max(p.M for p in ps), 8)
    ctr = BuildCounters()
    tape = CounterTape()

    # ---- Initialization: deterministic shared random KNNG (Alg. 6 l.1-2) ---
    with obs.span(obs.VAMANA_INIT):
        init_ids = graph.random_knng_ids(seed, n, M_max)      # shared prefix
        init_dist = graph.with_distances(data, init_ids, kform)
        gids, gdist = [], []
        for p in ps:
            dm = jnp.arange(M_max)[None, :] < p.M
            gids.append(jnp.where(dm, init_ids, INVALID))
            gdist.append(jnp.where(dm, init_dist, jnp.inf))
        g = MultiGraph(ids=jnp.stack(gids), dist=jnp.stack(gdist))
        ctr.init_base += sum(n * p.M for p in ps)
        ctr.init += n * M_max if use_eso else ctr.init_base

        ep = int(graph.medoid(data, kform))                   # Alg. 6 l.3
    hops = max_hops or search.default_max_hops(L_max)
    step_kw = dict(ef_max=L_max, max_hops=hops, share_cache=use_eso,
                   use_epo=use_epo, metric=kform,
                   visited_impl=visited_impl,
                   expand_width=expand_width, k_in=k_in, m_max=M_max)

    # ---- main pass (Alg. 6 l.4-12), batched ---------------------------------
    # ONE compiled dispatch for the whole pass: lax.fori_loop over batches on
    # device, counter rows logged into a device array (DESIGN.md §12).  Looked
    # up through the module at call time so a wrapper installed there applies.
    new_ids, new_dist, log = build_lib.fused_vamana_pass(
        g.ids, g.dist, data, L, M, alpha, jnp.int32(ep),
        batch_size=batch_size, **step_kw)
    g = MultiGraph(ids=new_ids, dist=new_dist)
    tape.log_many(log)

    tape.drain_into(ctr)          # the build's ONE counter host sync
    g = MultiGraph(ids=g.ids[inv_order], dist=g.dist[inv_order])
    return BuildResult(g=g, entry=ep, counters=ctr, params=params,
                       metric=met.name)


def build_vamana(data, p: VamanaParams, **kw) -> BuildResult:
    """Single-graph build (baseline estimation path: no sharing possible)."""
    kw.setdefault("use_eso", False)
    kw.setdefault("use_epo", False)
    return build_multi_vamana(data, [p], **kw)
