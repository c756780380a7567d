"""Multi-NSG construction — Alg. 6 variant per §IV-F of the paper.

Differences from Vamana: the initial graph is a *real* KNNG (exact blocked
brute force here instead of KGraph/nn-descent — a strictly-higher-quality
deterministic stand-in, DESIGN.md §8), searches run on that static KNNG
(not on the evolving graph), alpha is fixed at 1, and a connectivity-repair
pass re-attaches nodes unreachable from the medoid (NSG's spanning step).

Parameters per graph: (K_i initial out-degree, L_i pool, M_i degree limit).
The exact KNNG is computed once at K_max and every graph takes a prefix —
the deterministic shared-initialization strategy (counted once under ESO).

Each batch's search + KNNG-row candidate merge + mPrune + commit is one
``core/build.nsg_insert_batch`` dispatch (DESIGN.md §12).  Counters
accumulate on device (CounterTape) and sync once at the end of the main
pass.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core import build as build_lib
from repro.core import graph, knng, search
from repro.core import metric as metric_lib
from repro.core.counters import BuildCounters, CounterTape
from repro.core.graph import INVALID, MultiGraph
from repro.kernels import ops


@dataclasses.dataclass(frozen=True)
class NSGParams:
    K: int      # initial KNNG out-degree
    L: int      # search pool size
    M: int      # out-degree limit

    def clamped(self, n: int) -> "NSGParams":
        return NSGParams(min(self.K, n - 1), min(self.L, n - 1),
                         min(self.M, n - 1))


@dataclasses.dataclass
class NSGBuildResult:
    g: MultiGraph
    entry: int
    counters: BuildCounters
    params: list
    metric: str = "l2"          # metric the graph was built (and ranks) under


def build_multi_nsg(
    data,
    params: list[NSGParams],
    *,
    seed: int = 0,           # unused (exact init is deterministic); kept for API
    batch_size: int = 128,
    use_eso: bool = True,
    use_epo: bool = True,
    k_in: int = 16,
    max_hops: int | None = None,
    repair_iters: int = 2,
    metric: str = "l2",
    visited_impl: str = "dense",
    expand_width: int = 1,
) -> NSGBuildResult:
    del seed
    met = metric_lib.resolve(metric)
    data = met.prepare(data)      # normalize ONCE for cosine (no-op otherwise)
    kform = met.kernel
    n, _ = data.shape
    params = [p.clamped(n) for p in params]
    m = len(params)
    L = jnp.array([p.L for p in params], jnp.int32)
    M = jnp.array([p.M for p in params], jnp.int32)
    alpha1 = jnp.ones((m,), jnp.float32)
    L_max = graph.bucket(max(p.L for p in params), 16)
    M_max = graph.bucket(max(p.M for p in params), 8)
    K_max = graph.bucket(max(p.K for p in params), 8)
    ctr = BuildCounters()
    tape = CounterTape()
    hops = max_hops or search.default_max_hops(L_max)
    K = jnp.array([p.K for p in params], jnp.int32)

    # ---- Initialization: shared exact KNNG at K_max, per-graph prefixes ----
    knn_ids, knn_dist = knng.build_knng(data, K_max, metric=kform)
    init_knng = []
    for p in params:
        dm = jnp.arange(K_max)[None, :] < p.K
        init_knng.append(jnp.where(dm, knn_ids, INVALID))
    init_stack = jnp.stack(init_knng)                     # (m, n, K_max)
    ctr.init_base += m * knng.knng_dist_count(n)
    ctr.init += knng.knng_dist_count(n) if use_eso else ctr.init_base

    ep = int(graph.medoid(data, kform))
    g = graph.empty_multigraph(m, n, M_max)

    # ---- Search on the static KNNG + prune + commit (batched) --------------
    for off in range(0, n, batch_size):
        ids_np = np.arange(off, min(off + batch_size, n), dtype=np.int32)
        b = batch_size
        u = jnp.full((b,), n, jnp.int32).at[:len(ids_np)].set(
            jnp.array(ids_np))
        row_mask = jnp.arange(b) < len(ids_np)
        queries = data[jnp.minimum(u, n - 1)]
        entry = jnp.broadcast_to(jnp.int32(ep), (b, m))

        # ONE dispatch: search + KNNG-row merge + mPrune + commit
        # (DESIGN.md §12).
        new_ids, new_dist, row = build_lib.nsg_insert_batch(
            init_stack, g.ids, g.dist, knn_ids, knn_dist, data, u,
            row_mask, queries, L, M, alpha1, K, entry,
            ef_max=L_max, max_hops=hops, share_cache=use_eso,
            use_epo=use_epo, metric=kform, visited_impl=visited_impl,
            expand_width=expand_width, k_in=k_in, m_max=M_max,
            k_max=K_max)
        g = MultiGraph(ids=new_ids, dist=new_dist)
        tape.log(row)

    tape.drain_into(ctr)          # the main pass's ONE counter host sync

    # ---- connectivity repair (NSG spanning step, simplified) ---------------
    for _ in range(repair_iters):
        g, n_fix, n_dist = _repair_connectivity(g, data, ep, kform)
        ctr.connect += n_dist
        if n_fix == 0:
            break

    return NSGBuildResult(g=g, entry=ep, counters=ctr, params=params,
                          metric=met.name)


def _bfs_python(ids_i, reach, iters):
    """bool[n] BFS reachability via boolean frontier propagation."""
    n = ids_i.shape[0]
    for _ in range(iters):
        nbr = jnp.where(reach[:, None], jnp.maximum(ids_i, 0), n)
        nbr = jnp.where(
            reach[:, None] & (ids_i != INVALID), nbr, n)
        new = jnp.zeros((n,), bool).at[nbr.reshape(-1)].set(True, mode="drop")
        nxt = reach | new
        if bool(jnp.all(nxt == reach)):
            return nxt, False
        reach = nxt
    return reach, True


def _repair_connectivity(g: MultiGraph, data, ep: int, metric: str = "l2"
                         ) -> tuple[MultiGraph, int, int]:
    """Attach each unreachable node to its nearest reachable node."""
    m, n, M_max = g.ids.shape
    new_ids, new_dist = g.ids, g.dist
    total_fix = 0
    n_dist = 0
    for i in range(m):
        reach, _ = _bfs_python(g.ids[i], jnp.zeros((n,), bool).at[ep].set(True), 64)
        unreach = np.asarray(~reach).nonzero()[0]
        total_fix += len(unreach)
        if len(unreach) == 0:
            continue
        # nearest *reachable* node of each unreachable node (brute force on
        # the unreachable set — small in practice).
        q = data[jnp.array(unreach)]
        d2 = ops.pairwise_distance(q, data, metric)       # (u, n)
        d2 = jnp.where(reach[None, :], d2, jnp.inf)
        parent = jnp.argmin(d2, axis=-1).astype(jnp.int32)
        pdist = jnp.min(d2, axis=-1)
        n_dist += len(unreach) * n
        # parent -> unreachable edge: replace parent's worst slot.
        worst = jnp.argmax(new_dist[i][parent], axis=-1)
        new_ids = new_ids.at[i, parent, worst].set(jnp.array(unreach, jnp.int32))
        new_dist = new_dist.at[i, parent, worst].set(pdist)
    return MultiGraph(ids=new_ids, dist=new_dist), total_fix, n_dist


def build_nsg(data, p: NSGParams, **kw) -> NSGBuildResult:
    kw.setdefault("use_eso", False)
    kw.setdefault("use_epo", False)
    return build_multi_nsg(data, [p], **kw)
