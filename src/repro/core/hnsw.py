"""BuildMultiHNSW — Algorithm 5, batched TPU adaptation.

m HNSW graphs with parameters {(efc_i, M_i)} share deterministic level draws
(one PRNG, DESIGN.md §3), so all m graphs have identical layer membership and
the same entry point.  Nodes are inserted in descending-level order in
batches; each batch descends the layer hierarchy with ef=1 searches, then
searches/prunes/commits on every layer it belongs to.  One V_delta per
inserted node is shared across *all* m graphs and *all* layers (Alg. 5 l.7).

Storage: ids int32[n_layers, m, n, M_max] — dense per layer (laptop-scale
simplicity; upper layers hold ~n/M rows).  alpha = 1 everywhere (HNSW).

The schedule of bootstrap batches and layers depends on the data, so it
stays a host loop (DESIGN.md §12): each layer's search + mPrune + commit is
one ``core/build.insert_batch`` dispatch, and the greedy ef=1 descent between
layers is a plain search dispatch.  Counters accumulate on device
(CounterTape) and sync once at the end of the build.
"""
from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp
import numpy as np

from repro.core import build as build_lib
from repro.core import graph, hashset, search
from repro.core import metric as metric_lib
from repro.core.counters import BuildCounters, CounterTape, step_row
from repro.core.graph import INVALID


@dataclasses.dataclass(frozen=True)
class HNSWParams:
    efc: int    # construction pool size
    M: int      # out-degree limit

    def clamped(self, n: int) -> "HNSWParams":
        return HNSWParams(min(self.efc, n - 1), min(self.M, n - 1))


@dataclasses.dataclass
class HNSWGraphs:
    layer_ids: jnp.ndarray    # int32[n_layers, m, n, M_max]
    layer_dist: jnp.ndarray   # float32[n_layers, m, n, M_max]
    levels: np.ndarray        # int32[n] shared deterministic levels
    entry: int                # global entry point (max-level node)
    top: int                  # top layer index


@dataclasses.dataclass
class HNSWBuildResult:
    g: HNSWGraphs
    counters: BuildCounters
    params: list
    metric: str = "l2"          # metric the graphs were built (and rank) under


def _mk_entry(b: int, m: int, ep: int) -> jnp.ndarray:
    return jnp.broadcast_to(jnp.int32(ep), (b, m))


def build_multi_hnsw(
    data,
    params: list[HNSWParams],
    *,
    seed: int = 0,
    batch_size: int = 128,
    use_eso: bool = True,
    use_epo: bool = True,
    k_in: int = 16,
    max_level: int = 4,
    max_hops: int | None = None,
    metric: str = "l2",
    visited_impl: str = "dense",
    expand_width: int = 1,
) -> HNSWBuildResult:
    met = metric_lib.resolve(metric)
    data = met.prepare(data)      # normalize ONCE for cosine (no-op otherwise)
    kform = met.kernel
    n, _ = data.shape
    params = [p.clamped(n) for p in params]
    m = len(params)
    efc = jnp.array([p.efc for p in params], jnp.int32)
    M = jnp.array([p.M for p in params], jnp.int32)
    ones = jnp.ones((m,), jnp.int32)
    alpha1 = jnp.ones((m,), jnp.float32)
    efc_max = graph.bucket(max(p.efc for p in params), 16)
    M_max = graph.bucket(max(p.M for p in params), 8)
    ctr = BuildCounters()
    tape = CounterTape()
    hops = max_hops or search.default_max_hops(efc_max)
    step_kw = dict(ef_max=efc_max, max_hops=hops, share_cache=use_eso,
                   use_epo=use_epo, metric=kform,
                   visited_impl=visited_impl,
                   expand_width=expand_width, k_in=k_in, m_max=M_max)

    # Deterministic shared levels; mL = 1/ln(M_ref) with M_ref = max_i M_i.
    m_l = 1.0 / math.log(max(2, M_max))
    levels = np.asarray(graph.hnsw_levels(seed, n, m_l, max_level))
    top = int(levels.max())
    order = np.lexsort((np.arange(n), -levels))     # descending level
    ep = int(order[0])
    n_layers = top + 1

    lids = jnp.full((n_layers, m, n, M_max), INVALID, jnp.int32)
    ldist = jnp.full((n_layers, m, n, M_max), jnp.inf, jnp.float32)

    # Geometric bootstrap: the first nodes would otherwise search a nearly
    # empty graph and stay isolated (GPU-HNSW-standard warmup schedule).
    offsets, off, step = [], 0, 8
    while off < n:
        offsets.append((off, min(step, batch_size)))
        off += min(step, batch_size)
        step *= 2

    for off, bsz in offsets:
        ids_np = order[off:off + bsz].astype(np.int32)
        b = batch_size  # static shape — bootstrap varies row_mask only
        u = jnp.full((b,), n, jnp.int32).at[:len(ids_np)].set(jnp.array(ids_np))
        row_mask_np = np.arange(b) < len(ids_np)
        lvl_np = np.zeros((b,), np.int32)
        lvl_np[:len(ids_np)] = levels[ids_np]
        queries = data[jnp.minimum(u, n - 1)]
        qids = jnp.where(jnp.array(row_mask_np), u, INVALID)
        entry = _mk_entry(b, m, ep)
        # One V_delta per inserted node across all layers/graphs; the hash
        # table must therefore cover m graphs x n_layers carried searches,
        # or inserts drop (and #dist inflates) before the size cap binds
        # (DESIGN.md §9).
        cache_d, cache_has = search.fresh_cache(
            b, n, use_eso, visited_impl,
            slots=hashset.auto_slots(hops, expand_width * M_max,
                                     searches=m * n_layers,
                                     cap=hashset.CACHE_SLOTS_CAP))

        for layer in range(top, -1, -1):
            desc_np = row_mask_np & (lvl_np < layer)
            ins_np = row_mask_np & (lvl_np >= layer)
            next_entry = entry
            if desc_np.any():   # greedy descent, Alg. 5 l.10-11
                desc_mask = jnp.array(desc_np)
                res = search.beam_search(
                    lids[layer], data, queries, qids, desc_mask,
                    ones, entry, cache_d, cache_has,
                    ef_max=1, max_hops=hops, share_cache=use_eso,
                    metric=kform, visited_impl=visited_impl)
                cache_d, cache_has = res.cache_d, res.cache_has
                tape.log(step_row(res, desc_mask, 1))
                got = res.pool_ids[:, :, 0]
                next_entry = jnp.where(
                    desc_mask[:, None] & (got != INVALID), got, next_entry)
            if ins_np.any():    # search + mPrune + commit, Alg. 5 l.13-19
                ins_mask = jnp.array(ins_np)
                # ONE dispatch for search + mPrune + commit of this layer's
                # inserts (DESIGN.md §12).
                nl, nd, row, got, cache_d, cache_has = build_lib.insert_batch(
                    lids[layer], ldist[layer], data, u, ins_mask, queries,
                    efc, M, alpha1, entry, cache_d, cache_has, **step_kw)
                tape.log(row)
                next_entry = jnp.where(
                    ins_mask[:, None] & (got != INVALID), got, next_entry)
                lids = lids.at[layer].set(nl)
                ldist = ldist.at[layer].set(nd)
            entry = next_entry

    tape.drain_into(ctr)          # the build's ONE counter host sync
    g = HNSWGraphs(layer_ids=lids, layer_dist=ldist, levels=levels,
                   entry=ep, top=top)
    return HNSWBuildResult(g=g, counters=ctr, params=params, metric=met.name)


def build_hnsw(data, p: HNSWParams, **kw) -> HNSWBuildResult:
    kw.setdefault("use_eso", False)
    kw.setdefault("use_epo", False)
    return build_multi_hnsw(data, [p], **kw)


def hnsw_search(g: HNSWGraphs, graph_idx: int, data, queries, k: int, ef: int,
                max_hops: int | None = None, *,
                metric: str = "l2",
                visited_impl: str = "dense",
                expand_width: int = 1) -> search.SearchResult:
    """Layered k-ANNS on one of the m built HNSW graphs.

    ``expand_width`` applies to the base-layer beam search (DESIGN.md §10);
    the upper-layer greedy descent is ef=1 and always single-expansion.
    """
    if k > ef:
        raise ValueError(
            f"k={k} > ef={ef}: slots beyond ef are INVALID padding; raise "
            f"ef to at least k")
    met = metric_lib.resolve(metric)
    data = met.prepare(data)          # once, not per layer
    queries = met.prepare(queries)
    metric = met.kernel
    b = queries.shape[0]
    qids = jnp.full((b,), INVALID, jnp.int32)
    row = jnp.ones((b,), bool)
    entry = _mk_entry(b, 1, g.entry)
    hops = max_hops or search.default_max_hops(ef, expand_width)
    nf = nc = 0
    for layer in range(g.top, 0, -1):
        res = search.beam_search(
            g.layer_ids[layer, graph_idx][None], data, queries, qids, row,
            jnp.ones((1,), jnp.int32), entry,
            ef_max=1, max_hops=hops, share_cache=False, metric=metric,
            visited_impl=visited_impl)
        got = res.pool_ids[:, :, 0]
        entry = jnp.where(got != INVALID, got, entry)
        nf += int(res.n_fresh); nc += int(res.n_computed)
    res = search.beam_search(
        g.layer_ids[0, graph_idx][None], data, queries, qids, row,
        jnp.array([ef], jnp.int32), entry,
        ef_max=ef, max_hops=hops, share_cache=False, metric=metric,
        visited_impl=visited_impl, expand_width=expand_width)
    return search.SearchResult(
        res.pool_ids[:, 0, :k], res.pool_dist[:, 0, :k],
        res.n_fresh + nf, res.n_computed + nc, res.hops,
        res.cache_d, res.cache_has)
