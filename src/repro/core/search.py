"""Batched lockstep beam search — Algorithm 1 (KANNS) and Algorithm 3 (mKANNS).

TPU adaptation of the paper's per-query priority-queue search: a whole batch
of ``b`` queries searches ``m`` graphs simultaneously inside one
``lax.while_loop``.  Pools are fixed-size sorted arrays (``ef_max`` slots);
each hop expands the ``W`` closest unexpanded pool entries per
(query, graph) (``expand_width``, DESIGN.md §10), gathers their
out-neighbors, computes distances through the V_delta-aware kernel and
merges by a sorted-pool ⊕ top-k candidate merge.

Multi-expansion (``expand_width``, DESIGN.md §10): W = 1 is the paper's
sequential best-first schedule — builders and the estimation path pin it so
§2.1 bit-identity and the paper-exact #dist counters hold.  W > 1 expands a
W-wide frontier per hop, cutting the ``while_loop`` trip count ~W× and
amortizing every fixed per-hop cost (pool merge, hash probing, kernel
dispatch) — the serving default (serve/retrieval.py uses W = 4).

ESO (shared V_delta cache): with ``share_cache=True`` a per-query membership
structure is shared by all m graphs — exactly the paper's Alg. 3 cache.  The
*total* number of computed distances equals the size of the union of
(query, neighbor) pairs any graph visits, independent of visit order, so the
lockstep schedule reports the same #dist as the paper's sequential one.

Visited/V_delta representation (``visited_impl``, DESIGN.md §9):
  "dense"  bool[b, m, n] visit bitmap + bool[b, n] V_delta has-bit.  Exact
           membership, exact #dist counters, O(n) memory per query — the
           builder/estimation default (§2.1 bit-identity).
  "hash"   fixed-size open-addressing hash sets (core/hashset.py): int32
           keys, power-of-two slots sized from the hop bound × per-hop
           candidate width (W·Mx), windowed linear probing in-loop.
           O(ef·W·M·hops) memory per query independent of n — the serving
           default.  No false positives; overflow degrades to revisits, so
           hash-mode counters upper-bound dense counters.

Counters (paper metrics):
  n_fresh    — distances each graph would compute alone (no sharing): the
               per-graph Algorithm-1 cost, summed over graphs.
  n_computed — distances actually computed (cache misses). Equal to n_fresh
               when share_cache=False.
  With W > 1 both counters count the W-wide schedule's work, which can
  exceed the sequential schedule's (DESIGN.md §10).

Per-graph pool sizes ``ef_i <= ef_max`` are enforced by slot masks; because
pools are kept globally sorted and entries only move backwards, masking slots
``j >= ef_i`` is equivalent to hard eviction (see tests/test_search.py).

The search is metric-generic (DESIGN.md §4): ``metric`` selects the distance
the pools rank by; builders pass the kernel form ("l2"/"ip") over prepared
data so the loop never normalizes, while external callers may pass "cosine"
and get one in-jit normalization per call.

Corpora beyond one device shard (DESIGN.md §11): ``sharded_knn_search``
runs this same loop per shard of a ``graph.ShardedGraph`` under a
``shard_map`` over the ``"shard"`` mesh axis, restores global ids, and
merges per-shard pools with ``_merge_topk`` — scatter-gather partitioned
search with the single-shard case bit-identical to ``knn_search``.
``routed_shards=p`` (DESIGN.md §13) turns the scatter-gather into a
routed search: each query scores the partition centroids with the same
metric kernels, searches only its top-p shards (``route_topk``), and the
per-shard query blocks are compacted host-side into static bucketed
shapes so every device only searches queries routed to it.
"""
from __future__ import annotations

import functools
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.core import graph as graph_lib
from repro.core import hashset
from repro.core import metric as metric_lib
from repro.core import obs
from repro.core.graph import INVALID
from repro.distributed import sharding as sharding_lib
from repro.kernels import ops

VISITED_IMPLS = ("dense", "hash")


class SearchResult(NamedTuple):
    pool_ids: jax.Array    # int32[b, m, ef_max] ascending by distance
    pool_dist: jax.Array   # float32[b, m, ef_max]
    n_fresh: jax.Array     # int32[] per-graph-alone distance count
    n_computed: jax.Array  # int32[] actually computed (ESO)
    hops: jax.Array        # int32[]
    cache_d: jax.Array     # float32[b, n] V_delta (or [b, 1] dummy)
    cache_has: jax.Array   # bool[b, n] dense | int32[b, S] hash key table
    # int32[] pool slots expanded over the live rows (beam_search only)
    expansions: jax.Array | None = None


def fresh_cache(b: int, n: int, share_cache: bool,
                visited_impl: str = "dense", *, slots: int | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """Empty V_delta — 'initialize V_delta as -1 for each vector' (Alg. 5 l.7).

    Only membership is materialized (see _expand_all_graphs); cache_d is a
    dummy kept for API stability.  In hash mode (DESIGN.md §9) membership
    is an int32[b, slots] open-addressing key table instead of bool[b, n];
    callers carrying the cache across calls size ``slots`` once via
    ``hashset.auto_slots``."""
    dummy = jnp.zeros((b, 1), jnp.float32)
    if share_cache and visited_impl == "hash":
        return (dummy, hashset.make_tables(
            (b,), slots or hashset.CACHE_SLOTS_CAP >> 4))
    w = n if share_cache else 1
    return (dummy, jnp.zeros((b, w), bool))


def _first_occurrence(ids: jax.Array, sentinel: int) -> jax.Array:
    """bool[..., k]: True at the first occurrence of each id (flat order).

    Sort-based: O(k log k) per row, vectorized (the in-hop cross-graph dedup
    that makes the lockstep schedule's V_delta accounting match the paper's
    sequential one — §Perf iteration 4)."""
    k = ids.shape[-1]
    pos = jnp.broadcast_to(jnp.arange(k), ids.shape)
    order = jnp.argsort(ids, axis=-1)
    s_ids = jnp.take_along_axis(ids, order, axis=-1)
    s_pos = jnp.take_along_axis(pos, order, axis=-1)
    first_sorted = jnp.concatenate(
        [jnp.ones_like(s_ids[..., :1], bool),
         s_ids[..., 1:] != s_ids[..., :-1]], axis=-1)
    inv = jnp.argsort(s_pos, axis=-1)
    return jnp.take_along_axis(first_sorted, inv, axis=-1)


def _merge_topk(pool_ids, pool_dist, expanded, cand_ids, cand_dist):
    """Sorted-pool ⊕ top-k candidate merge (§Perf iteration 6).

    The pool is already sorted ascending, so only the candidates need a
    partial sort: ``lax.top_k`` keeps the ``min(kx, ef_max)`` closest
    (ties prefer lower index — the flat candidate order), then a rank-based
    two-way merge places every survivor, materialized with gathers only
    (scatters serialize on CPU and copy on accelerators).  Byte-equivalent
    to the stable full-argsort merge over the (ef_max + kx)-wide
    concatenation it replaces (pool entries win distance ties; verified
    adversarially in tests/test_multi_expand.py), at O(ef·kc) compare work
    instead of O((ef + kx)·log(ef + kx)) sort work per (query, graph).

    Args:
      pool_ids/pool_dist/expanded: (..., ef_max) sorted pools.
      cand_ids/cand_dist: (..., kx) candidates (INVALID/inf where masked).
    Returns the merged (pool_ids, pool_dist, expanded).
    """
    ef_max = pool_ids.shape[-1]
    kx = cand_ids.shape[-1]
    kc = min(kx, ef_max)
    negd, order = jax.lax.top_k(-cand_dist, kc)
    c_dist = -negd
    c_ids = jnp.take_along_axis(cand_ids, order, axis=-1)
    # Each pool entry's merged rank, with the stable tie rule of the old
    # concat-argsort (pool slots preceded candidates in the concatenation,
    # so a pool entry outranks an equal-distance candidate).
    cand_lt = c_dist[..., None, :] < pool_dist[..., :, None]   # (..., ef, kc)
    rank_pool = jnp.arange(ef_max) + jnp.sum(cand_lt, axis=-1)
    # Invert by gathering: output slot r holds pool[i] iff some pool entry
    # has rank r (i = #pool ranks < r, strictly increasing), else candidate
    # j = r - i — the j-th candidate is the only unplaced element left.
    rr = jnp.arange(ef_max)
    i_r = jnp.sum(rank_pool[..., None, :] < rr[:, None], axis=-1)
    i_safe = jnp.minimum(i_r, ef_max - 1)
    is_pool = jnp.take_along_axis(rank_pool, i_safe, axis=-1) == rr
    j_safe = jnp.clip(rr - i_r, 0, kc - 1)
    out_ids = jnp.where(is_pool,
                        jnp.take_along_axis(pool_ids, i_safe, axis=-1),
                        jnp.take_along_axis(c_ids, j_safe, axis=-1))
    out_dist = jnp.where(is_pool,
                         jnp.take_along_axis(pool_dist, i_safe, axis=-1),
                         jnp.take_along_axis(c_dist, j_safe, axis=-1))
    # candidates enter unexpanded
    out_exp = jnp.where(is_pool,
                        jnp.take_along_axis(expanded, i_safe, axis=-1),
                        False)
    return out_ids, out_dist, out_exp


def apply_tombstones(pool_ids, pool_dist, tomb_ids):
    """Mask tombstoned ids out of a sorted pool at merge time (DESIGN.md §15).

    ``tomb_ids`` is int32[..., T], INVALID-padded (the padding can never
    match a pool entry: the equality is guarded on ``pool_ids != INVALID``,
    and live tombstones are real ids >= 0).  Matching slots become
    INVALID/inf and are pushed behind every survivor by a stable argsort on
    the dead flag — survivors keep their relative (ascending-distance,
    bit-pinned tie) order, so the result is exactly the pool a search that
    never saw the deleted nodes would have *ranked*, over the candidates
    this search visited.  Applied to the full ef-wide pool BEFORE any k
    truncation, so the ef − k slack refills the top-k with the next-best
    live candidates (tests/test_streaming.py pins the refill).
    """
    hit = jnp.any(pool_ids[..., :, None] == tomb_ids[..., None, :], axis=-1)
    dead = hit & (pool_ids != INVALID)
    pool_ids = jnp.where(dead, INVALID, pool_ids)
    pool_dist = jnp.where(dead, jnp.inf, pool_dist)
    order = jnp.argsort(dead, axis=-1, stable=True)   # False (live) first
    return (jnp.take_along_axis(pool_ids, order, axis=-1),
            jnp.take_along_axis(pool_dist, order, axis=-1))


def _corpus_len(data) -> int:
    """Corpus row count of either representation the search accepts:
    a bare f32[n, d] array, or a ``metric.QuantizedData`` (DESIGN.md §16)."""
    if isinstance(data, metric_lib.QuantizedData):
        return data.codes.shape[0]
    return data.shape[0]


def _gathered_distance(data, flat_ids, queries, metric):
    """Per-query gathered distances under either corpus representation.

    fp32 gathers the vectors and dispatches the V_delta-aware kernel;
    SQ8 gathers int8 codes + precomputed dequantized norms and dispatches
    the quantized form (ops.gather_distance_q) — same (b, k) f32 output,
    priced against the dequantized corpus (DESIGN.md §16).
    """
    with jax.named_scope(obs.DISTANCE):
        if isinstance(data, metric_lib.QuantizedData):
            ccodes = data.codes[flat_ids]                # (b, k, d) int8
            cn = data.norms[flat_ids]                    # (b, k)
            return ops.gather_distance_q(queries, ccodes, data.scale, cn,
                                         metric=metric)
        cvec = data[flat_ids]                            # (b, k, d)
        return ops.gather_distance(queries, cvec, metric=metric)


def rerank_pool(queries, data, pool_ids, *, metric):
    """Re-rank a quantized-search pool against the fp32 corpus.

    The ef-wide pool a ``QuantizedData`` beam search returns ranks by
    distances to the *dequantized* corpus; before any k truncation the
    surviving candidates are re-priced against the full-precision keys and
    stably re-sorted (DESIGN.md §16).  INVALID slots keep +inf and sink;
    ties keep the quantized-pool order (stable argsort).  Returns
    (pool_ids, pool_dist, n_rerank) where ``n_rerank`` counts the fp32
    distances computed — the quantized path's extra #dist, added to
    ``n_computed`` but not ``n_fresh`` (re-pricing visited nodes computes
    distances without visiting anything new).
    """
    valid = pool_ids != INVALID
    cvec = data[jnp.maximum(pool_ids, 0)]                # (b, ef, d)
    dist = ops.gather_distance(
        queries, cvec, cached=jnp.full(pool_ids.shape, jnp.inf, jnp.float32),
        mask=valid, metric=metric)
    order = jnp.argsort(dist, axis=-1, stable=True)
    return (jnp.take_along_axis(pool_ids, order, axis=-1),
            jnp.take_along_axis(dist, order, axis=-1),
            jnp.sum(valid).astype(jnp.int32))


def _expand_all_graphs(graph_ids, data, queries, query_ids, row_mask,
                       slot_mask, pool_ids, pool_dist, expanded,
                       visited, cache_d, cache_has, share_cache, metric,
                       width):
    """One hop of ALL m graphs, fully vectorized over (b, m, W).

    The ``width`` closest unexpanded pool entries per (query, graph) expand
    together (W = 1 is the sequential best-first schedule); their W·Mx
    candidate neighbors are deduplicated in-row, and cross-graph duplicates
    within the hop are deduplicated by first occurrence in graph order, so
    with W = 1 the computed-distance counter equals the sequential
    schedule's |union| exactly (DESIGN.md §10 for W > 1 semantics).

    ``visited`` is either the dense bool[b, m, n] bitmap or an int32
    [b, m, S] hash-key table (dispatch on dtype; DESIGN.md §9), and
    ``cache_has`` likewise bool[b, n] or int32[b, S'].
    """
    b, m, ef_max = pool_ids.shape
    n = _corpus_len(data)
    mx = graph_ids.shape[2]
    kx = width * mx
    brange = jnp.arange(b)
    mrange = jnp.arange(m)
    hash_visited = visited.dtype != jnp.bool_

    with jax.named_scope(obs.FRONTIER):
        unexp = (pool_ids != INVALID) & (~expanded) & slot_mask[None]
        # W closest unexpanded slots: the pool is sorted ascending, so these
        # are the first W unexpanded slot positions (top_k of the negated
        # position; position ef_max = "no slot" sentinel).
        slot_pos = jnp.where(unexp, jnp.arange(ef_max), ef_max)
        neg_sel, _ = jax.lax.top_k(-slot_pos, width)
        sel = -neg_sel                                           # (b, m, W)
        act = (sel < ef_max) & row_mask[:, None, None]           # (b, m, W)
        sel_safe = jnp.minimum(sel, ef_max - 1)
        u = jnp.take_along_axis(pool_ids, sel_safe, axis=-1)     # (b, m, W)
        u_safe = jnp.where(act, jnp.maximum(u, 0), 0)
        expanded = expanded.at[brange[:, None, None], mrange[None, :, None],
                               jnp.where(act, sel_safe, ef_max)].set(
            True, mode="drop")

        nbrs = graph_ids[mrange[None, :, None], u_safe]      # (b, m, W, Mx)
        nbrs = nbrs.reshape(b, m, kx)
        nbrs_safe = jnp.maximum(nbrs, 0)
        # same-id duplicates within one (query, graph) hop count/insert once
        # (small W·Mx: a triangular compare beats a sort here).  Compared on
        # the raw ids: clamped INVALID lanes would alias node 0 and discard a
        # genuine id-0 candidate arriving after padding.
        eq = nbrs[..., :, None] == nbrs[..., None, :]
        tri = jnp.tril(jnp.ones((kx, kx), bool), k=-1)
        dup = jnp.any(eq & tri[None, None], axis=-1)
        act_flat = jnp.repeat(act, mx, axis=-1)                  # (b, m, kx)
        prelim = ((nbrs != INVALID) & act_flat
                  & (nbrs != query_ids[:, None, None]) & ~dup)
    with jax.named_scope(obs.VISIT):
        if hash_visited:
            visited, vis, _ = hashset.lookup_insert(visited, nbrs_safe, prelim)
            # Overflow guard: a dropped insert can re-propose a node that is
            # already pooled; dense mode can't (pool membership implies a set
            # visit bit), so only hash mode pays this compare (DESIGN.md §9).
            in_pool = jnp.any(
                nbrs_safe[..., :, None] == pool_ids[..., None, :], axis=-1)
            valid = prelim & ~vis & ~in_pool
        else:
            vis = visited[brange[:, None, None], mrange[None, :, None],
                          nbrs_safe]
            valid = prelim & ~vis

        flat_ids = nbrs_safe.reshape(b, m * kx)
        flat_valid = valid.reshape(b, m * kx)
        if share_cache and m > 1:
            first = _first_occurrence(
                jnp.where(flat_valid, flat_ids,
                          n + jnp.arange(m * kx)[None, :]),
                n)                                                # (b, m*kx)
            first = first & flat_valid
        else:
            first = flat_valid

    dists = _gathered_distance(data, flat_ids, queries, metric)
    with jax.named_scope(obs.VISIT):
        if share_cache:
            # V_delta's domain is exactly the union of per-graph visit sets,
            # so only membership is tracked; the values come from the batched
            # kernel either way (lockstep hardware computes the tile
            # regardless — DESIGN.md §3, §Perf iteration 5). #dist counters
            # stay exact in dense mode; hash mode upper-bounds them under
            # overflow (§9).
            if cache_has.dtype != jnp.bool_:
                # first-occurrence lanes only: keys distinct within each row.
                cache_has, c_found, _ = hashset.lookup_insert(
                    cache_has, flat_ids, first)
                n_comp = jnp.sum(first & ~c_found).astype(jnp.int32)
            else:
                has = cache_has[brange[:, None], flat_ids]
                need = flat_valid & ~has
                scat = jnp.where(need, flat_ids, n)
                cache_has = cache_has.at[brange[:, None], scat].set(
                    True, mode="drop")
                n_comp = jnp.sum(need & first).astype(jnp.int32)
        else:
            n_comp = jnp.sum(flat_valid).astype(jnp.int32)
        n_fresh = jnp.sum(flat_valid).astype(jnp.int32)

        if not hash_visited:
            scat_v = jnp.where(flat_valid, flat_ids, n).reshape(b, m, kx)
            visited = visited.at[brange[:, None, None],
                                 mrange[None, :, None],
                                 scat_v].set(True, mode="drop")

    with jax.named_scope(obs.MERGE):
        dists3 = dists.reshape(b, m, kx)
        cand_ids = jnp.where(valid, nbrs, INVALID)
        cand_dist = jnp.where(valid, dists3, jnp.inf)
        pool_ids, pool_dist, expanded = _merge_topk(
            pool_ids, pool_dist, expanded, cand_ids, cand_dist)
    return (pool_ids, pool_dist, expanded, visited, cache_d, cache_has,
            n_fresh, n_comp)


@functools.partial(
    jax.jit,
    static_argnames=("ef_max", "max_hops", "share_cache", "metric",
                     "visited_impl", "hash_slots", "expand_width"))
def beam_search(
    graph_ids: jax.Array,      # int32[m, n, Mx]
    data: jax.Array,           # f32[n, d]
    queries: jax.Array,        # f32[b, d]
    query_ids: jax.Array,      # int32[b]; -1 for external queries
    row_mask: jax.Array,       # bool[b]; False = padding row
    ef: jax.Array,             # int32[m] per-graph pool size
    entry: jax.Array,          # int32[b, m] entry points
    cache_d: jax.Array | None = None,    # carried V_delta (ESO across calls)
    cache_has: jax.Array | None = None,
    *,
    ef_max: int,
    max_hops: int,
    share_cache: bool,
    metric: str = "l2",
    visited_impl: str = "dense",
    hash_slots: int | None = None,
    expand_width: int = 1,
) -> SearchResult:
    if visited_impl not in VISITED_IMPLS:
        raise ValueError(
            f"visited_impl {visited_impl!r} not in {VISITED_IMPLS}")
    if expand_width < 1:
        raise ValueError(f"expand_width must be >= 1, got {expand_width}")
    width = min(expand_width, ef_max)      # cannot expand more than the pool
    met = metric_lib.resolve(metric)
    if met.normalize:
        # One in-jit normalization per call; builders avoid even this by
        # preparing the dataset once and passing the kernel form ("ip").
        # A QuantizedData corpus was normalized BEFORE quantization
        # (Metric.prepare_quantized) — only the queries normalize here.
        if not isinstance(data, metric_lib.QuantizedData):
            data = metric_lib.normalize(data)
        queries = metric_lib.normalize(queries)
    metric = met.kernel
    m, n, mx = graph_ids.shape
    b = queries.shape[0]
    brange = jnp.arange(b)
    slot_mask = jnp.arange(ef_max)[None, :] < ef[:, None]        # (m, ef_max)

    # ---- init: pool[0] = (ep, delta(q, ep)), Alg. 1 line 2 ----------------
    pool_ids = jnp.full((b, m, ef_max), INVALID, jnp.int32)
    pool_dist = jnp.full((b, m, ef_max), jnp.inf, jnp.float32)
    expanded = jnp.zeros((b, m, ef_max), bool)
    if visited_impl == "hash":
        slots = hash_slots or hashset.auto_slots(max_hops, width * mx)
        visited = hashset.make_tables((b, m), slots)
    else:
        visited = jnp.zeros((b, m, n), bool)
    if cache_d is None:
        # The V_delta union absorbs all m graphs' inserts, so a caller-
        # supplied per-(query, graph) hash_slots is scaled by m here.
        cache_slots = (
            min(hashset.next_pow2(m * hash_slots), hashset.CACHE_SLOTS_CAP)
            if hash_slots else
            hashset.auto_slots(max_hops, width * mx, searches=m,
                               cap=hashset.CACHE_SLOTS_CAP))
        cache_d, cache_has = fresh_cache(b, n, share_cache, visited_impl,
                                         slots=cache_slots)
    n_fresh = jnp.int32(0)
    n_comp = jnp.int32(0)

    for i in range(m):
        ep = entry[:, i]
        ep_safe = jnp.maximum(ep, 0)
        # a build row whose node IS the entry (the medoid's own insertion)
        # seeds its pool with itself too, so the search expands the
        # entry's neighbourhood; the id is dropped from the final pool
        ok = (ep != INVALID) & row_mask
        d0 = _gathered_distance(data, ep_safe[:, None], queries,
                                metric)[:, 0]
        if share_cache:
            if cache_has.dtype != jnp.bool_:
                cache_has, c_found, _ = hashset.lookup_insert(
                    cache_has, ep_safe[:, None], ok[:, None])
                need = ok & ~c_found[:, 0]
            else:
                has = cache_has[brange, ep_safe]
                need = ok & ~has
                scat = jnp.where(need, ep_safe, n)
                cache_has = cache_has.at[brange, scat].set(True, mode="drop")
            n_comp += jnp.sum(need).astype(jnp.int32)
        else:
            n_comp += jnp.sum(ok).astype(jnp.int32)
        n_fresh += jnp.sum(ok).astype(jnp.int32)
        pool_ids = pool_ids.at[:, i, 0].set(jnp.where(ok, ep, INVALID))
        pool_dist = pool_dist.at[:, i, 0].set(jnp.where(ok, d0, jnp.inf))
        if visited_impl == "hash":
            vtab, _, _ = hashset.lookup_insert(
                visited[:, i], ep_safe[:, None], ok[:, None])
            visited = visited.at[:, i].set(vtab)
        else:
            visited = visited.at[brange, i, jnp.where(ok, ep_safe, 0)].set(
                visited[brange, i, jnp.where(ok, ep_safe, 0)] | ok)

    state = (pool_ids, pool_dist, expanded, visited, cache_d, cache_has,
             n_fresh, n_comp, jnp.int32(0))

    def cond(state):
        pool_ids, _, expanded, *_, hop = state
        unexp = (pool_ids != INVALID) & ~expanded & slot_mask[None]
        return (hop < max_hops) & jnp.any(unexp & row_mask[:, None, None])

    def body(state):
        (pool_ids, pool_dist, expanded, visited, cache_d, cache_has,
         n_fresh, n_comp, hop) = state
        (pool_ids, pool_dist, expanded, visited, cache_d, cache_has,
         nf, nc) = _expand_all_graphs(
            graph_ids, data, queries, query_ids, row_mask, slot_mask,
            pool_ids, pool_dist, expanded, visited, cache_d, cache_has,
            share_cache, metric, width)
        return (pool_ids, pool_dist, expanded, visited, cache_d, cache_has,
                n_fresh + nf, n_comp + nc, hop + 1)

    # the loop's own control (the while op, its condition, the hop and
    # counter carries) is frontier work; the body's stages name themselves
    with jax.named_scope(obs.FRONTIER):
        state = jax.lax.while_loop(cond, body, state)
    (pool_ids, pool_dist, expanded, _, cache_d, cache_has,
     n_fresh, n_comp, hops) = state
    # expanded slots still pooled: one reduction per call, not per hop (an
    # entry pushed past ef_max by ef_max closer candidates is not counted)
    expansions = jnp.sum(expanded & row_mask[:, None, None]).astype(jnp.int32)
    # Mask out slots beyond each graph's ef (they are not part of C(u)).
    pool_ids = jnp.where(slot_mask[None], pool_ids, INVALID)
    pool_dist = jnp.where(slot_mask[None], pool_dist, jnp.inf)
    # C(u) excludes u: drop the query's own id (only a self-entry seed can
    # have pooled it) and shift the slots after it up by one
    is_self = ((pool_ids == query_ids[:, None, None])
               & (query_ids != INVALID)[:, None, None])
    at = jnp.where(jnp.any(is_self, axis=-1), jnp.argmax(is_self, axis=-1),
                   ef_max)[..., None]
    src = jnp.where(jnp.arange(ef_max) < at, jnp.arange(ef_max),
                    jnp.arange(1, ef_max + 1))
    pool_ids = jnp.take_along_axis(
        jnp.pad(pool_ids, ((0, 0), (0, 0), (0, 1)),
                constant_values=INVALID), src, axis=-1)
    pool_dist = jnp.take_along_axis(
        jnp.pad(pool_dist, ((0, 0), (0, 0), (0, 1)),
                constant_values=jnp.inf), src, axis=-1)
    return SearchResult(pool_ids, pool_dist, n_fresh, n_comp, hops,
                        cache_d, cache_has, expansions)


def default_max_hops(ef_max: int, expand_width: int = 1) -> int:
    """Generous hop bound: best-first search converges in ~ef expansions,
    and a width-W hop performs W of them — the bound (and with it the
    auto-sized hash tables, which scale as hops × W·Mx) shrinks ~W×.
    Invalid widths are left to ``beam_search``'s validation."""
    return 3 * -(-ef_max // max(1, expand_width)) + 16


def knn_search(graph_ids: jax.Array, data: jax.Array, queries: jax.Array,
               k: int, ef: int, entry: int | jax.Array,
               max_hops: int | None = None, *,
               metric: str = "l2",
               visited_impl: str = "dense",
               hash_slots: int | None = None,
               expand_width: int = 1,
               row_mask: jax.Array | None = None,
               tombstone_ids: jax.Array | None = None,
               quantize: str = "none",
               quant: metric_lib.QuantizedData | None = None) -> SearchResult:
    """Single-graph external k-ANNS (evaluation path, Alg. 1).

    ``metric`` must match the metric the graph was built under; pool
    distances come back in that metric's units (core/metric.py convention).
    ``visited_impl="hash"`` swaps the dense visit bitmap for the O(ef)
    hash-set state (DESIGN.md §9) — the serving default via
    serve/retrieval.py.  ``expand_width`` expands that many frontier nodes
    per hop (DESIGN.md §10); 1 reproduces the paper's sequential schedule,
    serving uses 4.  ``row_mask`` marks padding rows that must do no
    search work (static-shape batching; their pools come back INVALID).
    ``tombstone_ids`` (int32[T], INVALID-padded) masks deleted nodes out of
    the ef-wide pool before the k truncation (``apply_tombstones``,
    DESIGN.md §15); ``None`` dispatches the exact program of before the
    parameter existed.

    ``quantize="sq8"`` (DESIGN.md §16) beam-searches the int8 ``quant``
    corpus (a ``metric.QuantizedData`` over the same prepared vectors as
    ``data`` — ``Metric.prepare_quantized``) and re-ranks the final
    ef-wide pool against the fp32 ``data`` before the k truncation
    (``rerank_pool``); the re-rank's fp32 distances add to ``n_computed``
    while ``n_fresh`` keeps its paper-exact beam accounting.  The default
    ``"none"`` dispatches the exact fp32 program of before the knob
    existed.
    """
    if quantize not in metric_lib.QUANTIZE_MODES:
        raise ValueError(
            f"quantize {quantize!r} not in {metric_lib.QUANTIZE_MODES}")
    if quantize == "sq8" and quant is None:
        raise ValueError(
            "quantize='sq8' needs the quantized corpus: pass "
            "quant=metric.QuantizedData (Metric.prepare_quantized over the "
            "same vectors as data; retrieval.build_index(quantize='sq8') "
            "stores one on the index)")
    if k > ef:
        raise ValueError(
            f"k={k} > ef={ef}: the search pool holds only ef candidates, so "
            f"slots beyond ef would be INVALID padding, silently returning "
            f"fewer than k real neighbors; raise ef to at least k")
    if graph_ids.ndim == 2:
        graph_ids = graph_ids[None]
    if tombstone_ids is not None:
        tombstone_ids = jnp.asarray(tombstone_ids, jnp.int32)
        if tombstone_ids.ndim != 1:
            raise ValueError(
                f"tombstone_ids must be a 1-D id array, got shape "
                f"{tombstone_ids.shape}")
        if tombstone_ids.shape[0] == 0:
            tombstone_ids = None       # empty: skip the mask entirely
    b = queries.shape[0]
    ep = jnp.broadcast_to(jnp.asarray(entry, jnp.int32), (b,))[:, None]
    res = beam_search(
        graph_ids, quant if quantize == "sq8" else data, queries,
        jnp.full((b,), INVALID, jnp.int32),
        jnp.ones((b,), bool) if row_mask is None else row_mask,
        jnp.array([ef], jnp.int32), ep,
        ef_max=ef, max_hops=max_hops or default_max_hops(ef, expand_width),
        share_cache=False, metric=metric, visited_impl=visited_impl,
        hash_slots=hash_slots, expand_width=expand_width)
    pool_i, pool_d = res.pool_ids[:, 0], res.pool_dist[:, 0]
    n_comp = res.n_computed
    if quantize == "sq8":
        pool_i, pool_d, n_rr = rerank_pool(queries, data, pool_i,
                                           metric=metric)
        n_comp = n_comp + n_rr
    if tombstone_ids is not None:
        pool_i, pool_d = apply_tombstones(pool_i, pool_d, tombstone_ids)
    return SearchResult(pool_i[:, :k], pool_d[:, :k],
                        res.n_fresh, n_comp, res.hops,
                        res.cache_d, res.cache_has, res.expansions)


# ---------------------------------------------------------------------------
# Mesh-partitioned scatter-gather search (DESIGN.md §11).
# ---------------------------------------------------------------------------

def _shard_search_body(graph_ids, data, global_ids, entries, shard_mask,
                       queries, row_mask, *quant, ef, max_hops, metric,
                       visited_impl, hash_slots, expand_width):
    """Search every shard of one mesh slot's block; merge its pools locally.

    Runs inside ``shard_map``: arguments carry this slot's ``s_loc``
    contiguous shards.  Each shard runs the *unchanged* lockstep beam
    search (W, metric, dense/hash visited state all preserved) on its
    local-id subgraph with full pool size ``ef`` — scatter-gather explores
    each partition as deeply as the unsharded search explores the whole
    corpus, which is where the recall of the merged result comes from.
    Pool ids are restored to global ids *before* any merge (a local id is
    meaningless outside its shard), then folded left-to-right in shard
    order through the rank merge; counters psum over the mesh so every
    slot returns the global totals.

    ``*quant`` (DESIGN.md §16), when present, is this slot's
    ``(qcodes, qscale, qnorms)`` SQ8 block: each shard then beam-searches
    its int8 codes and re-ranks its local ef-pool against its fp32
    ``data[s]`` *before* the global-id restore and the fold, so every
    distance that crosses a merge is an fp32 distance and the folded pool
    stays rank-merge-sorted.  The re-rank counts add to ``n_comp``.

    ``shard_mask`` (bool[s_loc], DESIGN.md §14) is this slot's view of the
    shard liveness mask: a dead shard searches with an all-False row mask,
    which is beam_search's zero-work state — its pool comes back all
    INVALID/inf (rank-merging it is a no-op), its counters are 0 (so the
    psum'd totals count live shards only), and its hop count is 0 (so
    pmax reflects the slowest *live* shard).
    """
    s_loc = graph_ids.shape[0]
    b = queries.shape[0]
    qids = jnp.full((b,), INVALID, jnp.int32)
    pool_i = pool_d = None
    n_fresh = n_comp = hops = jnp.int32(0)
    for s in range(s_loc):
        sdata = (metric_lib.QuantizedData(quant[0][s], quant[1][s],
                                          quant[2][s])
                 if quant else data[s])
        ep = jnp.broadcast_to(entries[s].astype(jnp.int32), (b,))[:, None]
        res = beam_search(
            graph_ids[s][None], sdata, queries, qids,
            row_mask & shard_mask[s],
            jnp.array([ef], jnp.int32), ep,
            ef_max=ef, max_hops=max_hops, share_cache=False, metric=metric,
            visited_impl=visited_impl, hash_slots=hash_slots,
            expand_width=expand_width)
        lids = res.pool_ids[:, 0]                              # (b, ef) local
        dist = res.pool_dist[:, 0]
        if quant:
            lids, dist, n_rr = rerank_pool(queries, data[s], lids,
                                           metric=metric)
            n_comp += n_rr
        gids = jnp.where(lids == INVALID, INVALID,
                         global_ids[s][jnp.maximum(lids, 0)])
        if pool_i is None:
            pool_i, pool_d = gids, dist
        else:
            pool_i, pool_d, _ = _merge_topk(
                pool_i, pool_d, jnp.zeros_like(pool_i, bool), gids, dist)
        n_fresh += res.n_fresh
        n_comp += res.n_computed
        hops = jnp.maximum(hops, res.hops)
    n_fresh = jax.lax.psum(n_fresh, "shard")
    n_comp = jax.lax.psum(n_comp, "shard")
    hops = jax.lax.pmax(hops, "shard")
    return pool_i[None], pool_d[None], n_fresh, n_comp, hops


@functools.lru_cache(maxsize=None)
def _sharded_search_fn(mesh, *, k, ef, max_hops, metric, visited_impl,
                       hash_slots, expand_width, tombstones=False,
                       quantize=False):
    """jit'd mesh-partitioned search, cached per (mesh, static knobs).

    ``tombstones=True`` compiles a variant taking one extra trailing
    ``tomb_ids`` argument, masked into the folded ef-wide pool before the
    k truncation (``apply_tombstones``, DESIGN.md §15).  ``quantize=True``
    compiles the SQ8 variant (DESIGN.md §16): three shard-sharded trailing
    arguments ``(qcodes, qscale, qnorms)`` *before* any ``tomb_ids``.  The
    all-False variant is byte-for-byte the program of before the flags
    existed — the healthy fp32 no-delete serving path stays the
    bit-identical cached program.
    """
    body = functools.partial(
        _shard_search_body, ef=ef, max_hops=max_hops, metric=metric,
        visited_impl=visited_impl, hash_slots=hash_slots,
        expand_width=expand_width)
    n_quant = 3 if quantize else 0
    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("shard"), P("shard"), P("shard"), P("shard"),
                  P("shard"), P(), P()) + (P("shard"),) * n_quant,
        out_specs=(P("shard"), P("shard"), P(), P(), P()),
        check_vma=False)

    @jax.jit
    def run(graph_ids, data, global_ids, entries, shard_mask, queries,
            row_mask, *extra):
        blocks_i, blocks_d, n_fresh, n_comp, hops = sharded(
            graph_ids, data, global_ids, entries, shard_mask, queries,
            row_mask, *extra[:n_quant])
        tomb = extra[n_quant:]
        # Fold the per-slot pools in slot order: slots hold contiguous
        # shard blocks, and each block was itself folded in shard order, so
        # the tie precedence is globally (shard, pool rank) — identical to
        # a serial fold over shards 0..S-1 (tests/test_sharded_search.py).
        pool_i, pool_d = blocks_i[0], blocks_d[0]
        for g in range(1, blocks_i.shape[0]):
            pool_i, pool_d, _ = _merge_topk(
                pool_i, pool_d, jnp.zeros_like(pool_i, bool),
                blocks_i[g], blocks_d[g])
        if tombstones:
            pool_i, pool_d = apply_tombstones(pool_i, pool_d, tomb[0])
        return pool_i[:, :k], pool_d[:, :k], n_fresh, n_comp, hops
    return run


# ---------------------------------------------------------------------------
# Query-routed sharded search (DESIGN.md §13).
# ---------------------------------------------------------------------------

# Per-shard query blocks pad up to a multiple of this (graph.bucket) so the
# set of compiled routed-search shapes stays small across batches.  Tighter
# than the serving block multiple of 16: routed per-shard counts are b·p/S
# in expectation, and padding rows still pay full lockstep search work.
ROUTED_BLOCK_MULT = 4


def route_topk(scores: jax.Array, p: int) -> jax.Array:
    """Top-p shard selection from centroid distances (smaller = closer).

    ``scores`` is float[b, S]; returns int32[b, p] shard ids.  Stable
    argsort fixes the tie rule — equal-distance centroids route to the
    LOWER shard id — and the selected ids come back sorted ascending so
    the pool fold visits shards in the same serial order the
    scatter-gather fold uses (tie precedence stays (shard, pool rank)).
    Module-level on purpose: the routing oracle's mutation test swaps it
    (tests/test_oracle.py) the way PR 5's swapped ``_merge_topk``.
    """
    order = jnp.argsort(scores, axis=-1)            # jnp.argsort is stable
    return jnp.sort(order[..., :p].astype(jnp.int32), axis=-1)


def _routed_search_body(graph_ids, data, global_ids, entries, qblocks,
                       qmask, *quant, ef, max_hops, metric, visited_impl,
                       hash_slots, expand_width):
    """Search one mesh slot's shards over their own routed query blocks.

    Runs inside ``shard_map``: this slot's ``s_loc`` shards each receive a
    compacted (Bq, d) block holding ONLY the queries routed to them
    (padding rows masked by ``qmask`` do no search work — beam_search's
    row_mask semantics).  Same unchanged lockstep search per shard as the
    scatter-gather body, same global-id restore before anything leaves the
    shard; but no local fold — each (shard, slot) pool is returned intact
    because a query's p pools live at different slots and merge outside
    the shard_map.  Counters psum over the mesh: since un-routed
    (query, shard) pairs never enter any block, the totals count routed
    work only (DESIGN.md §13).

    ``*quant`` as in ``_shard_search_body`` (DESIGN.md §16): beam over the
    slot's int8 codes, per-shard fp32 re-rank before the global-id
    restore (the fold happens outside the shard_map, so the pools that
    leave this body must already carry fp32 distances).
    """
    s_loc = graph_ids.shape[0]
    bq = qblocks.shape[1]
    qids = jnp.full((bq,), INVALID, jnp.int32)
    outs_i, outs_d = [], []
    n_fresh = n_comp = hops = jnp.int32(0)
    for s in range(s_loc):
        sdata = (metric_lib.QuantizedData(quant[0][s], quant[1][s],
                                          quant[2][s])
                 if quant else data[s])
        ep = jnp.broadcast_to(entries[s].astype(jnp.int32), (bq,))[:, None]
        res = beam_search(
            graph_ids[s][None], sdata, qblocks[s], qids, qmask[s],
            jnp.array([ef], jnp.int32), ep,
            ef_max=ef, max_hops=max_hops, share_cache=False, metric=metric,
            visited_impl=visited_impl, hash_slots=hash_slots,
            expand_width=expand_width)
        lids = res.pool_ids[:, 0]                             # (Bq, ef) local
        dist = res.pool_dist[:, 0]
        if quant:
            lids, dist, n_rr = rerank_pool(qblocks[s], data[s], lids,
                                           metric=metric)
            n_comp += n_rr
        outs_i.append(jnp.where(lids == INVALID, INVALID,
                                global_ids[s][jnp.maximum(lids, 0)]))
        outs_d.append(dist)
        n_fresh += res.n_fresh
        n_comp += res.n_computed
        hops = jnp.maximum(hops, res.hops)
    n_fresh = jax.lax.psum(n_fresh, "shard")
    n_comp = jax.lax.psum(n_comp, "shard")
    hops = jax.lax.pmax(hops, "shard")
    return jnp.stack(outs_i), jnp.stack(outs_d), n_fresh, n_comp, hops


@functools.lru_cache(maxsize=None)
def _routed_search_fn(mesh, *, k, ef, max_hops, metric, visited_impl,
                      hash_slots, expand_width, p, tombstones=False,
                      quantize=False):
    """jit'd routed mesh search, cached per (mesh, static knobs, p).

    ``tombstones`` / ``quantize`` as in ``_sharded_search_fn``: True adds
    a trailing ``tomb_ids`` argument masked into the per-query fold before
    truncation; ``quantize=True`` adds the three shard-sharded SQ8
    arguments ``(qcodes, qscale, qnorms)`` before any ``tomb_ids``.
    """
    body = functools.partial(
        _routed_search_body, ef=ef, max_hops=max_hops, metric=metric,
        visited_impl=visited_impl, hash_slots=hash_slots,
        expand_width=expand_width)
    n_quant = 3 if quantize else 0
    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("shard"),) * (6 + n_quant),
        out_specs=(P("shard"), P("shard"), P(), P(), P()),
        check_vma=False)

    @jax.jit
    def run(graph_ids, data, global_ids, entries, queries, q_index, q_mask,
            routed, slot_of, row_mask, *extra):
        qblocks = queries[q_index]                             # (S, Bq, d)
        blocks_i, blocks_d, n_fresh, n_comp, hops = sharded(
            graph_ids, data, global_ids, entries, qblocks, q_mask,
            *extra[:n_quant])
        tomb = extra[n_quant:]
        # Per-query fold over its p pools: query b's j-th routed shard
        # searched it at (routed[b,j], slot_of[b,j]).  routed rows are
        # sorted ascending, so the fold runs in ascending shard order —
        # the serial tie precedence of the scatter-gather fold.
        pool_i = blocks_i[routed[:, 0], slot_of[:, 0]]         # (b, ef)
        pool_d = blocks_d[routed[:, 0], slot_of[:, 0]]
        for j in range(1, p):
            pool_i, pool_d, _ = _merge_topk(
                pool_i, pool_d, jnp.zeros_like(pool_i, bool),
                blocks_i[routed[:, j], slot_of[:, j]],
                blocks_d[routed[:, j], slot_of[:, j]])
        if tombstones:
            pool_i, pool_d = apply_tombstones(pool_i, pool_d, tomb[0])
        pool_i = jnp.where(row_mask[:, None], pool_i[:, :k], INVALID)
        pool_d = jnp.where(row_mask[:, None], pool_d[:, :k], jnp.inf)
        return pool_i, pool_d, n_fresh, n_comp, hops
    return run


@functools.lru_cache(maxsize=None)
def _fused_routed_search_fn(*, k, ef, max_hops, metric, visited_impl,
                            hash_slots, expand_width, p, tombstones=False,
                            quantize=False):
    """jit'd single-dispatch routed search over the stacked-flat graph.

    The packed execution strategy (DESIGN.md §13): when a mesh slot holds
    more than one shard (always true on a single device), the shard_map
    body's per-shard ``data[s]`` / ``graph_ids[s]`` slices materialize
    O(n/S) copies per shard per call — measured at ~4× the search itself
    at n=1M — and the slot's shards run as serial while loops.  This
    program instead searches ``ShardedGraph.flat_ids``, the precomputed
    block-diagonal adjacency over the concatenated shard rows: every
    routed (query, shard) pair becomes one row of a single ``beam_search``
    whose entry point is that shard's entry in flat id space.  Rows cannot
    escape their shard (the flat graph has no cross-shard edges), so each
    row is bit-identical to the same row of the per-shard search under
    dense visited state, and identical under hash state while the
    auto-sized tables don't overflow (flat vs local ids hash to different
    slots, so overflow — which upper-bounds counters either way, DESIGN.md
    §9 — is the one divergence point).  No per-shard query blocks and no
    padding rows: the row batch is exactly b·p.  Counter semantics match
    the mesh path: one search's totals equal the psum over shards, and its
    hop count is the max over rows = pmax over shards.

    Routing itself (centroid scoring + ``route_topk``) runs inside the jit
    — the mesh path must route on the host to build per-shard blocks, but
    here the routed pairs feed straight into the row batch, so the device
    round-trip would be pure latency.  Same ops, same backend, so both
    paths pick identical shards.  (Consequence: a monkeypatched
    ``route_topk`` only affects this path's freshly-compiled entries — the
    oracle's mutation test targets the host-routed mesh path.)

    ``quantize=True`` (DESIGN.md §16): three trailing SQ8 arguments
    ``(qcodes (S,n_s,d) int8, qscale (S,d) replicated global scale,
    qnorms (S,n_s))`` before any ``tomb_ids``; the b·p-row beam runs over
    the flattened codes and every row's ef-pool re-ranks against the fp32
    flat data before the per-query fold, so folded distances are fp32.
    """
    met = metric_lib.resolve(metric)
    n_quant = 3 if quantize else 0

    @jax.jit
    def run(flat_ids, data, global_ids, entries, centroids, shard_mask,
            queries, row_mask, *extra):
        quant, tomb = extra[:n_quant], extra[n_quant:]
        b = queries.shape[0]
        n_s, d = data.shape[1], data.shape[2]
        flat_data = data.reshape(-1, d)                # contiguous: no copy
        flat_gids = global_ids.reshape(-1)
        beam_data = (metric_lib.QuantizedData(
            quant[0].reshape(-1, d), quant[1][0], quant[2].reshape(-1))
            if quantize else flat_data)
        qprep = met.prepare(queries)
        scores = metric_lib.kernel_distance(
            qprep[:, None, :], centroids[None, :, :], met.kernel)
        # Dead shards score +inf, so route_topk never selects one while
        # p <= live count (the caller clamps; DESIGN.md §14).  All-True
        # masks leave scores bit-unchanged — the healthy path stays
        # identical to the unmasked program.
        scores = jnp.where(shard_mask[None, :], scores, jnp.inf)
        routed = route_topk(scores, p)                 # (b, p) ascending
        p_ = routed.shape[1]
        # row r = (query r // p, routed shard r % p), ascending shard order
        # within each query (route_topk sorts), so the pool fold below
        # keeps the serial (shard, pool rank) tie precedence.
        qrows = jnp.repeat(queries, p_, axis=0)                  # (b*p, d)
        ep = (entries[routed] + routed * n_s).reshape(-1)        # flat ids
        rmask = jnp.repeat(row_mask, p_, axis=0)
        res = beam_search(
            flat_ids[None], beam_data, qrows,
            jnp.full((b * p_,), INVALID, jnp.int32), rmask,
            jnp.array([ef], jnp.int32), ep[:, None],
            ef_max=ef, max_hops=max_hops, share_cache=False, metric=metric,
            visited_impl=visited_impl, hash_slots=hash_slots,
            expand_width=expand_width)
        lids = res.pool_ids[:, 0]                           # (b*p, ef) flat
        dist = res.pool_dist[:, 0]
        n_comp = res.n_computed
        if quantize:
            lids, dist, n_rr = rerank_pool(qrows, flat_data, lids,
                                           metric=metric)
            n_comp = n_comp + n_rr
        gpool = jnp.where(lids == INVALID, INVALID,
                          flat_gids[jnp.maximum(lids, 0)]).reshape(b, p_, -1)
        dpool = dist.reshape(b, p_, -1)
        pool_i, pool_d = gpool[:, 0], dpool[:, 0]
        for j in range(1, p):
            pool_i, pool_d, _ = _merge_topk(
                pool_i, pool_d, jnp.zeros_like(pool_i, bool),
                gpool[:, j], dpool[:, j])
        if tombstones:
            pool_i, pool_d = apply_tombstones(pool_i, pool_d, tomb[0])
        pool_i = jnp.where(row_mask[:, None], pool_i[:, :k], INVALID)
        pool_d = jnp.where(row_mask[:, None], pool_d[:, :k], jnp.inf)
        return pool_i, pool_d, res.n_fresh, n_comp, res.hops
    return run


# Warn-once state for the routed_shards > live-shards clamp: holds the
# (num_shards, n_live, p) tuple of the last ShardHealth state that warned.
# A degraded serving loop calls sharded_knn_search per batch — warning on
# every call floods logs with thousands of identical lines — so the clamp
# warns once per state *transition*: repeat calls under the same degraded
# state stay silent, and any unclamped routed call resets the state so the
# next degradation warns again.
_CLAMP_WARNED_STATE: "tuple[int, int, int] | None" = None


def sharded_knn_search(sharded_graph, queries: jax.Array, k: int, ef: int,
                       *, metric: str = "l2", visited_impl: str = "dense",
                       hash_slots: int | None = None, expand_width: int = 1,
                       max_hops: int | None = None,
                       row_mask: jax.Array | None = None,
                       routed_shards: int | None = None,
                       shard_mask=None,
                       tombstone_ids: jax.Array | None = None,
                       quantize: str = "none",
                       mesh=None) -> SearchResult:
    """Scatter-gather k-ANNS over a mesh-partitioned corpus (DESIGN.md §11).

    Each shard of ``sharded_graph`` (graph.partition) searches its own
    subgraph with the full ``ef`` pool via the unchanged ``beam_search``
    (so ``metric`` / ``visited_impl`` / ``expand_width`` mean exactly what
    they mean unsharded); per-shard pools come back in shard-local ids,
    are restored to global ids, and merge through the same rank merge the
    in-loop pool update uses (``_merge_topk`` — earlier shards win
    distance ties, matching a serial fold).  Counter semantics: ``n_fresh``
    / ``n_computed`` are psum-reduced totals over all shards (the cost of
    the scatter-gather schedule: every shard pays its own search);
    ``hops`` is the max over shards (shards run in parallel, so the
    slowest shard bounds latency).

    With ``num_shards == 1`` the decomposition is trivial and the result
    is bit-identical to ``knn_search`` from the same entry point (pinned
    by test); the default mesh places num_shards / n_devices shards per
    device (distributed.sharding.search_mesh).

    ``routed_shards=p`` (DESIGN.md §13) searches only each query's top-p
    shards by centroid distance (``route_topk`` — stable: ties go to the
    lower shard id), and each query's p pools fold through ``_merge_topk``
    in ascending shard order.  Counters then total the routed work only.
    Two execution strategies, selected by mesh shape: with one device per
    shard, queries are compacted host-side into one static bucketed block
    per shard (padding rows masked, ROUTED_BLOCK_MULT) so each device only
    searches queries routed to it (shard_map); with shards packed many-per-
    device (any single-device run), the routed pairs instead become the
    b·p rows of ONE beam search over the precomputed block-diagonal flat
    graph (``ShardedGraph.flat_ids``) — same results row-for-row, none of
    the per-shard slice copies (``_fused_routed_search_fn``).  ``p == S``
    routes every query to every shard — the scatter-gather decomposition
    exactly — and dispatches the scatter-gather program itself, so it is
    bit-identical to ``routed_shards=None`` by construction.

    ``shard_mask`` (bool[S], DESIGN.md §14) marks live shards for
    degraded-mode serving: dead shards are excluded from BOTH routing
    (their centroid scores mask to +inf so ``route_topk`` never picks
    them) and the merge (their scatter-gather pools search under an
    all-False row mask, returning INVALID/inf that rank-merge as no-ops),
    and the psum'd counters count live-shard work only.  An all-False
    mask raises (no live shard can answer); ``routed_shards`` above the
    live count clamps down with a warning.  ``shard_mask=None`` (and any
    all-True mask) is the healthy path, bit-identical to not having the
    parameter.

    ``tombstone_ids`` (int32[T] global ids, INVALID-padded, DESIGN.md §15)
    masks deleted nodes out of the final folded ef-wide pool before the k
    truncation — on every execution strategy, so a deleted id never
    surfaces even while still a node of some shard's graph.  ``None`` (and
    an empty array) dispatches the exact cached program of before the
    parameter existed (static ``tombstones=False`` variant).

    ``quantize="sq8"`` (DESIGN.md §16) beam-searches each shard's int8
    codes (``ShardedGraph.qcodes`` / ``qscale`` / ``qnorms`` — stored by
    ``graph.partition(..., quantize="sq8")``) and re-ranks every per-shard
    ef-pool against that shard's fp32 rows *before* the global-id restore
    and the fold, on all three execution strategies; re-rank distances add
    to ``n_computed``.  ``"none"`` dispatches the exact fp32 cached
    program of before the knob existed (static ``quantize=False``
    variant).
    """
    if k > ef:
        raise ValueError(
            f"k={k} > ef={ef}: the search pool holds only ef candidates, so "
            f"slots beyond ef would be INVALID padding, silently returning "
            f"fewer than k real neighbors; raise ef to at least k")
    if visited_impl not in VISITED_IMPLS:
        raise ValueError(
            f"visited_impl {visited_impl!r} not in {VISITED_IMPLS}")
    if quantize not in metric_lib.QUANTIZE_MODES:
        raise ValueError(
            f"quantize {quantize!r} not in {metric_lib.QUANTIZE_MODES}")
    if quantize == "sq8" and getattr(sharded_graph, "qcodes", None) is None:
        raise ValueError(
            "quantize='sq8' needs per-shard int8 codes but this "
            "ShardedGraph has none — rebuild it with "
            "graph.partition(..., quantize='sq8') (or "
            "retrieval.build_index(quantize='sq8')), which stores them")
    if expand_width < 1:
        raise ValueError(f"expand_width must be >= 1, got {expand_width}")
    if row_mask is not None:
        row_mask = jnp.asarray(row_mask)
        if row_mask.dtype != jnp.bool_:
            raise ValueError(
                f"row_mask dtype {row_mask.dtype} must be bool: integer "
                f"masks silently cast inside the search (0/1 arithmetic "
                f"instead of validity), so a wrong-dtype mask would search "
                f"padding rows; pass a bool array")
    num_shards = sharded_graph.num_shards
    import numpy as np       # host-side mask validation + routing below
    if shard_mask is not None:
        shard_mask = np.asarray(shard_mask)
        if shard_mask.dtype != np.bool_:
            raise ValueError(
                f"shard_mask dtype {shard_mask.dtype} must be bool: an "
                f"integer mask would silently cast inside the search; pass "
                f"a bool array (True = shard alive)")
        if shard_mask.shape != (num_shards,):
            raise ValueError(
                f"shard_mask shape {shard_mask.shape} must be "
                f"({num_shards},): one liveness flag per shard of this "
                f"ShardedGraph")
        if bool(shard_mask.all()):
            shard_mask = None           # healthy: identical program + args
        elif not bool(shard_mask.any()):
            raise ValueError(
                f"shard_mask is all-False: every one of the {num_shards} "
                f"shards is marked dead, so no shard can answer the query "
                f"— an all-INVALID pool would be silently softmaxed by "
                f"retrieval attention.  Refusing to search; restore at "
                f"least one shard (ShardHealth.revive) or swap in a "
                f"snapshot (serve.resilience)")
    n_live = int(shard_mask.sum()) if shard_mask is not None else num_shards
    global _CLAMP_WARNED_STATE
    if routed_shards is not None:
        p = int(routed_shards)
        if not 1 <= p <= num_shards:
            raise ValueError(
                f"routed_shards={routed_shards} must be in [1, "
                f"num_shards={num_shards}]: each query searches its top-p "
                f"shards by centroid distance")
        if p > n_live:
            # Warn once per ShardHealth state transition, not per call: a
            # degraded serving loop re-enters here every batch with the
            # same (num_shards, n_live, p) state.
            clamp_state = (num_shards, n_live, p)
            if _CLAMP_WARNED_STATE != clamp_state:
                warnings.warn(
                    f"routed_shards={p} exceeds the {n_live} live shards "
                    f"(shard_mask kills {num_shards - n_live}); clamping "
                    f"to {n_live} — every live shard is searched "
                    f"(DESIGN.md §14)",
                    stacklevel=2)
                _CLAMP_WARNED_STATE = clamp_state
            p = n_live
        else:
            _CLAMP_WARNED_STATE = None
        if p == num_shards:
            routed_shards = None       # degenerate: exact scatter-gather
        elif sharded_graph.centroids is None:
            raise ValueError(
                "routed_shards needs per-shard centroids; this ShardedGraph "
                "has none — rebuild it with graph.partition (any "
                "assignment), which stores them")
        else:
            routed_shards = p
    if tombstone_ids is not None:
        tombstone_ids = jnp.asarray(tombstone_ids, jnp.int32)
        if tombstone_ids.ndim != 1:
            raise ValueError(
                f"tombstone_ids must be a 1-D id array, got shape "
                f"{tombstone_ids.shape}")
        if tombstone_ids.shape[0] == 0:
            tombstone_ids = None       # empty: healthy cached program
    tomb = () if tombstone_ids is None else (tombstone_ids,)
    qargs = (() if quantize == "none" else
             (sharded_graph.qcodes, sharded_graph.qscale,
              sharded_graph.qnorms))
    b = queries.shape[0]
    if mesh is None:
        # default to the mesh the graph was placed on (graph.place_sharded
        # commits the arrays along "shard" at build/restore time), so the
        # jit'd program consumes the resident layout with no per-call
        # reshard; an explicit mesh must match that placement (jax raises
        # otherwise)
        mesh = sharding_lib.placement_mesh(sharded_graph.ids, num_shards)
    max_hops = max_hops or default_max_hops(ef, expand_width)
    dummy_d, dummy_has = fresh_cache(b, 1, False)
    live = jnp.asarray(np.ones(num_shards, bool) if shard_mask is None
                       else shard_mask)
    if routed_shards is None:
        run = _sharded_search_fn(
            mesh, k=k, ef=ef, max_hops=max_hops, metric=metric,
            visited_impl=visited_impl, hash_slots=hash_slots,
            expand_width=expand_width, tombstones=bool(tomb),
            quantize=bool(qargs))
        pool_i, pool_d, n_fresh, n_comp, hops = run(
            sharded_graph.ids, sharded_graph.data, sharded_graph.global_ids,
            sharded_graph.entries, live, queries,
            jnp.ones((b,), bool) if row_mask is None else row_mask,
            *qargs, *tomb)
        return SearchResult(pool_i, pool_d, n_fresh, n_comp, hops,
                            dummy_d, dummy_has)

    p = int(routed_shards)
    if mesh.size < num_shards and sharded_graph.flat_ids is not None:
        # Packed slots (> 1 shard per device): the shard_map body's
        # per-shard slices would materialize O(n/S) copies per shard per
        # call, so dispatch the fused single-search program over the
        # precomputed block-diagonal flat graph instead (DESIGN.md §13).
        # Bit-identical per routed (query, shard) row — pinned by test.
        run = _fused_routed_search_fn(
            k=k, ef=ef, max_hops=max_hops, metric=metric,
            visited_impl=visited_impl, hash_slots=hash_slots,
            expand_width=expand_width, p=p, tombstones=bool(tomb),
            quantize=bool(qargs))
        pool_i, pool_d, n_fresh, n_comp, hops = run(
            sharded_graph.flat_ids, sharded_graph.data,
            sharded_graph.global_ids, sharded_graph.entries,
            sharded_graph.centroids, live, queries,
            jnp.ones((b,), bool) if row_mask is None else row_mask,
            *qargs, *tomb)
        return SearchResult(pool_i, pool_d, n_fresh, n_comp, hops,
                            dummy_d, dummy_has)

    met = metric_lib.resolve(metric)
    qprep = met.prepare(queries)
    scores = metric_lib.kernel_distance(
        qprep[:, None, :], sharded_graph.centroids[None, :, :], met.kernel)
    scores = np.asarray(scores)
    if shard_mask is not None:
        # Host-side analogue of the fused path's in-jit masking: dead
        # shards score +inf and p <= n_live, so they are never routed to —
        # their blocks stay empty and contribute nothing to the psums.
        scores = np.where(shard_mask[None, :], scores, np.inf)
    routed = np.asarray(route_topk(jnp.asarray(scores), p))    # (b, p) asc
    rmask = (np.ones(b, bool) if row_mask is None
             else np.asarray(row_mask))
    # Compact per shard: shard s searches exactly the queries routed to it,
    # in query order; slot_of[b, j] is query b's row inside shard
    # routed[b, j]'s block.  Static bucketed block height (graph.bucket)
    # keeps the compiled-shape set small across batches.
    per_shard: list = [[] for _ in range(num_shards)]
    slot_of = np.zeros((b, p), np.int32)
    for i in range(b):
        if not rmask[i]:
            continue                     # padding queries route nowhere
        for j, s in enumerate(routed[i]):
            slot_of[i, j] = len(per_shard[s])
            per_shard[s].append(i)
    bq = graph_lib.bucket(max(1, max(len(l) for l in per_shard)),
                          ROUTED_BLOCK_MULT)
    q_index = np.zeros((num_shards, bq), np.int32)
    q_mask = np.zeros((num_shards, bq), bool)
    for s, rows in enumerate(per_shard):
        q_index[s, :len(rows)] = rows
        q_mask[s, :len(rows)] = True
    run = _routed_search_fn(
        mesh, k=k, ef=ef, max_hops=max_hops, metric=metric,
        visited_impl=visited_impl, hash_slots=hash_slots,
        expand_width=expand_width, p=p, tombstones=bool(tomb),
        quantize=bool(qargs))
    pool_i, pool_d, n_fresh, n_comp, hops = run(
        sharded_graph.ids, sharded_graph.data, sharded_graph.global_ids,
        sharded_graph.entries, queries, jnp.asarray(q_index),
        jnp.asarray(q_mask), jnp.asarray(routed), jnp.asarray(slot_of),
        jnp.asarray(rmask), *qargs, *tomb)
    return SearchResult(pool_i, pool_d, n_fresh, n_comp, hops,
                        dummy_d, dummy_has)
