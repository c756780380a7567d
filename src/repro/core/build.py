"""Multi-graph build step — one compiled dispatch per insertion batch.

The paper's headline speedup comes from constructing many PGs
simultaneously so repeated computation is paid once (PAPER.md).  Driving
each batch's stages from the host would pay that saving back in dispatch
overhead: one ``beam_search`` dispatch, m ``rng_prune`` and m
``add_reverse_edges`` dispatches and dozens of eager ops per batch, with
``int(...)`` counter casts blocking the host after each one.

This module is the builders' one driver: the whole search → mPrune → commit
batch step is ONE jitted function (DESIGN.md §12):

  ``insert_batch``      the Vamana/HNSW-shaped step (search the evolving
                        graph, prune, commit) — a single dispatch per batch,
                        counters returned as an int32[7] device row.
  ``nsg_insert_batch``  the NSG-shaped step (search a *static* KNNG, merge
                        each node's own KNNG row into the candidates, prune,
                        commit) — same single-dispatch contract.
  ``fused_vamana_pass`` the device-resident outer loop: ``lax.fori_loop``
                        over ALL insertion batches inside one jit, writing
                        per-batch counter rows into a preallocated log —
                        the host dispatches once per build pass and never
                        blocks mid-build.

The step traces the stage functions (``search.beam_search``,
``prune.multi_prune``, ``commit.commit_group``) in order — there is no
separate algorithm to drift.  Two facts are pinned by
tests/test_fused_build.py: (1) ``fused_vamana_pass`` is bit-identical to
dispatching ``insert_batch`` once per batch (the ``fori_loop`` adds
nothing); (2) one jitted step is bit-identical to calling the stage
functions in order on the same state.

Shapes are static and bucketed (``graph.bucket`` on L_max/M_max, fixed
``batch_size``), so one XLA compile of the step is reused across every
batch of every tuning iteration that shares the bucketed shape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import commit, counters as counters_lib, obs, prune, search
from repro.core.graph import INVALID

BUILD_IMPLS = ("fused",)


def resolve_build_impl(build_impl: str) -> str:
    """Check the ``build_impl`` keyword the entry points still accept: the
    build has one driver, the compiled step of this module."""
    if build_impl not in BUILD_IMPLS:
        raise ValueError(
            f"build_impl {build_impl!r} not in {BUILD_IMPLS}: the build has "
            f"one driver, the compiled step (DESIGN.md §12)")
    return build_impl


def _insert_step(graph_ids, graph_dist, data, u, row_mask, queries, L, M,
                 alpha, entry, cache_d, cache_has, *, ef_max, max_hops,
                 share_cache, use_epo, metric, visited_impl, expand_width,
                 k_in, m_max):
    """Traced body of one insertion batch: search → mPrune → commit.

    The stage functions called in order in one traceable function, with
    counters returned as device scalars.  Returns ``(new_ids, new_dist,
    ctr_row, top_ids, cache_d, cache_has)`` where ``ctr_row`` is the
    int32[7] CounterTape row and ``top_ids`` is each (query, graph)'s
    closest pool entry (HNSW's next-layer entry points; Vamana ignores it).
    """
    qids = jnp.where(row_mask, u, INVALID)
    res = search.beam_search(
        graph_ids, data, queries, qids, row_mask, L, entry,
        cache_d, cache_has, ef_max=ef_max, max_hops=max_hops,
        share_cache=share_cache, metric=metric, visited_impl=visited_impl,
        expand_width=expand_width)

    cand_ids = jnp.transpose(res.pool_ids, (1, 0, 2))     # (m, b, ef_max)
    cand_dist = jnp.transpose(res.pool_dist, (1, 0, 2))
    valid = cand_ids != INVALID
    with jax.named_scope(obs.PRUNE):
        pruned, nb, nc = prune.multi_prune(
            data, cand_ids, cand_dist, valid, M, alpha,
            m_max=m_max, use_epo=use_epo, metric=metric)

    with jax.named_scope(obs.COMMIT):
        new_ids, new_dist, rev_checks = commit.commit_group(
            data, graph_ids, graph_dist, u, pruned, row_mask, M, alpha,
            k_in=k_in, m_max=m_max, metric=metric)
    ctr_row = counters_lib.step_row(res, row_mask, expand_width,
                                    nb + rev_checks, nc + rev_checks)
    return (new_ids, new_dist, ctr_row, res.pool_ids[:, :, 0],
            res.cache_d, res.cache_has)


insert_batch = jax.jit(
    _insert_step,
    static_argnames=("ef_max", "max_hops", "share_cache", "use_epo",
                     "metric", "visited_impl", "expand_width", "k_in",
                     "m_max"))
insert_batch.__doc__ = (
    "One compiled dispatch per insertion batch: jitted _insert_step.  The "
    "dispatch-count contract tests/test_fused_build.py pins — after "
    "warmup, a batch step invokes NO other jitted callable at the Python "
    "level (DESIGN.md §12).")


def _nsg_step(search_graph_ids, graph_ids, graph_dist, knn_ids, knn_dist,
              data, u, row_mask, queries, L, M, alpha, K, entry, *, ef_max,
              max_hops, share_cache, use_epo, metric, visited_impl,
              expand_width, k_in, m_max, k_max):
    """Traced body of one NSG insertion batch (search on the static KNNG,
    candidates = search pool ∪ the node's own KNNG row, prune, commit).

    NSG's prune candidates are the nodes *visited* during search; the pool
    alone loses u's local KNNG structure, so each node's own KNNG row (exact
    distances already known — no extra #dist) is merged with the search
    pool, deduplicated (first occurrence after a sort by distance) and
    sorted ascending, as prune requires."""
    n = data.shape[0]
    qids = jnp.where(row_mask, u, INVALID)
    res = search.beam_search(
        search_graph_ids, data, queries, qids, row_mask, L, entry,
        ef_max=ef_max, max_hops=max_hops, share_cache=share_cache,
        metric=metric, visited_impl=visited_impl,
        expand_width=expand_width)

    u_safe = jnp.minimum(u, n - 1)
    m = graph_ids.shape[0]
    own_ids = jnp.broadcast_to(knn_ids[u_safe][None],
                               (m,) + knn_ids[u_safe].shape)
    own_dist = jnp.broadcast_to(knn_dist[u_safe][None], own_ids.shape)
    kmask = jnp.arange(k_max)[None, None, :] < K[:, None, None]
    own_ids = jnp.where(kmask & row_mask[None, :, None], own_ids, INVALID)
    own_dist = jnp.where(own_ids != INVALID, own_dist, jnp.inf)
    cand_ids = jnp.concatenate(
        [jnp.transpose(res.pool_ids, (1, 0, 2)), own_ids], axis=-1)
    cand_dist = jnp.concatenate(
        [jnp.transpose(res.pool_dist, (1, 0, 2)), own_dist], axis=-1)
    srt = jnp.argsort(cand_dist, axis=-1)
    cand_ids = jnp.take_along_axis(cand_ids, srt, axis=-1)
    cand_dist = jnp.take_along_axis(cand_dist, srt, axis=-1)
    eq = cand_ids[:, :, None, :] == cand_ids[:, :, :, None]
    c = cand_ids.shape[-1]
    tri = jnp.tril(jnp.ones((c, c), bool), k=-1)
    dup = jnp.any(eq & tri[None, None], axis=-1)
    cand_ids = jnp.where(dup, INVALID, cand_ids)
    cand_dist = jnp.where(dup, jnp.inf, cand_dist)
    valid = cand_ids != INVALID
    with jax.named_scope(obs.PRUNE):
        pruned, nb, nc = prune.multi_prune(
            data, cand_ids, cand_dist, valid, M, alpha,
            m_max=m_max, use_epo=use_epo, metric=metric)

    with jax.named_scope(obs.COMMIT):
        new_ids, new_dist, rev_checks = commit.commit_group(
            data, graph_ids, graph_dist, u, pruned, row_mask, M, alpha,
            k_in=k_in, m_max=m_max, metric=metric)
    ctr_row = counters_lib.step_row(res, row_mask, expand_width,
                                    nb + rev_checks, nc + rev_checks)
    return new_ids, new_dist, ctr_row


nsg_insert_batch = jax.jit(
    _nsg_step,
    static_argnames=("ef_max", "max_hops", "share_cache", "use_epo",
                     "metric", "visited_impl", "expand_width", "k_in",
                     "m_max", "k_max"))


@functools.partial(
    jax.jit,
    static_argnames=("batch_size", "ef_max", "max_hops", "share_cache",
                     "use_epo", "metric", "visited_impl", "expand_width",
                     "k_in", "m_max"))
def fused_vamana_pass(graph_ids, graph_dist, data, L, M, alpha, ep, *,
                      batch_size, ef_max, max_hops, share_cache, use_epo,
                      metric, visited_impl, expand_width, k_in, m_max):
    """Device-resident Vamana main pass: every insertion batch inside ONE
    compiled dispatch (``lax.fori_loop`` over batches).

    The loop body builds each batch — ``u`` padded with ``n`` past the
    corpus, padding rows masked, queries gathered at ``min(u, n-1)`` — then
    runs ``_insert_step``, so the final graphs and the per-batch counter
    rows are bit-identical to dispatching ``insert_batch`` once per batch.
    Counter rows land in a preallocated
    int32[n_batches, 7] log (no int64 carry needed: the host sums the
    fetched log in int64), returned alongside the graphs for one
    ``CounterTape.log_many`` + end-of-build sync.
    """
    n = data.shape[0]
    m = graph_ids.shape[0]
    n_batches = -(-n // batch_size)
    log = jnp.zeros((n_batches, len(counters_lib.TAPE_FIELDS)), jnp.int32)

    def body(t, carry):
        ids, dist, log = carry
        off = t * batch_size
        u = off + jnp.arange(batch_size, dtype=jnp.int32)
        row_mask = u < n
        u = jnp.where(row_mask, u, n)
        queries = data[jnp.minimum(u, n - 1)]
        entry = jnp.broadcast_to(ep.astype(jnp.int32), (batch_size, m))
        ids, dist, row, _, _, _ = _insert_step(
            ids, dist, data, u, row_mask, queries, L, M, alpha, entry,
            None, None, ef_max=ef_max, max_hops=max_hops,
            share_cache=share_cache, use_epo=use_epo, metric=metric,
            visited_impl=visited_impl, expand_width=expand_width,
            k_in=k_in, m_max=m_max)
        return ids, dist, log.at[t].set(row)

    graph_ids, graph_dist, log = jax.lax.fori_loop(
        0, n_batches, body, (graph_ids, graph_dist, log))
    return graph_ids, graph_dist, log
