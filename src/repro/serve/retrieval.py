"""Retrieval attention: the paper's technique serving the LM stack.

Long-context decode attends over an enormous KV cache; RetrievalAttention
(paper ref [8]) replaces the exhaustive pass with k-ANNS over cached keys
using a proximity graph.  This module builds a PG over a layer's keys and
answers decode-time attention by searching top-k keys, attending only to
those — and the PG's construction parameters are exactly what FastPGT tunes
(examples/serve_retrieval.py runs the tuner over this index).

Attention ranks keys by raw inner product q.k, so the index is built
natively under the "ip" metric (core/metric.py): argmin (1 - q.k) is exactly
argmax q.k — no normalization, no MIPS-to-L2 reduction, and ranking is exact
rather than the angle-only approximation the old normalize-and-L2 hack gave.
``metric="cosine"`` remains available (keys unit-normalized ONCE at build
and stored on the index; queries normalized per call — never the full key
matrix again), as does plain "l2".

Serving memory model (DESIGN.md §9): decode-time searches default to
``visited_impl="hash"`` — per-query visit state is an O(ef·M·hops)
open-addressing hash set instead of the dense O(n) bitmap, so search memory
is independent of context length and the path scales to million-key caches.
Serving also defaults to width-``DEFAULT_EXPAND_WIDTH`` multi-expansion
(DESIGN.md §10): each search hop expands the W closest unexpanded frontier
nodes at once, cutting the sequential hop count ~W× and amortizing the
fixed per-hop costs (pool merge, hash probing, kernel dispatch) that
dominate decode-time latency.  Builders keep the dense, W = 1 defaults
(§2.1 bit-identity of build outputs).

``retrieval_attention`` answers one decode batch; for heavy traffic,
``retrieval_attention_batched`` blocks large/ragged query batches into
static bucketed shapes (graph.bucket) so XLA compiles one search per block
shape and reuses it across requests.

Corpora beyond one device's memory shard (DESIGN.md §11):
``build_index(..., num_shards=S)`` chunk-partitions the keys and builds an
independent Vamana subindex per shard; searches then scatter-gather over a
``"shard"`` mesh axis (``search.sharded_knn_search``) and merge per-shard
pools with global ids restored.  ``num_shards=1`` (default) is bit-identical
to the unsharded path.  Scope note: sharding covers the *search-side*
corpus — prepared keys + graph live once, split across the mesh (no
replicated ``search_keys`` copy is kept) — while the attention gather
(``_attend``) still reads replicated ``keys``/``values``; sharding that
gather is the multi-host follow-up.

Scope: per-(layer, head) indexes over a frozen prefill cache (the common
RAG/long-doc serving pattern); incremental insertion reuses the same
builders batch-wise.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import build as build_lib
from repro.core import graph as graph_lib
from repro.core import metric as metric_lib
from repro.core import search as search_lib
from repro.core import vamana as vamana_lib

# Serving-side multi-expansion width (DESIGN.md §10): searches answer with
# the same pools a W = 1 search would find at equal recall targets, in ~W×
# fewer sequential hops; builders deliberately do NOT share this default.
DEFAULT_EXPAND_WIDTH = 4


@dataclasses.dataclass
class RetrievalIndex:
    graph_ids: jax.Array | None  # (n_ctx, M_max) over one head's keys
                                 # (None when the index is sharded)
    keys: jax.Array            # (n_ctx, dh) raw keys (attention logits)
    values: jax.Array          # (n_ctx, dh)
    search_keys: jax.Array | None  # (n_ctx, dh) metric-prepared ONCE at
                                   # build (None when sharded: the prepared
                                   # keys live split across shards.data —
                                   # keeping a replicated copy too would
                                   # double per-host memory)
    entry: int                 # global entry id (unsharded search path;
                               # sharded searches use shards.entries)
    params: vamana_lib.VamanaParams
    metric: str                # public metric name ("ip" | "cosine" | "l2")
    shards: graph_lib.ShardedGraph | None = None   # mesh-partitioned index
    provenance: dict | None = None   # build knobs (build_impl/assign/seed/
                                     # batch_size) — recorded by build_index
                                     # so snapshot manifests can say how the
                                     # index was built (DESIGN.md §14)
    quantize: str = "none"           # corpus representation searches use by
                                     # default (DESIGN.md §16): "sq8" beam-
                                     # searches int8 codes + fp32 re-rank
    quant: metric_lib.QuantizedData | None = None  # unsharded SQ8 codes,
                                     # computed ONCE at build (sharded
                                     # indexes carry theirs on shards.q*)

    @property
    def kernel(self) -> str:
        """Kernel form searches run under (search_keys are pre-prepared)."""
        return metric_lib.resolve(self.metric).kernel

    @property
    def num_shards(self) -> int:
        return 1 if self.shards is None else self.shards.num_shards


def build_index(keys: jax.Array, values: jax.Array,
                params: vamana_lib.VamanaParams, *, metric: str = "ip",
                seed: int = 0, batch_size: int = 256,
                num_shards: int = 1, assign: str = "chunked",
                build_impl: str = "fused",
                quantize: str = "none") -> RetrievalIndex:
    """Index one head's keys under ``metric`` (default: native ip/MIPS).

    Any metric preparation (unit-normalization for cosine) happens exactly
    once here; ``search_keys`` stores the prepared matrix so query-time
    never touches the full cache again.

    ``num_shards > 1`` partitions the keys (placement policy ``assign``:
    "chunked" | "random" | "kmeans", graph.ASSIGNMENTS — kmeans clusters
    the keys so centroid routing can skip shards, DESIGN.md §13) and
    builds an independent Vamana subindex per shard (same ``params``);
    searches then run scatter-gather over a ``"shard"`` mesh axis
    (``search.sharded_knn_search``, DESIGN.md §11) so no device ever holds
    the whole corpus.  The default 1 is bit-identical to the unsharded
    path — same builder call, same ``knn_search``.

    The Vamana build runs its whole insertion pass as one compiled
    dispatch (DESIGN.md §12).  ``build_impl`` accepts only "fused", is
    recorded in ``provenance`` and is not passed on: it stays only until
    the benchmark's mixes stop naming it.

    ``quantize="sq8"`` (DESIGN.md §16) additionally stores an int8 SQ8
    view of the prepared keys (scale computed ONCE here, carried on the
    index and its snapshots) so searches beam over 4×-smaller codes and
    re-rank against fp32.  The graph build itself ALWAYS runs fp32 — the
    builders and the tuner's estimation stay bit-identical (§2.1) and the
    paper-exact #dist accounting holds — so "sq8" changes search-time
    representation only.
    """
    build_lib.resolve_build_impl(build_impl)
    met = metric_lib.resolve(metric)
    if quantize not in metric_lib.QUANTIZE_MODES:
        raise ValueError(
            f"quantize {quantize!r} not in {metric_lib.QUANTIZE_MODES}")
    search_keys = met.prepare(keys)
    prov = {"build_impl": build_impl, "assign": assign, "seed": seed,
            "batch_size": batch_size, "num_shards": num_shards,
            "quantize": quantize}
    if num_shards == 1:
        res = vamana_lib.build_vamana(search_keys, params, seed=seed,
                                      batch_size=batch_size,
                                      metric=met.kernel)
        quant = (metric_lib.quantize_sq8(search_keys)
                 if quantize == "sq8" else None)
        return RetrievalIndex(graph_ids=res.g.ids[0], keys=keys,
                              values=values, search_keys=search_keys,
                              entry=res.entry, params=params,
                              metric=met.name, provenance=prov,
                              quantize=quantize, quant=quant)

    def shard_builder(local):
        res = vamana_lib.build_vamana(local, params, seed=seed,
                                      batch_size=batch_size,
                                      metric=met.kernel)
        return res.g.ids[0], res.entry

    shards = graph_lib.partition(search_keys, num_shards,
                                 assignment=assign, seed=seed,
                                 build_fn=shard_builder, metric=met.kernel,
                                 quantize=quantize)
    entry = int(shards.global_ids[0][int(shards.entries[0])])
    return RetrievalIndex(graph_ids=None, keys=keys, values=values,
                          search_keys=None, entry=entry,
                          params=params, metric=met.name, shards=shards,
                          provenance=prov, quantize=quantize)


def _attend(idx: RetrievalIndex, q: jax.Array, pool_ids: jax.Array,
            scale: float | None) -> jax.Array:
    """Softmax-attend queries (B, dh) over retrieved key ids (B, k)."""
    dh = q.shape[-1]
    scale = scale or 1.0 / (dh ** 0.5)
    ids = jnp.maximum(pool_ids, 0)                        # (B, k)
    valid = pool_ids >= 0
    k_sel = idx.keys[ids]                                 # (B, k, dh)
    v_sel = idx.values[ids]
    logits = jnp.einsum("bd,bkd->bk", q, k_sel) * scale
    logits = jnp.where(valid, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bk,bkd->bd", w, v_sel)


def _search_index(idx: RetrievalIndex, qs: jax.Array, top_k: int, ef: int,
                  visited_impl: str, expand_width: int,
                  row_mask: jax.Array | None = None,
                  routed_shards: int | None = None,
                  shard_mask=None,
                  tombstone_ids=None,
                  quantize: str | None = None) -> search_lib.SearchResult:
    """Route one prepared-query batch to the un- or mesh-sharded search.

    ``tombstone_ids`` (int32[T] global ids, INVALID-padded) masks deleted
    nodes out of the merged pool on either path (DESIGN.md §15); the
    streaming MutableIndex is the owner of the mask.

    ``quantize=None`` defaults to the index's own representation
    (``build_index(quantize=...)``, DESIGN.md §16); pass "none" to force
    the fp32 path of a quantized index (parity checks) or "sq8" to search
    an index that stored codes.
    """
    quantize = idx.quantize if quantize is None else quantize
    if idx.shards is not None:
        return search_lib.sharded_knn_search(
            idx.shards, qs, top_k, ef, metric=idx.kernel,
            visited_impl=visited_impl, expand_width=expand_width,
            row_mask=row_mask, routed_shards=routed_shards,
            shard_mask=shard_mask, tombstone_ids=tombstone_ids,
            quantize=quantize)
    if routed_shards not in (None, 1):
        raise ValueError(
            f"routed_shards={routed_shards} on an unsharded index: routing "
            f"selects among shards, so build the index with num_shards > 1 "
            f"(DESIGN.md §13)")
    if shard_mask is not None:
        raise ValueError(
            "shard_mask on an unsharded index: liveness masking selects "
            "among shards, so build the index with num_shards > 1 "
            "(DESIGN.md §14)")
    return search_lib.knn_search(
        idx.graph_ids, idx.search_keys, qs, top_k, ef, idx.entry,
        metric=idx.kernel, visited_impl=visited_impl,
        expand_width=expand_width, row_mask=row_mask,
        tombstone_ids=tombstone_ids, quantize=quantize,
        quant=idx.quant if quantize == "sq8" else None)


def retrieval_attention(idx: RetrievalIndex, q: jax.Array, *, top_k: int,
                        ef: int, scale: float | None = None,
                        visited_impl: str = "hash",
                        expand_width: int = DEFAULT_EXPAND_WIDTH,
                        routed_shards: int | None = None,
                        shard_mask=None,
                        quantize: str | None = None,
                        ) -> tuple[jax.Array, search_lib.SearchResult]:
    """Approximate attention for decode queries q: (B, dh).

    Searches the PG for top_k keys per query and softmax-attends over just
    those.  Returns (out (B, dh), SearchResult for instrumentation).
    Search state is O(ef)-memory hash-set based by default (DESIGN.md §9);
    pass ``visited_impl="dense"`` to get the exact-counter bitmap path.
    ``expand_width`` is the per-hop frontier width (DESIGN.md §10) —
    1 reproduces the paper's sequential schedule exactly.  On an index
    built with ``num_shards > 1`` the search scatter-gathers across the
    shard mesh (DESIGN.md §11) and returns global key ids either way;
    ``routed_shards=p`` searches only each query's top-p shards by
    centroid distance (DESIGN.md §13 — pair with
    ``build_index(assign="kmeans")`` for shards worth routing between).
    ``shard_mask`` (bool[S]) excludes dead shards from routing and merge —
    degraded-mode serving, DESIGN.md §14 (serve.resilience owns the mask).
    ``quantize`` (None = the index's own mode, DESIGN.md §16) selects the
    corpus representation the search beams over.
    """
    met = metric_lib.resolve(idx.metric)
    qs = met.prepare(q)            # per-call cost is (B, dh) — keys untouched
    res = _search_index(idx, qs, top_k, ef, visited_impl, expand_width,
                        routed_shards=routed_shards, shard_mask=shard_mask,
                        quantize=quantize)
    return _attend(idx, q, res.pool_ids, scale), res


def retrieval_attention_batched(
    idx: RetrievalIndex, q: jax.Array, *, top_k: int, ef: int,
    scale: float | None = None, block_size: int = 64,
    visited_impl: str = "hash",
    expand_width: int = DEFAULT_EXPAND_WIDTH,
    routed_shards: int | None = None,
    shard_mask=None,
    quantize: str | None = None,
) -> tuple[jax.Array, search_lib.SearchResult]:
    """Query-blocked retrieval attention for serving-sized batches.

    Splits q: (B, dh) into blocks of a static bucketed size
    (``graph.bucket``: ragged tails pad up to a multiple of 16, masked via
    ``row_mask`` so padding rows do no search work), which keeps the set of
    compiled search shapes small and reused across requests of any B.
    Returns the same (out, SearchResult) pair as ``retrieval_attention``
    with per-block pools concatenated and #dist counters summed (``hops``
    is the max over blocks; cache fields are the last block's dummies).
    """
    B, dh = q.shape
    if B == 0:
        raise ValueError("empty query batch")
    met = metric_lib.resolve(idx.metric)
    qs_all = met.prepare(q)
    bs = graph_lib.bucket(min(block_size, B), 16)
    pool_ids, pool_dist, n_fresh, n_comp, hop_cnt = [], [], [], [], []
    res = None
    for off in range(0, B, bs):
        nrows = min(bs, B - off)
        qb = jnp.zeros((bs, dh), qs_all.dtype).at[:nrows].set(
            qs_all[off:off + nrows])
        res = _search_index(idx, qb, top_k, ef, visited_impl, expand_width,
                            row_mask=jnp.arange(bs) < nrows,
                            routed_shards=routed_shards,
                            shard_mask=shard_mask, quantize=quantize)
        # accumulate device scalars — no host sync inside the dispatch loop
        pool_ids.append(res.pool_ids[:nrows])
        pool_dist.append(res.pool_dist[:nrows])
        n_fresh.append(res.n_fresh)
        n_comp.append(res.n_computed)
        hop_cnt.append(res.hops)
    ids = jnp.concatenate(pool_ids, axis=0)
    agg = search_lib.SearchResult(
        ids, jnp.concatenate(pool_dist, axis=0),
        jnp.sum(jnp.stack(n_fresh)), jnp.sum(jnp.stack(n_comp)),
        jnp.max(jnp.stack(hop_cnt)), res.cache_d, res.cache_has)
    return _attend(idx, q, ids, scale), agg


def exact_attention(keys: jax.Array, values: jax.Array, q: jax.Array,
                    scale: float | None = None) -> jax.Array:
    """Dense reference for quality checks (recall of attention mass)."""
    dh = q.shape[-1]
    scale = scale or 1.0 / (dh ** 0.5)
    logits = jnp.einsum("bd,nd->bn", q, keys) * scale
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bn,nd->bd", w, values)
