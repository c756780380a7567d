"""Proximity-graph representation and shared utilities.

TPU-native representation: a PG over ``n`` vectors is a dense adjacency
matrix ``int32[n, M_max]`` padded with ``INVALID = -1``; ``m`` simultaneously
constructed graphs stack to ``int32[m, n, M_max]``.  Distances annotate edges
as ``float32`` with ``+inf`` padding so top-k merges need no branching.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import metric as metric_lib

INVALID = -1
INF = jnp.inf


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MultiGraph:
    """m stacked PGs over the same vertex set.

    Attributes:
      ids:  int32[m, n, M_max]  out-neighbor ids, INVALID-padded.
      dist: float32[m, n, M_max] matching edge lengths, +inf-padded.
    """
    ids: jax.Array
    dist: jax.Array

    @property
    def m(self) -> int:
        return self.ids.shape[0]

    @property
    def n(self) -> int:
        return self.ids.shape[1]

    @property
    def max_degree(self) -> int:
        return self.ids.shape[2]


def empty_multigraph(m: int, n: int, max_degree: int) -> MultiGraph:
    return MultiGraph(
        ids=jnp.full((m, n, max_degree), INVALID, jnp.int32),
        dist=jnp.full((m, n, max_degree), INF, jnp.float32),
    )


def degree(g: MultiGraph) -> jax.Array:
    """int32[m, n] current out-degrees."""
    return jnp.sum(g.ids != INVALID, axis=-1).astype(jnp.int32)


def medoid(data: jax.Array, metric: str = "l2") -> jax.Array:
    """Index of the vector closest (under ``metric``) to the dataset centroid."""
    met = metric_lib.resolve(metric)
    data = met.prepare(data)
    c = jnp.mean(data, axis=0, keepdims=True)
    d = metric_lib.kernel_distance(data, c, met.kernel)
    return jnp.argmin(d).astype(jnp.int32)


def sort_edges(ids: jax.Array, dist: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Sort edge lists ascending by distance (axis -1), INVALID/+inf last."""
    order = jnp.argsort(dist, axis=-1)
    return (jnp.take_along_axis(ids, order, axis=-1),
            jnp.take_along_axis(dist, order, axis=-1))


# ---------------------------------------------------------------------------
# Deterministic random strategy (FastPGT §IV-C).
#
# All randomness used during construction is a pure function of
# (seed, node_id), so the m simultaneously built graphs see *identical* HNSW
# level draws and *identical* initial-KNNG neighbor prefixes, maximizing the
# structural overlap the ESO/EPO sharing exploits.  Nothing is stored: values
# are regenerated on demand (O(1) memory, as in the paper).
# ---------------------------------------------------------------------------

def hnsw_levels(seed: int, n: int, m_l: float, max_level: int) -> jax.Array:
    """Deterministic HNSW level per node: floor(-ln U * m_l), clipped."""
    key = jax.random.PRNGKey(seed)
    u = jax.random.uniform(key, (n,), jnp.float32, minval=1e-9, maxval=1.0)
    lvl = jnp.floor(-jnp.log(u) * m_l).astype(jnp.int32)
    return jnp.clip(lvl, 0, max_level)


def random_knng_ids(seed: int, n: int, degree: int) -> jax.Array:
    """Deterministic random initial KNNG ids int32[n, degree].

    Row u is a prefix-stable pseudo-random sequence: a graph needing a
    smaller initial degree takes a prefix of the same row, so all m Vamana
    initial graphs overlap maximally (deterministic random strategy).
    Self-loops are redirected to (u+1) mod n.  Column j is drawn from its
    own key ``fold_in(key, j)``: one draw of shape (n, degree) is not
    prefix-stable across degrees under JAX's partitionable threefry.
    """
    key = jax.random.PRNGKey(seed ^ 0x5EED)
    ids = jax.vmap(lambda j: jax.random.randint(
        jax.random.fold_in(key, j), (n,), 0, n, jnp.int32),
        out_axes=1)(jnp.arange(degree))
    rows = jnp.arange(n, dtype=jnp.int32)[:, None]
    return jnp.where(ids == rows, (ids + 1) % n, ids)


def with_distances(data: jax.Array, ids: jax.Array,
                   metric: str = "l2") -> jax.Array:
    """Edge distances float32[..., k] for id matrix int32[n, k] (INVALID->inf)."""
    met = metric_lib.resolve(metric)
    data = met.prepare(data)
    src = data[jnp.arange(ids.shape[0])[:, None]]          # (n, 1, d) via bcast
    dst = data[jnp.clip(ids, 0, None)]                     # (n, k, d)
    d2 = metric_lib.kernel_distance(dst, src, met.kernel)
    return jnp.where(ids == INVALID, INF, d2).astype(jnp.float32)


def stack_graphs(gs: list[tuple[jax.Array, jax.Array]],
                 max_degree: int) -> MultiGraph:
    """Stack per-graph (ids, dist) with per-graph degrees into a MultiGraph."""
    ids, dist = [], []
    for gid, gdist in gs:
        pad = max_degree - gid.shape[-1]
        ids.append(jnp.pad(gid, ((0, 0), (0, pad)), constant_values=INVALID))
        dist.append(jnp.pad(gdist, ((0, 0), (0, pad)), constant_values=INF))
    return MultiGraph(ids=jnp.stack(ids), dist=jnp.stack(dist))


def degree_mask(m: int, max_degree: int, degrees: jax.Array) -> jax.Array:
    """bool[m, max_degree]: slot j active for graph i iff j < degrees[i]."""
    return jnp.arange(max_degree)[None, :] < degrees[:, None]


def bucket(x: int, mult: int) -> int:
    """Round up to a multiple — static-shape bucketing for compile reuse."""
    return -(-x // mult) * mult


# ---------------------------------------------------------------------------
# Corpus sharding (DESIGN.md §11) and query routing (DESIGN.md §13).
#
# Scatter-gather partitioned search splits the corpus into ``num_shards``
# disjoint node sets; each shard holds its own vectors and a subgraph over
# them in *shard-local* int32 ids, plus the local -> global id map used to
# restore global ids when per-shard pools merge.  Shards are padded to a
# common row count so they stack on a leading axis that a "shard" mesh axis
# can partition (core/search.py sharded_knn_search) — the first place the
# corpus-resident arrays stop being replicated across devices.
#
# Every partition also records a per-shard *centroid* (the mean of the
# shard's metric-prepared member vectors): the routing statistic
# ``search.sharded_knn_search(routed_shards=p)`` scores queries against to
# search only the p most promising shards (DESIGN.md §13).  The "kmeans"
# assignment optimizes exactly that statistic — mini-batch k-means with
# jitted Lloyd steps, balanced by capacity-constrained rounding — while
# "chunked"/"random" keep their placement and merely report their means.
# ---------------------------------------------------------------------------

ASSIGNMENTS = ("chunked", "random", "kmeans")

# mini-batch k-means schedule (Sculley-style per-centroid learning rates);
# build-time only, so the defaults favor determinism and partition quality
KMEANS_BATCH = 4096
KMEANS_EPOCHS = 8
# balanced full-batch Lloyd steps after the mini-batch epochs, the
# price-update cap inside each, and the k-means++ restarts whose
# lowest balanced cost wins (see _kmeans_fit / _kmeans_parts)
KMEANS_REFINE = 10
KMEANS_PRICE_ITERS = 128
KMEANS_RESTARTS = 4
# capacity slack ε: shards may hold up to ⌈n/S · (1+ε)⌉ rows.  A hard
# ⌈n/S⌉ cap forcibly spills cluster-boundary points into geometrically
# wrong shards, and each misplaced point is a routing recall hole (its
# neighborhood stays behind); 5% slack removes most forced spills while
# keeping shards balanced enough for the mesh (DESIGN.md §13).
KMEANS_CAP_SLACK = 0.05


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ShardedGraph:
    """num_shards stacked per-shard subindexes over a partitioned corpus.

    Attributes:
      ids:        int32[S, n_s, Mx] shard-local out-neighbor ids
                  (INVALID-padded; they index rows of the same shard).
      data:       float32[S, n_s, d] shard-local vectors (padding rows are
                  zero and unreachable: no adjacency row points at them).
      global_ids: int32[S, n_s] local row -> global id (INVALID on padding).
      entries:    int32[S] shard-local search entry point per shard.
      counts:     int32[S] real (non-padding) rows per shard.
      centroids:  float32[S, d] routing statistic in metric-prepared space
                  — ``search.sharded_knn_search(routed_shards=p)`` scores
                  queries against it (DESIGN.md §13).  Lloyd centroids for
                  kmeans partitions (the statistic the placement
                  optimized), member means otherwise.  None on
                  ShardedGraphs constructed before routing existed
                  (routing then raises).
      flat_ids:   int32[S * n_s, Mx] the same per-shard adjacency in
                  *stacked-flat* id space (shard s row i lives at
                  s * n_s + i; INVALID padding preserved).  The graph is
                  block-diagonal — no row points outside its shard — so a
                  single beam search over it explores exactly one shard
                  per query row.  Precomputed here because the fused
                  routed path (DESIGN.md §13) would otherwise pay an
                  O(n·Mx) offset materialization per search call; None on
                  pre-routing ShardedGraphs (the routed search then falls
                  back to the shard_map path).
      qcodes:     int8[S, n_s, d] SQ8 codes of the metric-prepared shard
                  rows (DESIGN.md §16), present iff the graph was
                  quantized (``quantize_sharded``); None otherwise
                  (``sharded_knn_search(quantize="sq8")`` then raises).
      qscale:     float32[S, d] per-dimension SQ scale — one GLOBAL scale
                  replicated per shard row so codes are comparable across
                  shards and the fused routed path can use any row.
      qnorms:     float32[S, n_s] squared norms of the dequantized rows.
    """
    ids: jax.Array
    data: jax.Array
    global_ids: jax.Array
    entries: jax.Array
    counts: jax.Array
    centroids: jax.Array | None = None
    flat_ids: jax.Array | None = None
    qcodes: jax.Array | None = None
    qscale: jax.Array | None = None
    qnorms: jax.Array | None = None

    @property
    def num_shards(self) -> int:
        return self.ids.shape[0]

    @property
    def shard_rows(self) -> int:
        return self.ids.shape[1]

    @property
    def max_degree(self) -> int:
        return self.ids.shape[2]


def _kmeans_init(x: jax.Array, key: jax.Array, num_shards: int,
                 kernel: str) -> jax.Array:
    """Greedy k-means++ seeds float32[S, d] (Arthur & Vassilvitskii 2007,
    with scikit-learn's greedy refinement).

    The first seed is a uniform draw; each later seed is the best of
    ``2 + ⌊ln S⌋`` candidates drawn with probability proportional to the
    distance to the nearest seed so far (shifted to be non-negative, so
    raw-ip distances work too), keeping the candidate that lowers the
    total nearest-seed distance most.  Spreading the seeds this way keeps
    the partition from depending on one lucky uniform draw of S rows:
    two seeds inside one blob would split it and merge two others.
    """
    n, d = x.shape
    trials = 2 + int(math.log(num_shards))
    first = jax.random.randint(jax.random.fold_in(key, 0), (), 0, n)
    cents = jnp.zeros((num_shards, d), x.dtype).at[0].set(x[first])
    dmin = metric_lib.kernel_distance(x, x[first][None, :], kernel)  # (n,)
    for s in range(1, num_shards):
        w = dmin - jnp.min(dmin)
        cand = jax.random.categorical(jax.random.fold_in(key, s),
                                      jnp.log(w), shape=(trials,))
        dc = metric_lib.kernel_distance(x[:, None, :], x[cand][None, :, :],
                                        kernel)                 # (n, trials)
        pot = jnp.sum(jnp.minimum(dmin[:, None], dc), axis=0)
        best = jnp.argmin(pot)
        cents = cents.at[s].set(x[cand[best]])
        dmin = jnp.minimum(dmin, dc[:, best])
    return cents


def _balance_prices(d: jax.Array, cap: int) -> jax.Array:
    """Per-shard prices f32[S] so that ``argmin(d + prices)`` fills no
    shard far beyond ``cap`` rows (a power-diagram assignment).

    An over-full shard raises its price in proportion to its overflow,
    with a decaying step, until every count fits or KMEANS_PRICE_ITERS
    steps ran; ``_capacity_round`` enforces the cap exactly afterwards.
    A priced shard sheds the rows that are cheapest to move — the ones on
    its border with a neighbour — where a hard cap on raw distances sheds
    its farthest rows, which need not border anything.
    """
    n, num_shards = d.shape
    scale = jnp.mean(d - jnp.min(d, axis=1, keepdims=True))

    def counts(prices):
        a = jnp.argmin(d + prices, axis=1)
        return jax.ops.segment_sum(jnp.ones((n,), jnp.float32), a,
                                   num_segments=num_shards)

    def cond(st):
        t, prices = st
        return (t < KMEANS_PRICE_ITERS) & (jnp.max(counts(prices)) > cap)

    def body(st):
        t, prices = st
        over = jnp.maximum(counts(prices) - cap, 0.0)
        step = 0.5 * scale * num_shards / n / (1.0 + t) ** 0.3
        return t + 1, prices + step * over

    _, prices = jax.lax.while_loop(
        cond, body, (jnp.int32(0), jnp.zeros((num_shards,), jnp.float32)))
    return prices


@functools.partial(
    jax.jit,
    static_argnames=("num_shards", "kernel", "batch", "epochs", "cap"))
def _kmeans_fit(x: jax.Array, key: jax.Array, *, num_shards: int,
                kernel: str, batch: int, epochs: int, cap: int):
    """Balanced k-means: (centroids f32[S, d], prices f32[S], cost f32[]),
    one compiled dispatch.

    Seeds by greedy k-means++ (``_kmeans_init``).  Sculley-style Lloyd
    steps: each assigns one mini-batch to its nearest centroid under
    ``kernel`` distance and moves centroids by the count-weighted running
    mean (per-centroid learning rate 1/seen_count), so early batches move
    centroids fast and late batches anneal.  Each epoch re-shuffles via
    ``fold_in(key, epoch)``; the ragged tail of a shuffle is dropped to
    keep every batch the same static shape.  ``KMEANS_REFINE`` full-batch
    Lloyd steps then settle the centroids under the capacity: each prices
    the shards (``_balance_prices``; l2 kernel only), assigns every row
    to its cheapest shard and moves each centroid to its members' mean.  ``cost`` is the
    summed distance of each row to the centroid of its priced shard — the
    balanced objective ``_kmeans_parts`` compares restarts by.  Pure
    function of (x, key) — partition determinism inherits from here.
    """
    n = x.shape[0]
    init_key, key = jax.random.split(key)
    cents = _kmeans_init(x, init_key, num_shards, kernel)
    counts = jnp.ones((num_shards,), jnp.float32)
    nb = max(n // batch, 1)

    def batch_step(carry, ids):
        cents, counts = carry
        xb = x[ids]                                              # (batch, d)
        d = metric_lib.kernel_distance(xb[:, None, :], cents[None, :, :],
                                       kernel)                   # (batch, S)
        a = jnp.argmin(d, axis=-1)
        cnt = jax.ops.segment_sum(jnp.ones_like(a, jnp.float32), a,
                                  num_segments=num_shards)
        sx = jax.ops.segment_sum(xb, a, num_segments=num_shards)  # (S, d)
        counts = counts + cnt
        cents = cents + (sx - cnt[:, None] * cents) / counts[:, None]
        return (cents, counts), None

    def epoch(e, carry):
        perm = jax.random.permutation(jax.random.fold_in(key, e), n)
        carry, _ = jax.lax.scan(batch_step, carry,
                                perm[:nb * batch].reshape(nb, batch))
        return carry

    def priced(cents):
        d = metric_lib.kernel_distance(x[:, None, :], cents[None, :, :],
                                       kernel)                   # (n, S)
        # ip routing ranks shards by <q, c>, which a query's scale does not
        # change; an additive price does, so a priced ip partition puts
        # rows where the router would not look for them: ip stays unpriced
        prices = (_balance_prices(d, cap) if kernel == "l2"
                  else jnp.zeros((num_shards,), jnp.float32))
        return d, prices, jnp.argmin(d + prices, axis=1)

    def lloyd(_, cents):
        _, _, a = priced(cents)
        cnt = jax.ops.segment_sum(jnp.ones((n,), jnp.float32), a,
                                  num_segments=num_shards)
        sx = jax.ops.segment_sum(x, a, num_segments=num_shards)
        return jnp.where(cnt[:, None] > 0,
                         sx / jnp.maximum(cnt, 1.0)[:, None], cents)

    cents, _ = jax.lax.fori_loop(0, epochs, epoch, (cents, counts))
    cents = jax.lax.fori_loop(0, KMEANS_REFINE, lloyd, cents)
    d, prices, a = priced(cents)
    cost = jnp.sum(jnp.take_along_axis(d, a[:, None], axis=1))
    return cents, prices, cost


def _capacity_round(dist, cap: int):
    """Round a soft k-means assignment to a ≤ ``cap``-per-shard hard one.

    Deterministic host-side spill rounds over ``dist`` float[n, S]:
    every point starts at its argmin column; while some shard exceeds
    ``cap``, that shard keeps its ``cap`` closest movable members (stable
    sort; forced members — rows with only one finite column left — always
    stay) and spills the rest, striking the spilled (row, col) entries to
    +inf so a point never bounces back.  Each productive round strikes
    ≥ 1 entry of the finite n×S budget, so the loop terminates.  Shards
    left empty (duplicate centroids can starve one) are repaired by
    moving the closest point under the ORIGINAL distances from a donor
    shard that keeps ≥ 1 member.  Returns int assignment[n].
    """
    import numpy as np
    n, num_shards = dist.shape
    orig = np.asarray(dist, np.float64)
    d = orig.copy()
    assign = np.argmin(d, axis=1)
    while True:
        counts = np.bincount(assign, minlength=num_shards)
        over = np.flatnonzero(counts > cap)
        if over.size == 0:
            break
        moved = False
        for s in over:
            members = np.flatnonzero(assign == s)
            if members.size <= cap:       # earlier spill this round shrank it
                continue
            movable = members[np.isfinite(d[members]).sum(axis=1) > 1]
            keep = max(cap - (members.size - movable.size), 0)
            order = np.argsort(d[movable, s], kind="stable")
            spill = movable[order[keep:]]
            if spill.size == 0:           # all forced: accept the overflow
                continue
            moved = True
            d[spill, s] = np.inf
            assign[spill] = np.argmin(d[spill], axis=1)
        if not moved:
            break
    counts = np.bincount(assign, minlength=num_shards)
    for s in np.flatnonzero(counts == 0):
        for i in np.argsort(orig[:, s], kind="stable"):
            if counts[assign[i]] > 1:
                counts[assign[i]] -= 1
                assign[i] = s
                counts[s] += 1
                break
    return assign


def _kmeans_parts(n: int, num_shards: int, data, metric: str, seed: int):
    """(per-shard global-id arrays, centroids f32[S, d]) for "kmeans".

    Runs KMEANS_RESTARTS balanced k-means fits from different k-means++
    seeds and keeps the one with the lowest balanced cost: the plain
    k-means optimum can be badly unbalanced (three blobs in one cluster,
    one in another), and any balanced placement of it spills whole
    cluster borders into the wrong shard — routing recall holes.  The
    winner's priced distances are then rounded to the hard cap by
    ``_capacity_round``, which moves few rows because the prices already
    balanced the shards.  The centroids returned are the fit's (the
    statistic the placement optimized), which routing scores queries
    against (DESIGN.md §13).
    """
    import numpy as np
    met = metric_lib.resolve(metric)
    x = met.prepare(jnp.asarray(data, jnp.float32))
    cap = int(np.ceil(n / num_shards * (1.0 + KMEANS_CAP_SLACK)))
    key = jax.random.PRNGKey(seed ^ 0xC3A7)
    fits = [_kmeans_fit(x, jax.random.fold_in(key, r), num_shards=num_shards,
                        kernel=met.kernel, batch=min(KMEANS_BATCH, n),
                        epochs=KMEANS_EPOCHS, cap=cap)
            for r in range(KMEANS_RESTARTS)]
    cents, prices, _ = min(fits, key=lambda f: float(f[2]))
    d = metric_lib.kernel_distance(x[:, None, :], cents[None, :, :],
                                   met.kernel) + prices[None, :]
    assign = _capacity_round(np.asarray(d), cap)
    return [np.flatnonzero(assign == s).astype(np.int32)
            for s in range(num_shards)], cents


def shard_assignment(n: int, num_shards: int, *, assignment: str = "chunked",
                     seed: int = 0, data: jax.Array | None = None,
                     metric: str = "l2") -> list:
    """Global-id arrays per shard (ascending within each shard).

    "chunked" splits [0, n) into contiguous runs (np.array_split balance:
    the first n % S shards get one extra row); "random" deterministically
    permutes ids first (pure function of ``seed`` — the deterministic
    random strategy of §IV-C applied to placement), then chunks the
    permutation.  "kmeans" (DESIGN.md §13) clusters ``data`` (required)
    with mini-batch k-means under ``metric`` and balances the assignment
    by capacity-constrained rounding (cap = ⌈n/S · (1+ε)⌉,
    ε = KMEANS_CAP_SLACK), so routed searches can skip shards without
    any shard hoarding the corpus.  Every id lands in exactly one shard;
    every path is deterministic in ``seed``.
    """
    import numpy as np
    if assignment not in ASSIGNMENTS:
        raise ValueError(f"assignment {assignment!r} not in {ASSIGNMENTS}")
    if not 1 <= num_shards <= n:
        raise ValueError(
            f"num_shards={num_shards} must be in [1, n={n}]: an empty shard "
            f"has no entry point")
    if assignment == "kmeans":
        if data is None:
            raise ValueError(
                "assignment='kmeans' clusters the corpus vectors: pass "
                "data= (the other assignments are data-independent)")
        return _kmeans_parts(n, num_shards, data, metric, seed)[0]
    ids = np.arange(n, dtype=np.int32)
    if assignment == "random":
        ids = np.random.default_rng(seed).permutation(ids)
    return [np.sort(part) for part in np.array_split(ids, num_shards)]


def partition(data: jax.Array, num_shards: int, *,
              assignment: str = "chunked", seed: int = 0,
              graph_ids: jax.Array | None = None,
              build_fn=None, degree: int = 16,
              metric: str = "l2", quantize: str = "none",
              mesh=None) -> ShardedGraph:
    """Partition a corpus (and its graph) into a ``ShardedGraph``.

    Per-shard subgraphs come from one of three sources:
      * ``build_fn(local_data) -> (ids, entry)``: build a fresh subindex
        over each shard's vectors (what serving uses — per-shard Vamana,
        see serve/retrieval.py).  ``ids`` are shard-local.
      * ``graph_ids`` int32[n, Mx]: induce from an existing global graph —
        local rows keep only in-shard edges, remapped to local ids.
        Cross-shard edges are dropped (documented recall cost, DESIGN.md
        §11), so this path is for structure-preserving experiments, not
        quality-sensitive serving.
      * neither: exact KNNG of ``degree`` per shard (knng.build_knng) —
        the quality default at container scale.
    Entry points come from ``build_fn`` when given, else the shard-local
    medoid under ``metric``.  ``assignment`` picks the placement
    (shard_assignment; "kmeans" clusters ``data`` under ``metric``), and
    every mode stores per-shard centroids for query routing (DESIGN.md
    §13).

    The result is placed onto ``mesh`` (default: the ``"shard"`` mesh
    ``distributed.sharding.search_mesh(num_shards)``) with every array
    split along the shard axis — done ONCE here so repeated
    ``sharded_knn_search`` calls never re-scatter the corpus.  Note the
    capacity guarantee is for *steady-state search*: construction stages
    the full corpus (and the stacked per-shard arrays) on the default
    device before that one placement, so building truly
    beyond-device-memory indexes needs shard-at-a-time staging — the
    multi-host follow-up DESIGN.md §11 names.

    ``quantize="sq8"`` additionally stores SQ8 codes for every shard
    (``quantize_sharded``, DESIGN.md §16) so
    ``sharded_knn_search(quantize="sq8")`` can search int8; the graph is
    always built over the fp32 vectors either way (§2.1 bit-identity).
    """
    import numpy as np

    if quantize not in metric_lib.QUANTIZE_MODES:
        raise ValueError(
            f"quantize {quantize!r} not in {metric_lib.QUANTIZE_MODES}")

    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core import knng as knng_lib   # local: keeps graph.py light
    from repro.distributed import sharding as sharding_lib

    data = jnp.asarray(data)
    n = data.shape[0]
    # Routing statistic per assignment mode (DESIGN.md §13): kmeans shards
    # get the Lloyd centroid their placement optimized (NOT the post-
    # rounding member mean — see _kmeans_parts); chunked/random report
    # their member means (routing over them is legal, just geometrically
    # blind).
    if assignment == "kmeans":
        if not 1 <= num_shards <= n:     # mirror shard_assignment's guard
            raise ValueError(
                f"num_shards={num_shards} must be in [1, n={n}]: an empty "
                f"shard has no entry point")
        parts, cents = _kmeans_parts(n, num_shards, data, metric, seed)
    else:
        parts = shard_assignment(n, num_shards, assignment=assignment,
                                 seed=seed)
        prepared = metric_lib.resolve(metric).prepare(data)
        cents = jnp.stack([jnp.mean(prepared[jnp.asarray(part)], axis=0)
                           for part in parts])
    cents = jnp.asarray(cents, jnp.float32)
    all_ids, all_data, all_gids, entries = [], [], [], []
    for part in parts:
        c = len(part)
        local = data[jnp.asarray(part)]
        if build_fn is not None:
            lids, entry = build_fn(local)
            lids = jnp.asarray(lids, jnp.int32)
        elif graph_ids is not None:
            g = jnp.asarray(graph_ids)
            if g.ndim == 3:       # (1, n, Mx) MultiGraph slice
                g = g[0]
            rows = np.asarray(g)[part]                     # (c, Mx) global
            inv = np.full(n, INVALID, np.int32)
            inv[part] = np.arange(c, dtype=np.int32)
            lids = jnp.asarray(
                np.where(rows >= 0, inv[np.maximum(rows, 0)], INVALID))
            entry = int(medoid(local, metric))
        else:
            lids, _ = knng_lib.build_knng(local, min(degree, c - 1),
                                          metric=metric)
            entry = int(medoid(local, metric))
        all_ids.append(lids)
        all_data.append(local)
        all_gids.append(jnp.asarray(part, jnp.int32))
        entries.append(entry)
    sg = assemble_sharded(all_ids, all_data, all_gids, entries,
                          centroids=cents, mesh=mesh)
    if quantize == "sq8":
        sg = quantize_sharded(sg, metric=metric, mesh=mesh)
    return sg


def assemble_sharded(ids_parts, data_parts, gid_parts, entries, *,
                     centroids=None, mesh=None) -> ShardedGraph:
    """Pad/stack per-shard (local graph, vectors, global ids) into a placed
    ``ShardedGraph``.

    The shared assembly tail of ``partition`` — and the seam streaming
    compaction (serve/streaming.py, DESIGN.md §15) reuses to restack a mix
    of freshly rebuilt and untouched shards without re-partitioning: each
    shard contributes ragged (c_s, Mx_s) local adjacency, (c_s, d) vectors
    and (c_s,) global ids; padding, the stacked-flat adjacency for the
    fused routed path, and mesh placement all happen here exactly as at
    first build, so a compacted index dispatches the same cached search
    programs as a fresh one.
    """
    n_s = max(x.shape[0] for x in data_parts)
    mx = max(g.shape[-1] for g in ids_parts)
    counts = [int(x.shape[0]) for x in data_parts]
    ids = jnp.stack([
        jnp.pad(jnp.asarray(g, jnp.int32),
                ((0, n_s - g.shape[0]), (0, mx - g.shape[1])),
                constant_values=INVALID) for g in ids_parts])
    dat = jnp.stack([
        jnp.pad(jnp.asarray(x), ((0, n_s - x.shape[0]), (0, 0)))
        for x in data_parts])
    gids = jnp.stack([
        jnp.pad(jnp.asarray(g, jnp.int32), (0, n_s - g.shape[0]),
                constant_values=INVALID) for g in gid_parts])
    # Stacked-flat adjacency for the fused routed path (DESIGN.md §13):
    # offset each shard's local ids into the concatenated row space once at
    # build time (INVALID padding stays INVALID, so padded rows stay
    # unreachable and the flat graph stays block-diagonal).
    offs = (jnp.arange(len(counts), dtype=jnp.int32) * n_s)[:, None, None]
    flat = jnp.where(ids >= 0, ids + offs, INVALID).reshape(-1, mx)
    sg = ShardedGraph(ids=ids, data=dat, global_ids=gids,
                      entries=jnp.asarray(entries, jnp.int32),
                      counts=jnp.asarray(counts, jnp.int32),
                      centroids=(None if centroids is None
                                 else jnp.asarray(centroids, jnp.float32)),
                      flat_ids=flat)
    return place_sharded(sg, mesh=mesh)


def place_sharded(sg: ShardedGraph, mesh=None) -> ShardedGraph:
    """Commit a ShardedGraph's arrays onto the ``"shard"`` mesh.

    The one ``device_put`` of the sharded-search lifecycle — ``partition``
    calls it at build time, and ``serve.resilience.load_index`` calls it
    when restoring a snapshot, so a restored index gets the same resident
    layout (and hence the same zero-reshard dispatch) as a freshly built
    one.  Default mesh: ``distributed.sharding.search_mesh(num_shards)``.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.distributed import sharding as sharding_lib

    mesh = mesh or sharding_lib.search_mesh(sg.num_shards)
    return jax.device_put(sg, NamedSharding(mesh, PartitionSpec("shard")))


def quantize_sharded(sg: ShardedGraph, metric: str = "l2",
                     mesh=None) -> ShardedGraph:
    """Attach SQ8 codes to a ShardedGraph (DESIGN.md §16).

    Computes ONE global per-dimension scale over the metric-prepared
    corpus (padding rows are zero, so they never raise the abs-max — the
    scale equals the unpadded corpus's) and stores per-shard int8 codes
    plus dequantized-row norms, re-placed on the ``"shard"`` mesh.  The
    scale is replicated per shard row so every shard's codes decode with
    the same statistic and the fused routed path can read any row.
    """
    met = metric_lib.resolve(metric)
    num_shards, n_s, d = sg.data.shape
    q = quantize_sq8_data(sg.data.reshape(-1, d), met)
    sg = dataclasses.replace(
        sg,
        qcodes=q.codes.reshape(num_shards, n_s, d),
        qscale=jnp.tile(q.scale[None, :], (num_shards, 1)),
        qnorms=q.norms.reshape(num_shards, n_s))
    return place_sharded(sg, mesh=mesh)


def quantize_sq8_data(data: jax.Array, metric) -> metric_lib.QuantizedData:
    """``Metric.prepare_quantized`` with a convenient string/Metric arg."""
    return metric_lib.resolve(metric).prepare_quantized(data)


def pytree_bytes(tree: Any) -> int:
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree)
               if hasattr(x, "size"))
