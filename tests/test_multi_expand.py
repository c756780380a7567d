"""Width-W multi-expansion search (DESIGN.md §10): sort-carried pool merge
byte-equivalence vs the stable argsort merge (adversarial ties / INVALID
padding), no gather in the merge, its NaN rule, the sort-free frontier pick
byte-equal to the top_k pick it replaced (alone and end to end), W=1 dense
bit-identity end to end, and W>1 recall parity at 10k across metrics and
visited impls."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import eval as evallib
from repro.core import knng, search
from repro.core.graph import INVALID, random_knng_ids

METRICS = ["l2", "ip", "cosine"]


def argsort_merge(pool_ids, pool_dist, expanded, cand_ids, cand_dist):
    """The pre-refactor merge: stable argsort over the full concatenation
    (pool first, candidates in flat order) — the byte-level oracle the
    top-k merge must reproduce."""
    ef_max = pool_ids.shape[-1]
    all_ids = jnp.concatenate([pool_ids, cand_ids], axis=-1)
    all_dist = jnp.concatenate([pool_dist, cand_dist], axis=-1)
    all_exp = jnp.concatenate(
        [expanded, jnp.zeros_like(cand_ids, bool)], axis=-1)
    order = jnp.argsort(all_dist, axis=-1)[..., :ef_max]
    return (jnp.take_along_axis(all_ids, order, axis=-1),
            jnp.take_along_axis(all_dist, order, axis=-1),
            jnp.take_along_axis(all_exp, order, axis=-1))


def topk_pick(pool_ids, expanded, slot_mask, row_mask, width):
    """The earlier frontier pick: ``lax.top_k`` of the negated unexpanded
    slot position, a ``take_along_axis`` read and a scatter — the byte-level
    oracle the sort-free pick must reproduce."""
    b, m, ef_max = pool_ids.shape
    unexp = (pool_ids != INVALID) & (~expanded) & slot_mask[None]
    slot_pos = jnp.where(unexp, jnp.arange(ef_max), ef_max)
    sel = -jax.lax.top_k(-slot_pos, width)[0]
    act = (sel < ef_max) & row_mask[:, None, None]
    sel_safe = jnp.minimum(sel, ef_max - 1)
    u = jnp.take_along_axis(pool_ids, sel_safe, axis=-1)
    u_safe = jnp.where(act, jnp.maximum(u, 0), 0)
    expanded = expanded.at[jnp.arange(b)[:, None, None],
                           jnp.arange(m)[None, :, None],
                           jnp.where(act, sel_safe, ef_max)].set(
        True, mode="drop")
    return act, u_safe, expanded


def _primitives(jaxpr):
    """Every primitive name in a jaxpr, nested sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if hasattr(sub, "eqns"):
                    yield from _primitives(sub)
                elif hasattr(sub, "jaxpr"):
                    yield from _primitives(sub.jaxpr)


def _random_pool(r, b, m, ef, n_valid, quant):
    """Sorted pool with an INVALID/inf tail and (optionally) heavy ties."""
    dist = np.sort(np.round(r.random((b, m, ef)) * quant) / quant, axis=-1)
    ids = r.integers(0, 10_000, size=(b, m, ef)).astype(np.int32)
    slot = np.arange(ef)[None, None, :]
    dist = np.where(slot < n_valid, dist, np.inf).astype(np.float32)
    ids = np.where(slot < n_valid, ids, INVALID).astype(np.int32)
    exp = (r.random((b, m, ef)) > 0.5) & (slot < n_valid)
    return jnp.asarray(ids), jnp.asarray(dist), jnp.asarray(exp)


def _random_cands(r, b, m, kx, p_invalid, quant):
    dist = np.round(r.random((b, m, kx)) * quant) / quant
    ids = r.integers(0, 10_000, size=(b, m, kx)).astype(np.int32)
    invalid = r.random((b, m, kx)) < p_invalid
    dist = np.where(invalid, np.inf, dist).astype(np.float32)
    ids = np.where(invalid, INVALID, ids).astype(np.int32)
    return jnp.asarray(ids), jnp.asarray(dist)


@pytest.mark.parametrize("ef,kx", [(8, 8), (16, 4), (16, 64), (32, 128),
                                   (1, 16),
                                   # the estimate's build (L_max 112 / 96)
                                   # and eval sweep (ef 80) at M_max 16,
                                   # and serving (ef 64, W=4 x M=32)
                                   (112, 16), (96, 16), (80, 16), (64, 128)])
@pytest.mark.parametrize("quant,p_invalid,n_valid_frac", [
    (4, 0.3, 1.0),      # massive ties, some INVALID candidates
    (1, 0.0, 1.0),      # EVERY distance identical: pure tie-order test
    (1000, 0.9, 0.25),  # mostly-INVALID candidates, mostly-padding pool
    (1000, 1.0, 0.5),   # all candidates INVALID
])
def test_topk_merge_byte_equals_argsort_merge(ef, kx, quant, p_invalid,
                                              n_valid_frac):
    r = np.random.default_rng(ef * 1000 + kx + quant)
    b, m = 5, 2
    n_valid = max(1, int(ef * n_valid_frac))
    pi, pd, pe = _random_pool(r, b, m, ef, n_valid, quant)
    ci, cd = _random_cands(r, b, m, kx, p_invalid, quant)
    got = search._merge_topk(pi, pd, pe, ci, cd)
    exp = argsort_merge(pi, pd, pe, ci, cd)
    for g, e, name in zip(got, exp, ("ids", "dist", "expanded")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(e),
                                      err_msg=f"merge {name} diverged")


def test_topk_merge_tie_priority_pool_wins():
    """A candidate at exactly a pool entry's distance must rank after it
    (the stable concat order), and tied candidates keep flat order."""
    pi = jnp.asarray([[[1, 2, 3]]], jnp.int32)
    pd = jnp.asarray([[[0.5, 0.5, jnp.inf]]], jnp.float32)
    pe = jnp.asarray([[[True, False, False]]])
    ci = jnp.asarray([[[7, 8]]], jnp.int32)
    cd = jnp.asarray([[[0.5, 0.5]]], jnp.float32)
    ids, dist, exp = search._merge_topk(pi, pd, pe, ci, cd)
    np.testing.assert_array_equal(np.asarray(ids), [[[1, 2, 7]]])
    np.testing.assert_array_equal(np.asarray(exp), [[[True, False, False]]])
    np.testing.assert_array_equal(np.asarray(dist), [[[0.5, 0.5, 0.5]]])


def test_merge_moves_no_payload_by_gather():
    """The merge carries ids, distances and flags through one sort: its
    jaxpr holds no gather (each cost ~1.3 ms a hop on the TPU)."""
    pool = (jnp.zeros((2, 4, 112), jnp.int32),
            jnp.zeros((2, 4, 112), jnp.float32),
            jnp.zeros((2, 4, 112), bool))
    cands = (jnp.zeros((2, 4, 16), jnp.int32),
             jnp.zeros((2, 4, 16), jnp.float32))
    jaxpr = jax.make_jaxpr(search._merge_topk)(*pool, *cands)
    prims = {e.primitive.name for e in jaxpr.jaxpr.eqns}
    assert "sort" in prims
    assert not {p for p in prims if "gather" in p}, prims


def test_merge_nan_rule():
    """A NaN pool distance keeps its slot ahead of every candidate; a NaN
    candidate, of either sign, never enters; finite candidates still do."""
    pi = jnp.asarray([[[5, INVALID, INVALID, INVALID]]], jnp.int32)
    pd = jnp.asarray([[[jnp.nan, jnp.inf, jnp.inf, jnp.inf]]], jnp.float32)
    pe = jnp.asarray([[[True, False, False, False]]])
    ci = jnp.asarray([[[7, 8, 9]]], jnp.int32)
    cd = jnp.asarray([[[jnp.nan, 0.5, -jnp.nan]]], jnp.float32)
    ids, dist, exp = search._merge_topk(pi, pd, pe, ci, cd)
    np.testing.assert_array_equal(np.asarray(ids),
                                  [[[5, 8, INVALID, INVALID]]])
    np.testing.assert_array_equal(np.asarray(exp),
                                  [[[True, False, False, False]]])
    np.testing.assert_array_equal(np.asarray(dist),
                                  [[[np.nan, 0.5, np.inf, np.inf]]])


@pytest.mark.parametrize("share", [0.0, 0.05, 0.5, 1.0])
@pytest.mark.parametrize("width", [1, 2, 4, 7])
def test_frontier_pick_byte_equals_topk_pick(width, share):
    """The min-reduction pick returns the top_k pick's lanes, ids and flags
    at the build's pool shape (64, 4, 112) for ``share`` of the live slots
    unexpanded, with fully expanded rows, INVALID tails, per-graph
    ``slot_mask``s shorter than ef_max and ``row_mask`` padding."""
    b, m, ef_max = 64, 4, 112
    r = np.random.default_rng(width * 100 + int(share * 100))
    slot = np.arange(ef_max)
    n_valid = r.integers(0, ef_max + 1, size=(b, m, 1))
    n_valid[:4] = ef_max                               # full pools
    ids = np.argsort(r.random((b, m, ef_max)), axis=-1).astype(np.int32)
    ids = np.where(slot < n_valid, ids, INVALID).astype(np.int32)
    exp = (r.random((b, m, ef_max)) >= share) & (slot < n_valid)
    exp[4:12] = slot < n_valid[4:12]                   # nothing unexpanded
    slot_mask = slot[None, :] < np.array([ef_max, 80, 33, 7])[:, None]
    row_mask = r.random(b) > 0.2
    row_mask[:2] = [True, False]
    args = (jnp.asarray(ids), jnp.asarray(exp), jnp.asarray(slot_mask),
            jnp.asarray(row_mask))
    got = search._frontier_pick(*args, width)
    ref = topk_pick(*args, width)
    for g, e, name in zip(got, ref, ("act", "u", "expanded")):
        assert g.dtype == e.dtype and g.shape == e.shape, name
        np.testing.assert_array_equal(np.asarray(g), np.asarray(e),
                                      err_msg=f"pick {name} diverged")
    if share == 1.0:
        assert np.asarray(got[0]).any()


def test_frontier_pick_holds_no_sort_gather_or_scatter():
    """The frontier pick is reductions only: its jaxpr holds no sort or
    top_k (the 3-D top_k cost ~354 µs a hop on the TPU), and no gather or
    scatter for the id read and the ``expanded`` update."""
    pool_ids = jnp.zeros((2, 4, 112), jnp.int32)
    expanded = jnp.zeros((2, 4, 112), bool)
    slot_mask = jnp.ones((4, 112), bool)
    row_mask = jnp.ones((2,), bool)
    for width in (1, 4):
        jaxpr = jax.make_jaxpr(search._frontier_pick, static_argnums=4)(
            pool_ids, expanded, slot_mask, row_mask, width)
        prims = set(_primitives(jaxpr.jaxpr))
        assert "reduce_min" in prims, prims
        banned = {p for p in prims
                  if any(w in p for w in ("sort", "top_k", "gather",
                                          "scatter"))}
        assert not banned, (width, banned)


@pytest.mark.parametrize("schedule", ["w1_eso_multigraph", "w4_hash"])
def test_search_bit_identical_to_topk_pick_reference(small_dataset,
                                                     schedule):
    """End to end, the sort-free pick returns the top_k pick's pools,
    distances, counters and hops: the build's W=1 multi-graph ESO search
    and serving's W=4 hash-mode search with padding rows."""
    data, queries = small_dataset
    adj, _ = knng.build_knng(data, 10)
    if schedule == "w1_eso_multigraph":
        pad = jnp.full((adj.shape[0], 4), INVALID, jnp.int32)
        g2 = jnp.stack([adj, jnp.concatenate([adj[:, :6], pad], axis=1)])
        b = 8
        args = (g2, data, queries[:b], jnp.full((b,), INVALID, jnp.int32),
                jnp.ones((b,), bool), jnp.array([15, 10], jnp.int32),
                jnp.zeros((b, 2), jnp.int32))

        def run():
            return search.beam_search(*args, ef_max=15, max_hops=60,
                                      share_cache=True)
    else:
        row_mask = jnp.arange(queries.shape[0]) < queries.shape[0] - 5

        def run():
            return search.knn_search(adj, data, queries, 10, 32, 0,
                                     visited_impl="hash", expand_width=4,
                                     row_mask=row_mask)
    new = run()
    orig = search._frontier_pick
    search._frontier_pick = topk_pick
    search.beam_search.clear_cache()
    try:
        ref = run()
    finally:
        search._frontier_pick = orig
        search.beam_search.clear_cache()
    np.testing.assert_array_equal(np.asarray(new.pool_ids),
                                  np.asarray(ref.pool_ids))
    np.testing.assert_array_equal(np.asarray(new.pool_dist),
                                  np.asarray(ref.pool_dist))
    for name in ("n_fresh", "n_computed", "hops"):
        assert int(getattr(new, name)) == int(getattr(ref, name)), name
    assert int(new.hops) > 1


@pytest.mark.parametrize("m", [1, 2])
def test_nan_search_ends_at_first_hop_keeping_entry(m):
    """On NaN data and queries no distance enters a pool: every search ends
    at its first hop with the entry alone in slot 0 (a warm-up on NaN data
    compiles every program and does no more work than that)."""
    n, b, mx = 200, 8, 8
    data = jnp.full((n, 16), jnp.nan, jnp.float32)
    queries = jnp.full((b, 16), jnp.nan, jnp.float32)
    g = jnp.stack([random_knng_ids(i, n, mx) for i in range(m)])
    entry = jnp.tile(jnp.arange(3, 3 + m, dtype=jnp.int32), (b, 1))
    res = search.beam_search(
        g, data, queries, jnp.full((b,), INVALID, jnp.int32),
        jnp.ones((b,), bool), jnp.full((m,), 16, jnp.int32), entry,
        ef_max=16, max_hops=40, share_cache=m > 1)
    assert int(res.hops) == 1
    ids = np.asarray(res.pool_ids)
    np.testing.assert_array_equal(ids[:, :, 0], np.asarray(entry))
    assert np.all(ids[:, :, 1:] == INVALID)
    assert np.all(np.isnan(np.asarray(res.pool_dist)[:, :, 0]))


def test_w1_dense_search_bit_identical_to_argsort_reference(small_dataset):
    """W=1 dense search under the top-k merge returns byte-identical pools
    AND exact counters vs the pre-refactor full-argsort merge — the
    no-regression contract on the paper-exact path."""
    data, queries = small_dataset
    adj, _ = knng.build_knng(data, 12)
    new = search.knn_search(adj, data, queries, 10, 30, 0)
    orig = search._merge_topk
    search._merge_topk = argsort_merge
    search.beam_search.clear_cache()
    try:
        ref = search.knn_search(adj, data, queries, 10, 30, 0)
    finally:
        search._merge_topk = orig
        search.beam_search.clear_cache()
    np.testing.assert_array_equal(np.asarray(new.pool_ids),
                                  np.asarray(ref.pool_ids))
    np.testing.assert_array_equal(np.asarray(new.pool_dist),
                                  np.asarray(ref.pool_dist))
    assert int(new.n_fresh) == int(ref.n_fresh)
    assert int(new.n_computed) == int(ref.n_computed)
    assert int(new.hops) == int(ref.hops)


def test_w1_multigraph_eso_bit_identical_to_argsort_reference(small_dataset):
    """Same byte-identity contract on the multi-graph ESO builder path."""
    data, queries = small_dataset
    adj, _ = knng.build_knng(data, 10)
    pad = jnp.full((adj.shape[0], 4), INVALID, jnp.int32)
    g2 = jnp.stack([adj, jnp.concatenate([adj[:, :6], pad], axis=1)])
    b = 8
    args = (g2, data, queries[:b], jnp.full((b,), INVALID, jnp.int32),
            jnp.ones((b,), bool), jnp.array([15, 10], jnp.int32),
            jnp.zeros((b, 2), jnp.int32))
    kw = dict(ef_max=15, max_hops=60, share_cache=True)
    new = search.beam_search(*args, **kw)
    orig = search._merge_topk
    search._merge_topk = argsort_merge
    search.beam_search.clear_cache()
    try:
        ref = search.beam_search(*args, **kw)
    finally:
        search._merge_topk = orig
        search.beam_search.clear_cache()
    np.testing.assert_array_equal(np.asarray(new.pool_ids),
                                  np.asarray(ref.pool_ids))
    np.testing.assert_array_equal(np.asarray(new.pool_dist),
                                  np.asarray(ref.pool_dist))
    assert int(new.n_computed) == int(ref.n_computed)
    assert int(new.n_fresh) == int(ref.n_fresh)


@pytest.mark.parametrize("impl", ["dense", "hash"])
@pytest.mark.parametrize("metric", METRICS)
def test_expand_width_recall_parity_10k(metric, impl):
    """Acceptance: W=4 recall@k within tolerance of W=1 on 10k points,
    with the hop count dropping (the latency the width buys)."""
    n, d, b, k, ef = 10_000, 16, 32, 10, 32
    r = np.random.default_rng(11)
    data = jnp.asarray(r.normal(size=(n, d)), jnp.float32)
    queries = data[:b] + 0.1 * jnp.asarray(r.normal(size=(b, d)),
                                           jnp.float32)
    adj = random_knng_ids(1, n, 16)
    gt = evallib.ground_truth(data, queries, k, metric=metric)
    r1 = search.knn_search(adj, data, queries, k, ef, 0, metric=metric,
                           visited_impl=impl, expand_width=1)
    r4 = search.knn_search(adj, data, queries, k, ef, 0, metric=metric,
                           visited_impl=impl, expand_width=4)
    rec1 = evallib.recall_at_k(r1.pool_ids[:, :k], gt)
    rec4 = evallib.recall_at_k(r4.pool_ids[:, :k], gt)
    assert rec4 >= rec1 - 0.02, (rec1, rec4)
    assert int(r4.hops) < int(r1.hops)
    # the W-wide schedule may overshoot the sequential #dist, never undershoot
    # by more than the tail effect; pin the deterministic workload
    assert int(r4.n_computed) >= int(r1.n_computed)


def test_expand_width_pools_stay_sorted_and_duplicate_free(small_dataset):
    data, queries = small_dataset
    adj, _ = knng.build_knng(data, 12)
    for impl in ("dense", "hash"):
        res = search.knn_search(adj, data, queries, 10, 24, 0,
                                visited_impl=impl, expand_width=3)
        dist = np.asarray(res.pool_dist)
        assert np.all(np.diff(dist, axis=-1) >= 0), "pool not sorted"
        for row in np.asarray(res.pool_ids):
            real = [x for x in row.tolist() if x >= 0]
            assert len(real) == len(set(real)), "duplicate ids in pool"


def test_expand_width_clamps_to_ef():
    """W > ef cannot expand more than the pool holds — clamped, not an
    error (the HNSW descent calls with ef_max=1)."""
    r = np.random.default_rng(0)
    data = jnp.asarray(r.normal(size=(300, 8)), jnp.float32)
    adj = random_knng_ids(0, 300, 8)
    a = search.knn_search(adj, data, data[:4], 2, 4, 0, expand_width=64)
    b = search.knn_search(adj, data, data[:4], 2, 4, 0, expand_width=4)
    np.testing.assert_array_equal(np.asarray(a.pool_ids),
                                  np.asarray(b.pool_ids))


def test_expand_width_rejected_below_one(small_dataset):
    data, queries = small_dataset
    adj, _ = knng.build_knng(data, 10)
    with pytest.raises(ValueError, match="expand_width"):
        search.knn_search(adj, data, queries, 5, 10, 0, expand_width=0)


def test_node_zero_after_invalid_padding_is_not_dropped():
    """In-hop dedup must compare raw ids: clamping INVALID to 0 would alias
    padding with a genuine id-0 candidate arriving later in the W·Mx
    window and silently drop it (regression for the widened window)."""
    r = np.random.default_rng(5)
    n, d = 64, 4
    data = jnp.asarray(r.normal(size=(n, d)), jnp.float32)
    # entry 1's adjacency: an INVALID pad lane BEFORE the node-0 lane
    adj = jnp.full((n, 4), INVALID, jnp.int32)
    adj = adj.at[1].set(jnp.array([2, INVALID, 0, 3], jnp.int32))
    adj = adj.at[0].set(jnp.array([1, 2, 3, INVALID], jnp.int32))
    q = data[0][None] * 0.9            # node 0 is the closest neighbor
    res = search.knn_search(adj, data, q, 4, 8, 1)
    ids = set(np.asarray(res.pool_ids[0]).tolist())
    assert 0 in ids, "id-0 candidate was dropped as a padding duplicate"
