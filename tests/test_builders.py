"""PG builders: multi == single invariance (core paper claim: ESO/EPO are
pure optimizations), recall quality, and counter reductions."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import eval as evallib
from repro.core import graph, hnsw, nsg, vamana


@pytest.fixture(scope="module")
def ds():
    r = np.random.default_rng(11)
    data = jnp.asarray(r.normal(size=(600, 12)), jnp.float32)
    queries = jnp.asarray(r.normal(size=(30, 12)), jnp.float32)
    gt = evallib.ground_truth(data, queries, 10)
    return data, queries, gt


def test_multi_equals_single_fast():
    """Small-n sharing-invariance guard that stays in the CI fast lane
    (the thorough variants below are slow-marked)."""
    r = np.random.default_rng(21)
    data = jnp.asarray(r.normal(size=(250, 8)), jnp.float32)
    ps = [vamana.VamanaParams(L=16, M=8, alpha=1.1),
          vamana.VamanaParams(L=20, M=8, alpha=1.2)]
    multi = vamana.build_multi_vamana(data, ps, seed=3, batch_size=128)
    for i, p in enumerate(ps):
        single = vamana.build_multi_vamana(data, [p], seed=3, batch_size=128,
                                           use_eso=False, use_epo=False)
        np.testing.assert_array_equal(
            np.asarray(multi.g.ids[i])[:, :p.M],
            np.asarray(single.g.ids[0])[:, :p.M])


def test_random_knng_prefix_stable():
    """A smaller initial degree takes a prefix of the same rows."""
    wide = np.asarray(graph.random_knng_ids(3, 100, 16))
    np.testing.assert_array_equal(
        wide[:, :8], np.asarray(graph.random_knng_ids(3, 100, 8)))
    assert not np.any(wide == np.arange(100)[:, None])


def test_multi_equals_single_across_degree_buckets():
    """A group member whose M pads to a smaller degree bucket alone (M=8
    -> 8) than in its group (-> 16, for M=12) still builds the same graph:
    the tuner's grouped estimates then equal its single ones."""
    r = np.random.default_rng(22)
    data = jnp.asarray(r.normal(size=(250, 8)), jnp.float32)
    ps = [vamana.VamanaParams(L=16, M=8, alpha=1.1),
          vamana.VamanaParams(L=20, M=12, alpha=1.3)]
    multi = vamana.build_multi_vamana(data, ps, seed=3, batch_size=128)
    for i, p in enumerate(ps):
        single = vamana.build_vamana(data, p, seed=3, batch_size=128)
        np.testing.assert_array_equal(
            np.asarray(multi.g.ids[i])[:, :p.M],
            np.asarray(single.g.ids[0])[:, :p.M])


def test_entry_inserted_last_keeps_graph_connected(ds):
    """The entry (medoid) inserted in the last batch: its own insertion
    searches from itself, and that search must expand its neighbourhood.
    An empty pool there wiped the entry's out-edges, leaving only the
    reverse edges of later rows — at scale, every row inserted before the
    entry was cut off from it."""
    data, _, _ = ds
    n, batch = data.shape[0], 128
    ep = int(graph.medoid(data))
    order = np.r_[np.delete(np.arange(n), ep), ep]
    x = data[order]
    res = vamana.build_vamana(x, vamana.VamanaParams(32, 12, 1.2),
                              batch_size=batch)
    assert res.entry == n - 1
    out = np.asarray(res.g.ids[0])[res.entry]
    out = out[out >= 0]
    d = np.sum((np.asarray(x) - np.asarray(x)[res.entry]) ** 2, axis=1)
    d[res.entry] = np.inf
    assert res.entry not in out
    assert int(np.argmin(d)) in out          # its nearest neighbour
    assert np.any(out < (n - 1) // batch * batch)   # rows of earlier batches


@pytest.mark.slow
def test_multi_vamana_equals_singles(ds):
    """Graph i of a shared multi-build must be IDENTICAL to building
    parameter i alone — sharing must never change results."""
    data, _, _ = ds
    ps = [vamana.VamanaParams(L=24, M=10, alpha=1.1),
          vamana.VamanaParams(L=32, M=12, alpha=1.3)]
    multi = vamana.build_multi_vamana(data, ps, seed=5, batch_size=128)
    for i, p in enumerate(ps):
        single = vamana.build_multi_vamana(data, [p], seed=5, batch_size=128,
                                           use_eso=False, use_epo=False)
        np.testing.assert_array_equal(
            np.asarray(multi.g.ids[i])[:, :p.M],
            np.asarray(single.g.ids[0])[:, :p.M])


def test_multi_vamana_counter_savings(ds):
    data, _, _ = ds
    ps = [vamana.VamanaParams(L=24, M=10, alpha=1.1),
          vamana.VamanaParams(L=28, M=12, alpha=1.2),
          vamana.VamanaParams(L=32, M=12, alpha=1.3)]
    shared = vamana.build_multi_vamana(data, ps, seed=5, batch_size=128)
    assert shared.counters.search < shared.counters.search_base
    assert shared.counters.prune <= shared.counters.prune_base
    assert shared.counters.total < shared.counters.total_base


@pytest.mark.parametrize("builder,params,searcher", [
    ("vamana", vamana.VamanaParams(L=48, M=16, alpha=1.2), None),
    ("nsg", nsg.NSGParams(K=16, L=48, M=16), None),
    ("hnsw", hnsw.HNSWParams(efc=48, M=16), None),
])
def test_builder_recall(ds, builder, params, searcher):
    data, queries, gt = ds
    if builder == "vamana":
        res = vamana.build_multi_vamana(data, [params], batch_size=128)
        fn = evallib.flat_graph_search_fn(res.g, 0, data, res.entry, 10)
        got = fn(queries, 60).pool_ids[:, :10]
    elif builder == "nsg":
        res = nsg.build_multi_nsg(data, [params], batch_size=128)
        fn = evallib.flat_graph_search_fn(res.g, 0, data, res.entry, 10)
        got = fn(queries, 60).pool_ids[:, :10]
    else:
        res = hnsw.build_multi_hnsw(data, [params], batch_size=128)
        got = hnsw.hnsw_search(res.g, 0, data, queries, 10, 60).pool_ids
    rec = evallib.recall_at_k(got, gt)
    assert rec > 0.80, f"{builder} recall {rec}"


@pytest.mark.slow
def test_hnsw_shared_levels_and_multi(ds):
    data, queries, gt = ds
    ps = [hnsw.HNSWParams(efc=32, M=12), hnsw.HNSWParams(efc=48, M=16)]
    multi = hnsw.build_multi_hnsw(data, ps, seed=2, batch_size=128)
    assert multi.counters.search < multi.counters.search_base
    # levels identical across graphs by construction (deterministic random)
    for gi in range(2):
        got = hnsw.hnsw_search(multi.g, gi, data, queries, 10, 60).pool_ids
        assert evallib.recall_at_k(got, gt) > 0.75


def test_nsg_connectivity_repair(ds):
    data, queries, gt = ds
    res = nsg.build_multi_nsg(data, [nsg.NSGParams(K=12, L=32, M=10)],
                              batch_size=128)
    # after repair, searches from the medoid must reach >90% of gt space
    fn = evallib.flat_graph_search_fn(res.g, 0, data, res.entry, 10)
    rec = evallib.recall_at_k(fn(queries, 80).pool_ids[:, :10], gt)
    assert rec > 0.7


@pytest.mark.slow
def test_multi_vamana_equals_singles_cosine(ds):
    """Sharing invariance must hold under every metric, not just L2."""
    data, _, _ = ds
    ps = [vamana.VamanaParams(L=24, M=10, alpha=1.1),
          vamana.VamanaParams(L=32, M=12, alpha=1.3)]
    multi = vamana.build_multi_vamana(data, ps, seed=5, batch_size=128,
                                      metric="cosine")
    for i, p in enumerate(ps):
        single = vamana.build_multi_vamana(data, [p], seed=5, batch_size=128,
                                           use_eso=False, use_epo=False,
                                           metric="cosine")
        np.testing.assert_array_equal(
            np.asarray(multi.g.ids[i])[:, :p.M],
            np.asarray(single.g.ids[0])[:, :p.M])


@pytest.mark.parametrize("metric", ["cosine", "ip"])
def test_builder_recall_other_metrics(ds, metric):
    data, queries, _ = ds
    gt = evallib.ground_truth(data, queries, 10, metric=metric)
    res = vamana.build_multi_vamana(
        data, [vamana.VamanaParams(L=48, M=16, alpha=1.2)],
        batch_size=128, metric=metric)
    fn = evallib.flat_graph_search_fn(res.g, 0, data, res.entry, 10, metric)
    rec = evallib.recall_at_k(fn(queries, 60).pool_ids[:, :10], gt)
    floor = 0.9 if metric == "cosine" else 0.6   # raw MIPS graphs are hubby
    assert rec > floor, f"vamana/{metric} recall {rec}"


@pytest.mark.slow
def test_hnsw_cosine_reaches_recall_target(ds):
    """Acceptance: an HNSW built on a cosine-metric dataset reaches
    recall@10 >= 0.9 at some ef in the default eval grid [10, 20, 40, 80]."""
    data, queries, _ = ds
    gt = evallib.ground_truth(data, queries, 10, metric="cosine")
    res = hnsw.build_multi_hnsw(data, [hnsw.HNSWParams(efc=48, M=16)],
                                batch_size=128, metric="cosine")
    recs = []
    for ef in [10, 20, 40, 80]:
        got = hnsw.hnsw_search(res.g, 0, data, queries, 10, ef,
                               metric="cosine").pool_ids
        recs.append(evallib.recall_at_k(got, gt))
    assert max(recs) >= 0.9, f"cosine hnsw recall sweep {recs}"
