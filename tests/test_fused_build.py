"""Fused build (DESIGN.md §12): at test scale build_impl="fused" must be
bit-identical to "per_batch" — graphs AND counters — because the fused
step traces the very functions the per_batch loop dispatches.  (At large
n the staged path's eager prune-stage reduction admits a ppm-bounded FP
tie deviation; benchmarks/build_bench.py asserts that bound.)  Also pins
the dispatch contract: one compiled dispatch per fused batch step
(Vamana: per pass), versus 1 + 2m jitted dispatches per batch on the
host loop."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build as build_lib
from repro.core import commit, hnsw, nsg, prune, search, vamana

METRICS = ("l2", "ip", "cosine")


def _data(n=180, d=10, seed=3):
    r = np.random.default_rng(seed)
    return jnp.asarray(r.normal(size=(n, d)), jnp.float32)


VAMANA_PS = [vamana.VamanaParams(L=16, M=8, alpha=1.1),
             vamana.VamanaParams(L=20, M=8, alpha=1.3)]
HNSW_PS = [hnsw.HNSWParams(efc=16, M=8), hnsw.HNSWParams(efc=20, M=8)]
NSG_PS = [nsg.NSGParams(K=8, L=16, M=8), nsg.NSGParams(K=10, L=20, M=8)]


def _assert_graphs_equal(ids_a, dist_a, ids_b, dist_b):
    np.testing.assert_array_equal(np.asarray(ids_a), np.asarray(ids_b))
    da, db = np.asarray(dist_a), np.asarray(dist_b)
    np.testing.assert_array_equal(np.isinf(da), np.isinf(db))
    np.testing.assert_array_equal(da[~np.isinf(da)], db[~np.isinf(db)])


def _pair(builder, ps, **kw):
    a = builder(_data(), ps, batch_size=64, build_impl="per_batch", **kw)
    b = builder(_data(), ps, batch_size=64, build_impl="fused", **kw)
    assert a.counters.as_dict() == b.counters.as_dict()
    return a, b


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("visited_impl", ("dense", "hash"))
@pytest.mark.parametrize("sharing", (True, False))
def test_vamana_fused_identity(metric, visited_impl, sharing):
    a, b = _pair(vamana.build_multi_vamana, VAMANA_PS, metric=metric,
                 visited_impl=visited_impl, use_eso=sharing, use_epo=sharing)
    _assert_graphs_equal(a.g.ids, a.g.dist, b.g.ids, b.g.dist)
    # the lockstep beam's counters are part of the compared counters
    c = a.counters
    assert c.batches == 3 and c.hops > 0
    assert 0 < c.expansions <= c.lane_hops


@pytest.mark.parametrize("metric", METRICS)
def test_hnsw_fused_identity(metric):
    a, b = _pair(hnsw.build_multi_hnsw, HNSW_PS, metric=metric)
    _assert_graphs_equal(a.g.layer_ids, a.g.layer_dist,
                         b.g.layer_ids, b.g.layer_dist)
    assert a.g.entry == b.g.entry and a.g.top == b.g.top


@pytest.mark.parametrize("metric", METRICS)
def test_nsg_fused_identity(metric):
    a, b = _pair(nsg.build_multi_nsg, NSG_PS, metric=metric)
    _assert_graphs_equal(a.g.ids, a.g.dist, b.g.ids, b.g.dist)
    assert a.entry == b.entry


@pytest.mark.slow
@pytest.mark.parametrize("visited_impl", ("dense", "hash"))
@pytest.mark.parametrize("sharing", (True, False))
def test_hnsw_nsg_fused_identity_thorough(visited_impl, sharing):
    a, b = _pair(hnsw.build_multi_hnsw, HNSW_PS, visited_impl=visited_impl,
                 use_eso=sharing, use_epo=sharing)
    _assert_graphs_equal(a.g.layer_ids, a.g.layer_dist,
                         b.g.layer_ids, b.g.layer_dist)
    a, b = _pair(nsg.build_multi_nsg, NSG_PS, visited_impl=visited_impl,
                 use_eso=sharing, use_epo=sharing)
    _assert_graphs_equal(a.g.ids, a.g.dist, b.g.ids, b.g.dist)


def test_resolve_build_impl_rejects_unknown():
    with pytest.raises(ValueError, match="build_impl"):
        build_lib.resolve_build_impl("bogus")
    with pytest.raises(ValueError, match="build_impl"):
        vamana.build_multi_vamana(_data(64), VAMANA_PS, build_impl="eager")


# ---- dispatch-count contract (DESIGN.md §12) -------------------------------

# Every module-level jitted callable a build can invoke from Python.
_TARGETS = ((search, "beam_search"), (prune, "rng_prune"),
            (commit, "add_reverse_edges"), (build_lib, "insert_batch"),
            (build_lib, "nsg_insert_batch"),
            (build_lib, "fused_vamana_pass"))


class _Counting:
    def __init__(self, fn):
        self.fn, self.calls = fn, 0

    def __call__(self, *a, **kw):
        self.calls += 1
        return self.fn(*a, **kw)


def _count_dispatches(fn):
    """Python-level jitted-callable invocations during fn() (call only
    after a warmup run: tracing itself invokes wrapped inner names)."""
    shims = [(mod, name, _Counting(getattr(mod, name)))
             for mod, name in _TARGETS]
    for mod, name, shim in shims:
        setattr(mod, name, shim)
    try:
        fn()
    finally:
        for mod, name, shim in shims:
            setattr(mod, name, shim.fn)
    return {name: shim.calls for _, name, shim in shims}


def test_fused_vamana_dispatch_pin():
    """A warmed fused Vamana build is ONE jitted dispatch for the whole
    insertion pass — and zero Python-level calls to the stage functions
    (they are traced into the fused program, not dispatched)."""
    data = _data()

    def build(impl):
        return vamana.build_multi_vamana(data, VAMANA_PS, batch_size=64,
                                         build_impl=impl)

    build("fused")                                   # warmup/compile
    cache_size = getattr(build_lib.fused_vamana_pass, "_cache_size",
                         lambda: None)()
    counts = _count_dispatches(lambda: build("fused"))
    assert counts == {"beam_search": 0, "rng_prune": 0,
                      "add_reverse_edges": 0, "insert_batch": 0,
                      "nsg_insert_batch": 0, "fused_vamana_pass": 1}
    if cache_size is not None:                       # compile-count audit
        assert build_lib.fused_vamana_pass._cache_size() == cache_size


def test_per_batch_dispatch_structure():
    """The host loop's measured structure: 1 search + m prunes + m reverse
    commits per batch — the 1 + 2m dispatches the fused path collapses."""
    data = _data()        # n=180, batch 64 -> 3 batches, m=2
    n_batches, m = 3, len(VAMANA_PS)

    def build():
        return vamana.build_multi_vamana(data, VAMANA_PS, batch_size=64,
                                         build_impl="per_batch")

    build()                                          # warmup/compile
    counts = _count_dispatches(build)
    assert counts == {"beam_search": n_batches, "rng_prune": n_batches * m,
                      "add_reverse_edges": n_batches * m, "insert_batch": 0,
                      "nsg_insert_batch": 0, "fused_vamana_pass": 0}


def test_insert_batch_single_dispatch():
    """One fused batch step (the HNSW-shaped entry point) invokes no other
    jitted callable after warmup, and reuses its compiled program."""
    m, n, d, b, m_max = 2, 96, 8, 16, 8
    r = np.random.default_rng(5)
    data = jnp.asarray(r.normal(size=(n, d)), jnp.float32)
    gids = jnp.full((m, n, m_max), -1, jnp.int32)
    gdist = jnp.full((m, n, m_max), jnp.inf, jnp.float32)
    u = jnp.arange(b, dtype=jnp.int32)
    row_mask = jnp.ones((b,), bool)
    kw = dict(ef_max=16, max_hops=8, share_cache=True, use_epo=True,
              metric="l2", visited_impl="dense", expand_width=1, k_in=8,
              m_max=m_max)

    def step():
        return build_lib.insert_batch(
            gids, gdist, data, u, row_mask, data[u],
            jnp.array([8, 8], jnp.int32), jnp.array([4, 4], jnp.int32),
            jnp.array([1.0, 1.1], jnp.float32),
            jnp.zeros((b, m), jnp.int32), None, None, **kw)

    jnp.asarray(step()[0]).block_until_ready()       # warmup/compile
    cache_size = getattr(build_lib.insert_batch, "_cache_size",
                         lambda: None)()
    counts = _count_dispatches(step)
    assert counts["beam_search"] == 0
    assert counts["rng_prune"] == 0
    assert counts["add_reverse_edges"] == 0
    if cache_size is not None:
        assert build_lib.insert_batch._cache_size() == cache_size
