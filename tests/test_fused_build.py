"""Build driver (DESIGN.md §12): every builder runs the compiled step of
core/build.py.  Two facts pin it to the stage functions it traces:
(1) ``fused_vamana_pass`` is bit-identical — graphs AND counter rows — to a
host loop that dispatches the jitted ``insert_batch`` once per batch (the
``fori_loop`` adds nothing); (2) one jitted step (``insert_batch`` /
``nsg_insert_batch``) on a mid-build state is bit-identical to calling
``search.beam_search`` → ``prune.multi_prune`` → ``commit.commit_group`` in
order on the same state.  Also pins the dispatch contract: one compiled
dispatch per batch step (Vamana: per pass), no stage-function dispatch,
and no recompile on a second build."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build as build_lib
from repro.core import commit, counters, hnsw, nsg, prune, search, vamana
from repro.core.graph import INVALID
from repro.core.tuner import estimator
from repro.serve import retrieval

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


def _host_loop_pass(graph_ids, graph_dist, data, L, M, alpha, ep, *,
                    batch_size, **kw):
    """``fused_vamana_pass``'s contract driven from the host: one jitted
    ``insert_batch`` dispatch per batch, counter rows stacked."""
    n, m = data.shape[0], graph_ids.shape[0]
    rows = []
    for off in range(0, n, batch_size):
        u = off + jnp.arange(batch_size, dtype=jnp.int32)
        row_mask = u < n
        u = jnp.where(row_mask, u, n)
        entry = jnp.broadcast_to(ep.astype(jnp.int32), (batch_size, m))
        graph_ids, graph_dist, row, _, _, _ = build_lib.insert_batch(
            graph_ids, graph_dist, data, u, row_mask,
            data[jnp.minimum(u, n - 1)], L, M, alpha, entry, None, None,
            **kw)
        rows.append(row)
    return graph_ids, graph_dist, jnp.stack(rows)


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("visited_impl", ("dense", "hash"))
@pytest.mark.parametrize("sharing", (True, False))
def test_vamana_fused_identity(monkeypatch, metric, visited_impl, sharing):
    kw = dict(batch_size=64, metric=metric, visited_impl=visited_impl,
              use_eso=sharing, use_epo=sharing)
    logs = {}

    def recording(name, fn):
        def run(*a, **k):
            out = fn(*a, **k)
            logs[name] = np.asarray(out[2])
            return out
        return run

    monkeypatch.setattr(build_lib, "fused_vamana_pass",
                        recording("fused", build_lib.fused_vamana_pass))
    a = vamana.build_multi_vamana(_data(), VAMANA_PS, **kw)
    monkeypatch.setattr(build_lib, "fused_vamana_pass",
                        recording("host", _host_loop_pass))
    b = vamana.build_multi_vamana(_data(), VAMANA_PS, **kw)
    _assert_graphs_equal(a.g.ids, a.g.dist, b.g.ids, b.g.dist)
    np.testing.assert_array_equal(logs["fused"], logs["host"])
    assert logs["fused"].shape == (3, len(counters.TAPE_FIELDS))
    assert a.counters.as_dict() == b.counters.as_dict()
    # the lockstep beam's counters are part of the compared rows
    c = a.counters
    assert c.batches == 3 and c.hops > 0
    assert 0 < c.expansions <= c.lane_hops


def _capture_step(monkeypatch, name, build):
    """The arguments of the builder's last dispatch of ``build_lib.<name>``
    — a real mid-build state, with the graphs full enough that reverse
    edges overflow and re-prune."""
    seen = []
    fn = getattr(build_lib, name)

    def spy(*a, **k):
        seen.append((a, k))
        return fn(*a, **k)

    monkeypatch.setattr(build_lib, name, spy)
    build()
    monkeypatch.setattr(build_lib, name, fn)
    assert len(seen) > 1
    return seen[-1]


def _staged_tail(res, data, graph_ids, graph_dist, u, row_mask, cand_ids,
                 cand_dist, M, alpha, kw):
    """Prune and commit a step's sorted candidates; its counter row."""
    pruned, nb, nc = prune.multi_prune(
        data, cand_ids, cand_dist, cand_ids != INVALID, M, alpha,
        m_max=kw["m_max"], use_epo=kw["use_epo"], metric=kw["metric"])
    new_ids, new_dist, rev = commit.commit_group(
        data, graph_ids, graph_dist, u, pruned, row_mask, M, alpha,
        k_in=kw["k_in"], m_max=kw["m_max"], metric=kw["metric"])
    row = counters.step_row(res, row_mask, kw["expand_width"], nb + rev,
                            nc + rev)
    return new_ids, new_dist, row


def _search_kw(kw):
    return {k: kw[k] for k in ("ef_max", "max_hops", "share_cache", "metric",
                               "visited_impl", "expand_width")}


def _staged_insert(graph_ids, graph_dist, data, u, row_mask, queries, L, M,
                   alpha, entry, cache_d, cache_has, **kw):
    """One Vamana/HNSW batch by the stage functions, called in order."""
    res = search.beam_search(
        graph_ids, data, queries, jnp.where(row_mask, u, INVALID), row_mask,
        L, entry, cache_d, cache_has, **_search_kw(kw))
    cand_ids = jnp.transpose(res.pool_ids, (1, 0, 2))
    cand_dist = jnp.transpose(res.pool_dist, (1, 0, 2))
    return _staged_tail(res, data, graph_ids, graph_dist, u, row_mask,
                        cand_ids, cand_dist, M, alpha, kw) + (
        res.pool_ids[:, :, 0], res.cache_d, res.cache_has)


def _staged_nsg(search_ids, graph_ids, graph_dist, knn_ids, knn_dist, data,
                u, row_mask, queries, L, M, alpha, K, entry, *, k_max, **kw):
    """One NSG batch: search the static KNNG, merge the node's own KNNG row
    (sort by distance, drop later duplicates), prune, commit."""
    n, m = data.shape[0], graph_ids.shape[0]
    res = search.beam_search(
        search_ids, data, queries, jnp.where(row_mask, u, INVALID), row_mask,
        L, entry, **_search_kw(kw))
    own = knn_ids[jnp.minimum(u, n - 1)]
    keep = (jnp.arange(k_max)[None, None, :] < K[:, None, None]) \
        & row_mask[None, :, None]
    own_ids = jnp.where(keep, jnp.broadcast_to(own[None], (m,) + own.shape),
                        INVALID)
    own_dist = jnp.where(own_ids != INVALID,
                         knn_dist[jnp.minimum(u, n - 1)][None], jnp.inf)
    ids = jnp.concatenate([jnp.transpose(res.pool_ids, (1, 0, 2)), own_ids],
                          -1)
    dist = jnp.concatenate([jnp.transpose(res.pool_dist, (1, 0, 2)),
                            own_dist], -1)
    srt = jnp.argsort(dist, axis=-1)
    ids = jnp.take_along_axis(ids, srt, -1)
    dist = jnp.take_along_axis(dist, srt, -1)
    c = ids.shape[-1]
    dup = jnp.any((ids[..., None, :] == ids[..., :, None])
                  & jnp.tril(jnp.ones((c, c), bool), k=-1), axis=-1)
    return _staged_tail(res, data, graph_ids, graph_dist, u, row_mask,
                        jnp.where(dup, INVALID, ids),
                        jnp.where(dup, jnp.inf, dist), M, alpha, kw)


def _assert_steps_equal(got, want):
    assert len(got) == len(want)
    _assert_graphs_equal(got[0], got[1], want[0], want[1])
    for x, y in zip(got[2:], want[2:]):
        if x is None or y is None:
            assert x is None and y is None
        else:
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _hnsw_step_identity(monkeypatch, **kw):
    a, k = _capture_step(monkeypatch, "insert_batch", lambda:
                         hnsw.build_multi_hnsw(_data(), HNSW_PS,
                                               batch_size=64, **kw))
    _assert_steps_equal(build_lib.insert_batch(*a, **k),
                        _staged_insert(*a, **k))


def _nsg_step_identity(monkeypatch, **kw):
    a, k = _capture_step(monkeypatch, "nsg_insert_batch", lambda:
                         nsg.build_multi_nsg(_data(), NSG_PS,
                                             batch_size=64, **kw))
    _assert_steps_equal(build_lib.nsg_insert_batch(*a, **k),
                        _staged_nsg(*a, **k))


@pytest.mark.parametrize("metric", METRICS)
def test_hnsw_fused_identity(monkeypatch, metric):
    _hnsw_step_identity(monkeypatch, metric=metric)


@pytest.mark.parametrize("metric", METRICS)
def test_nsg_fused_identity(monkeypatch, metric):
    _nsg_step_identity(monkeypatch, metric=metric)


@pytest.mark.slow
@pytest.mark.parametrize("visited_impl", ("dense", "hash"))
@pytest.mark.parametrize("sharing", (True, False))
def test_hnsw_nsg_fused_identity_thorough(monkeypatch, visited_impl,
                                          sharing):
    kw = dict(visited_impl=visited_impl, use_eso=sharing, use_epo=sharing)
    _hnsw_step_identity(monkeypatch, **kw)
    _nsg_step_identity(monkeypatch, **kw)


def test_resolve_build_impl_rejects_unknown():
    assert build_lib.BUILD_IMPLS == ("fused",)
    assert build_lib.resolve_build_impl("fused") == "fused"
    with pytest.raises(ValueError, match="build_impl"):
        build_lib.resolve_build_impl("bogus")
    # the builders take no driver option at all
    with pytest.raises(TypeError, match="build_impl"):
        vamana.build_multi_vamana(_data(64), VAMANA_PS, build_impl="fused")


@pytest.mark.parametrize("entry", ("estimate", "build_index"))
def test_build_impl_only_fused(entry):
    """The two entry points the benchmark's mixes call keep the keyword,
    and it names the one driver only."""
    data = _data(64)
    with pytest.raises(ValueError, match="one driver"):
        if entry == "estimate":
            estimator.estimate("vamana", data, data[:4],
                               np.zeros((4, 10), np.int32),
                               [{"L": 16, "M": 8, "alpha": 1.2}],
                               build_impl="per_batch")
        else:
            retrieval.build_index(data, data, VAMANA_PS[0],
                                  build_impl="per_batch")


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
    """A warmed Vamana build is ONE jitted dispatch for the whole insertion
    pass — and zero Python-level calls to the stage functions (they are
    traced into the compiled program, not dispatched)."""
    data = _data()

    def build():
        return vamana.build_multi_vamana(data, VAMANA_PS, batch_size=64)

    build()                                          # warmup/compile
    cache_size = getattr(build_lib.fused_vamana_pass, "_cache_size",
                         lambda: None)()
    counts = _count_dispatches(build)
    assert counts == {"beam_search": 0, "rng_prune": 0,
                      "add_reverse_edges": 0, "insert_batch": 0,
                      "nsg_insert_batch": 0, "fused_vamana_pass": 1}
    if cache_size is not None:                       # compile-count audit
        assert build_lib.fused_vamana_pass._cache_size() == cache_size


def _hnsw_expected_steps(levels, batch_size):
    """(insert steps, descent steps) of HNSW's schedule: nodes by
    descending level in bootstrap batches of 8, 16, 32, ... capped at
    ``batch_size``; a batch inserts on layers 0..max(level) and descends
    (ef=1) through every layer above some row's level."""
    order = np.lexsort((np.arange(len(levels)), -levels))
    top, ins, desc, off, step = int(levels.max()), 0, 0, 0, 8
    while off < len(levels):
        lv = levels[order[off:off + min(step, batch_size)]]
        ins += int(lv.max()) + 1
        desc += top - int(lv.min())
        off += min(step, batch_size)
        step *= 2
    return ins, desc


@pytest.mark.parametrize("pg", ("hnsw", "nsg"))
def test_step_dispatch_pin(pg):
    """After warmup, a build makes one step dispatch per inserted batch
    (HNSW: per batch and layer; its ef=1 descent is a plain search) and
    no dispatch of prune or commit, and the step's jit cache does not
    grow."""
    data = _data()        # n=180, batch 64
    if pg == "hnsw":
        step = "insert_batch"

        def build():
            return hnsw.build_multi_hnsw(data, HNSW_PS, batch_size=64)
    else:
        step = "nsg_insert_batch"

        def build():
            return nsg.build_multi_nsg(data, NSG_PS, batch_size=64)

    res = build()                                    # warmup/compile
    fn = getattr(build_lib, step)
    cache_size = getattr(fn, "_cache_size", lambda: None)()
    counts = _count_dispatches(build)
    if pg == "hnsw":
        ins, desc = _hnsw_expected_steps(res.g.levels, 64)
        assert ins + desc == res.counters.batches
    else:
        ins, desc = 3, 0
    want = dict(beam_search=desc, rng_prune=0, add_reverse_edges=0,
                insert_batch=0, nsg_insert_batch=0, fused_vamana_pass=0)
    want[step] = ins
    assert counts == want
    if cache_size is not None:
        assert fn._cache_size() == cache_size


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
