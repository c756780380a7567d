"""The estimate path's named scopes, host spans and counters
(``repro.core.obs``): every scope reaches the compiled HLO, the beam's
counters count what the loop did, each span appears once per unit of
work, the compile counter tells a compile from a reuse, and
``tools/scope_report.py`` attributes a CPU trace's device time by scope."""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build as build_lib
from repro.core import counters as counters_lib
from repro.core import obs, search, vamana
from repro.core.graph import INVALID
from repro.core.tuner import estimator, fastpgt

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))
import scope_report  # noqa: E402

BEAM_SCOPES = (obs.FRONTIER, obs.VISIT, obs.DISTANCE, obs.MERGE)


def _graph(n=100, m=2, mx=8, seed=0):
    r = np.random.default_rng(seed)
    data = jnp.asarray(r.normal(size=(n, 8)), jnp.float32)
    ids = jnp.asarray(r.integers(0, n, size=(m, n, mx)), jnp.int32)
    return data, ids


@functools.lru_cache(maxsize=None)
def _hlo(which: str) -> str:
    data, ids = _graph()
    if which == "fused_vamana_pass":
        lowered = build_lib.fused_vamana_pass.lower(
            ids, jnp.zeros(ids.shape, jnp.float32), data,
            jnp.array([16, 20], jnp.int32), jnp.array([8, 8], jnp.int32),
            jnp.array([1.1, 1.3], jnp.float32), jnp.int32(0),
            batch_size=64, ef_max=32, max_hops=112, share_cache=True,
            use_epo=True, metric="l2", visited_impl="dense", expand_width=1,
            k_in=16, m_max=8)
    else:       # the beam_search program knn_search dispatches
        q = data[:5]
        lowered = search.beam_search.lower(
            ids[:1], data, q, jnp.full((5,), INVALID, jnp.int32),
            jnp.ones((5,), bool), jnp.array([10], jnp.int32),
            jnp.zeros((5, 1), jnp.int32), ef_max=10,
            max_hops=search.default_max_hops(10), share_cache=False)
    return lowered.compile().as_text()


@pytest.mark.parametrize(
    "program,scope",
    [("fused_vamana_pass", s) for s in obs.SCOPES]
    + [("knn_search", s) for s in BEAM_SCOPES])
def test_compiled_hlo_carries_every_scope(program, scope):
    assert f"/{scope}/" in _hlo(program)


def _reach(ids_g, entry):
    """Nodes reachable from ``entry`` over one graph's INVALID-padded rows."""
    seen, todo = {entry}, [entry]
    while todo:
        for v in ids_g[todo.pop()]:
            if v != INVALID and v not in seen:
                seen.add(int(v))
                todo.append(int(v))
    return len(seen)


def test_beam_counters_match_a_numpy_count_of_active_lane_hops():
    """W=1 with a pool as wide as the corpus: every reachable node enters
    the pool and is expanded once, one per active (row, graph) lane per
    hop, so expansions are the reachable-set sizes summed over the live
    lanes, and hops the largest of them."""
    n, m, b = 40, 2, 6
    r = np.random.default_rng(3)
    ids = r.integers(0, n, size=(m, n, 3)).astype(np.int32)
    ids[r.random(ids.shape) < 0.5] = INVALID           # sparse: small sets
    data = jnp.asarray(r.normal(size=(n, 4)), jnp.float32)
    entry = r.integers(0, n, size=(b, m)).astype(np.int32)
    row_mask = np.arange(b) < b - 1                     # one padding row
    res = search.beam_search(
        jnp.asarray(ids), data, data[:b], jnp.full((b,), INVALID, jnp.int32),
        jnp.asarray(row_mask), jnp.array([n] * m, jnp.int32),
        jnp.asarray(entry), ef_max=n, max_hops=4 * n, share_cache=False)
    sizes = np.array([[_reach(ids[g], entry[i, g]) for g in range(m)]
                      for i in range(b)])[row_mask]
    assert int(res.expansions) == sizes.sum()
    assert int(res.hops) == sizes.max()
    row = np.asarray(counters_lib.step_row(res, jnp.asarray(row_mask), 1))
    fields = dict(zip(counters_lib.TAPE_FIELDS, row.tolist()))
    assert fields["lane_hops"] == sizes.max() * (b - 1) * m
    assert 0 < fields["expansions"] <= fields["lane_hops"]


@pytest.fixture(scope="module")
def tune_trace(tmp_path_factory):
    """Host spans of one small FastPGT run: an initial estimate of 2
    configurations, one recommendation, a second estimate of 2."""
    d = str(tmp_path_factory.mktemp("trace"))
    data, queries = estimator.make_dataset(300, 8, 15, seed=4)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        res = fastpgt.tune("vamana", data, queries, mode="fastpgt", budget=4,
                           batch=2, init_random=2, seed=0, scale=0.1,
                           build_batch_size=128, ef_grid=[10, 20],
                           mc_samples=8)
    finally:
        jax.profiler.stop_trace()
    events = scope_report.load(scope_report.xplane_file(d),
                               device_plane=scope_report.re.compile("^$"))
    return res, events["host"]


@pytest.mark.parametrize("span,count", [
    (obs.ESTIMATE, 2), (obs.RECOMMEND, 1), (obs.GROUP, 2),
    (obs.VAMANA_INIT, 2), (obs.DRAIN, 2), (obs.EVAL_POINT, 4 * 2),
    (obs.RECALL_SEARCH, 4 * 2), (obs.TIMED_SEARCH, 4 * 2)])
def test_host_spans_once_per_unit_of_work(tune_trace, span, count):
    res, host = tune_trace
    assert len(res.cfgs) == 4
    assert sum(name == span for name, _, _ in host) == count


def test_eval_search_spans_nest_in_their_point(tune_trace):
    _, host = tune_trace
    points = [(s, e) for n, s, e in host if n == obs.EVAL_POINT]
    for name, s, e in host:
        if name in (obs.RECALL_SEARCH, obs.TIMED_SEARCH):
            assert any(ps <= s and e <= pe for ps, pe in points)


def test_estimate_records_its_call():
    data, queries = estimator.make_dataset(200, 8, 10, seed=5)
    from repro.core import eval as evallib
    gt = evallib.ground_truth(data, queries, 10)
    cfgs = [{"L": 16, "M": 8, "alpha": 1.2}, {"L": 20, "M": 6, "alpha": 1.1}]
    rec = estimator.estimate("vamana", data, queries, gt, cfgs, group_size=2,
                             ef_grid=[10], build_batch_size=128)
    last = obs.ESTIMATES[-1]
    assert last["configs"] == 2
    assert last["total"] == rec.counters.total
    assert last["batches"] == 2 and last["hops"] == rec.counters.hops > 0
    assert 0 < last["expansions"] <= last["lane_hops"]


def test_compile_counter_counts_a_new_shape_once():
    f = jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)
    x = np.ones((os.getpid() % 89 + 7, 5), np.float32)   # a fresh shape
    c0, h0 = obs.compiles(), obs.cache_hits()
    f(x).block_until_ready()
    assert obs.compiles() - c0 + obs.cache_hits() - h0 == 1
    if jax.config.jax_compilation_cache_dir is None:
        assert obs.compiles() - c0 == 1
    c1 = obs.compiles()
    f(x).block_until_ready()
    assert obs.compiles() - c1 == 0


# -- tools/scope_report.py on a trace recorded on the CPU -----------------------

def test_scope_of_takes_the_innermost_scope():
    path = ("jit(fused_vamana_pass)/while/body/closed_call/jit(beam_search)/"
            "beam/frontier/while/body/beam/merge/jit(take_along_axis)/gather")
    assert scope_report.scope_of(path) == obs.MERGE
    assert scope_report.scope_of("jit(f)/beam/frontier/while/cond/lt") == \
        obs.FRONTIER
    assert scope_report.scope_of(
        "jit(fused_vamana_pass)/while/body/closed_call/commit/"
        "jit(add_reverse_edges)/jit(rng_prune)/sub") == obs.COMMIT
    assert scope_report.scope_of("jit(f)/jit(prune_rows)/add") == ""


def test_self_time_excludes_nested_ops():
    # a while op [0, 10] around [1, 4] (around [2, 3]) and [5, 7]; then
    # an op that starts as it ends
    start = np.array([0.0, 1.0, 2.0, 5.0, 10.0])
    end = np.array([10.0, 4.0, 3.0, 7.0, 12.0])
    np.testing.assert_allclose(scope_report.self_ns(start, end),
                               [5.0, 2.0, 1.0, 2.0, 2.0])


@pytest.fixture(scope="module")
def cpu_report(tmp_path_factory):
    """A fused build and an eval search, traced on the CPU and reduced."""
    d = str(tmp_path_factory.mktemp("scopes"))
    r = np.random.default_rng(0)
    data = jnp.asarray(r.normal(size=(300, 8)), jnp.float32)
    ps = [vamana.VamanaParams(L=16, M=8, alpha=1.1),
          vamana.VamanaParams(L=24, M=8, alpha=1.3)]

    def work():
        res = vamana.build_multi_vamana(data, ps, batch_size=128)
        hits = search.knn_search(res.g.ids[0], data, data[:32], 10, 16,
                                 res.entry)
        jax.block_until_ready(hits.pool_ids)
    work()                                            # compile outside
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation(scope_report.WINDOW_SPAN):
        work()
    jax.profiler.stop_trace()
    path = scope_report.xplane_file(d)
    events = scope_report.load(
        path, device_plane=scope_report.re.compile(r"^/host:CPU$"),
        device_line=("tf_XLAPjRtCpuClient", "tf_XLAEigen"), module_line=None)
    return scope_report.reduce(events, scope_report.op_scopes(path), top=50)


@pytest.mark.parametrize("scope", obs.SCOPES)
def test_cpu_trace_build_time_by_scope(cpu_report, scope):
    assert cpu_report["scope_s"]["build"].get(scope, 0.0) > 0


def test_cpu_trace_eval_side_holds_only_beam_scopes(cpu_report):
    ev = cpu_report["scope_s"]["eval"]
    assert any(ev.get(s, 0.0) > 0 for s in BEAM_SCOPES)
    assert ev.get(obs.PRUNE, 0.0) == 0 and ev.get(obs.COMMIT, 0.0) == 0


def test_cpu_trace_coverage_and_top_ops(cpu_report):
    assert cpu_report["coverage"]["build"] > 50.0
    for name, module, scope, sec in cpu_report["top_ops"]:
        assert sec >= 0 and scope in obs.SCOPES + ("",)
    assert any(m == "jit_fused_vamana_pass" and s
               for _, m, s, _ in cpu_report["top_ops"])
