"""CPU tests of the benchmark harness: nothing here touches a TPU.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q

Covers the trace reduction (on a trace recorded on the CPU), the roofline
arithmetic against ``peaks.json``, the seeded generators, the host truth,
the resolution of every cell by name, ``run.py``'s refusal to measure
without a TPU, the control, and a run of each traffic kind with its timed
path broken underneath, which has to come out as not correct.
"""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [p for p in (BENCH, os.path.join(ROOT, "src"))
                if p not in sys.path]

import data as datalib  # noqa: E402
import peaks as peaklib  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402

tracelib = run.tracelib
BIG_SEED = 2**31 + 12345


# ---------------------------------------------------------------- trace --

def test_union_self_time_and_gaps_on_synthetic_events():
    events = {
        "device": {"/device:TPU:0": [("while.1", 5, 35),
                                     ("gather_distance.3", 10, 20),
                                     ("fusion.1", 20, 30),
                                     ("gather_distance.7", 60, 80),
                                     ("outside", 200, 300)]},
        "host": [("window", 0, 100), ("estimate.build", 0, 50),
                 ("PjitFunction(fused_vamana_pass)", 30, 50),
                 ("estimate.eval", 50, 100)],
    }
    red = tracelib.reduce(events)
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(50e-9)          # [5,35] + [60,80]
    assert red["idle_share"] == pytest.approx(0.5)
    assert tracelib.kernel_seconds(red, r"gather_distance(\.\d+)?") == \
        pytest.approx(30e-9)
    assert red["kernel_s"]["while.1"] == pytest.approx(10e-9)   # self time
    gaps = red["breakdown"]["idle_gaps"]
    assert gaps[0] == ["estimate.build > PjitFunction(fused_vamana_pass)",
                       pytest.approx(25e-9)]                # [35, 60]
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    assert red["breakdown"]["device_ops"][0] == ["gather_distance.7",
                                                 pytest.approx(20e-9)]


def test_op_names_are_the_hlo_instruction_names():
    assert tracelib.op_name("%gather_distance.41 = f32[256,1,8]{2,1,0} "
                            "custom-call(f32[256,1,128] %b)") == \
        "gather_distance.41"
    assert tracelib.op_name("%while.2 = (s32[1000,1,80]) while(...)") == \
        "while.2"
    assert tracelib.op_name("dot_general.1") == "dot_general.1"


def test_union_merges_overlaps():
    assert tracelib.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]


def test_reduction_of_a_trace_recorded_on_the_cpu(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x.T).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    tracelib.start(str(tmp_path))
    with jax.profiler.TraceAnnotation(tracelib.WINDOW_SPAN):
        for _ in range(3):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = tracelib.load(tracelib.xplane_file(str(tmp_path)),
                           device_plane=re.compile(r"^/host:CPU$"),
                           device_line="tf_XLAPjRtCpuClient")
    red = tracelib.reduce(events)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["idle_share"] == pytest.approx(1 - red["busy_s"]
                                              / red["window_s"])
    names = set(red["kernel_s"])
    assert any(n.startswith("dot") for n in names), names
    assert len(red["breakdown"]["device_ops"]) <= 10
    assert len(red["breakdown"]["idle_gaps"]) <= 10


# -------------------------------------------------------------- peaks --

def test_roofline_arithmetic_against_the_peak_table():
    peak = peaklib.peaks("TPU v5 lite")
    assert peak["hbm_bytes_per_s"] == 819e9
    assert peaklib.memory_bound_share(819e9, 2.0, peak) == pytest.approx(50.0)
    assert peaklib.memory_bound_share(1e6, 0.0, peak) is None
    gr = run.reader("gather_roofline.serve")
    red = {"kernel_s": {"gather_distance.1": 0.5, "gather_distance.2": 0.5,
                        "fusion.3": 9.0}}
    v = gr.read("gather_roofline.serve", {"trace": red, "search_dist": 1e6,
                                          "d": 128, "peak": peak})
    assert v == pytest.approx(100.0 * (1e6 * 128 * 4 / 819e9) / 1.0)
    assert gr.read("gather_roofline.serve",
                   {"trace": {"kernel_s": {}}, "search_dist": 1e6, "d": 128,
                    "peak": peak}) is None


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaklib.peaks("TPU v9 imaginary")


# ------------------------------------------------------ data and truth --

def test_generators_are_deterministic_per_seed():
    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "sift1m-vamana.json")))
    a = datalib.corpus(cfg, 300, 20, BIG_SEED)
    b = datalib.corpus(cfg, 300, 20, BIG_SEED)
    c = datalib.corpus(cfg, 300, 20, BIG_SEED + 1)
    assert all(np.array_equal(u, v) for u, v in zip(a, b))
    assert not np.array_equal(a[1], c[1])
    assert a[0].shape == (300, 128) and a[0].dtype == np.float32


def test_seeds_share_one_corpus_and_reorder_its_queries():
    """Every seed inserts the same rows in the same order and sends the
    same queries in another order, so the work does not depend on the
    seed."""
    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "sift1m-vamana.json")))
    a = datalib.corpus(cfg, 300, 20, BIG_SEED)
    c = datalib.corpus(cfg, 300, 20, BIG_SEED + 1)
    assert np.array_equal(a[0], c[0])
    assert np.array_equal(np.unique(a[1], axis=0), np.unique(c[1], axis=0))


def test_host_truth_matches_brute_force():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(700, 24)).astype(np.float32)
    q = rng.normal(size=(30, 24)).astype(np.float32)
    got = datalib.host_knn(x, q, 10, block=128)
    d = ((q[:, None, :].astype(np.float64) - x[None]) ** 2).sum(-1)
    want = np.argsort(d, axis=1)[:, :10]
    assert all(set(g) == set(w) for g, w in zip(got, want))
    assert datalib.recall(got, want, 10) == 1.0


def test_reference_flags_bad_pools_and_edges():
    rng = np.random.default_rng(1)
    x = rng.normal(size=(50, 8)).astype(np.float32)
    q = x[:4] + 0.01
    ids = datalib.host_knn(x, q, 5)
    d = datalib.host_sqdist(q, x)
    order = np.argsort(np.take_along_axis(d, ids, 1), axis=1)
    ids = np.take_along_axis(ids, order, 1)
    dist = np.take_along_axis(d, ids, 1).astype(np.float32)
    good = reference.pools(q, x, ids, dist, 5)
    assert good["pool_bad"] == 0 and good["pool_dist_err"] < 1e-6
    bad = ids.copy()
    bad[0, 1] = bad[0, 0]
    bad[1, 0] = -1
    assert reference.pools(q, x, bad, dist, 5)["pool_bad"] >= 2
    g = np.full((1, 50, 4), -1, np.int32)
    g[0, :, 0] = (np.arange(50) + 1) % 50
    gd = np.full((1, 50, 4), np.inf, np.float32)
    gd[0, :, 0] = ((x - x[g[0, :, 0]]) ** 2).sum(1)
    ok = reference.graphs(x, g, gd, [1], np.arange(50))
    assert ok["edge_bad"] == 0 and ok["edge_dist_err"] < 1e-6
    g[0, 3, 1] = 3                                  # self loop past degree 1
    assert reference.graphs(x, g, gd, [1], np.arange(50))["edge_bad"] >= 2


# ----------------------------------------------------------- resolution --

def test_every_cell_resolves_by_name():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert bench["paths"] == ["chipbench"]
    for cfg in bench["configs"]:
        assert cfg["file"].startswith("chipbench/")
        assert os.path.isfile(os.path.join(ROOT, cfg["file"]))
    for w in bench["workloads"]:
        spec = run.cell_spec(w["name"])
        assert os.path.isfile(spec["kind_file"])
        assert spec["end_to_end"] and spec["per_layer"]
        assert "setup_s" in {m["name"] for m in spec["end_to_end"]}
        for m in spec["per_layer"]:
            assert hasattr(run.reader(m["name"]), "read")
            assert m["moves"] in {e["name"] for e in spec["end_to_end"]}
        n = spec["config"]["n"][spec["mix"]["kind"]]
        assert n > 0 and spec["limits"]


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("REPRO_PALLAS_INTERPRET", None)
    return env


def test_run_exits_nonzero_without_a_tpu():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "sift-tune", "--seed", str(BIG_SEED), "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=_cpu_env(),
        cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "sift-tune",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=_cpu_env(), cwd=tmp_path,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


# ------------------------------------------------ correctness and faults --

TINY = {"estimate": (800, 100), "batched": (1500, 300), "single": (1500, 300)}
MIXES = {"batched": "batched1000", "single": "single1"}
# the serving kinds have no cell yet: their limits as a serving cell of
# sift1m-vamana would state them (recall@10 >= 0.9 is the configuration's)
SERVE_LIMITS = {"outputs_missing": 0, "pool_bad": 0, "pool_dist_err": 1e-6,
                "recall_gap": 0.1}
SERVE_METRICS = {"batched": "serve_qps", "single": "query_p95_ms"}


def _tiny_spec(kind: str) -> dict:
    spec = run.cell_spec("sift-tune")
    if kind != "estimate":
        spec["mix"] = json.load(open(os.path.join(BENCH, "traffic",
                                                  MIXES[kind] + ".json")))
        spec["kind_file"] = os.path.join(BENCH, "traffic", kind + ".py")
        spec["limits"] = dict(SERVE_LIMITS)
        spec["end_to_end"] = [{"name": SERVE_METRICS[kind], "unit": "-"},
                              {"name": "setup_s", "unit": "s"}]
    n, nq = TINY[kind]
    spec["config"]["n"][kind] = n
    spec["config"]["queries"][kind] = nq
    if kind == "batched":
        spec["mix"]["batch"] = 200
    return spec


def _unchanged(monkeypatch):
    import control
    from repro.core import build
    monkeypatch.setattr(build, "fused_vamana_pass", build.fused_vamana_pass)
    control.plant_unchanged()


def _search_fault(monkeypatch, how: str):
    import jax.numpy as jnp
    from repro.core import search
    orig = search.knn_search

    def broken(graph_ids, data, queries, *a, **kw):
        r = orig(graph_ids, data, queries, *a, **kw)
        ids, dist = r.pool_ids, r.pool_dist
        if how == "half":        # half of the batch left out
            b = ids.shape[0] // 2
            ids, dist = ids.at[b:].set(-1), dist.at[b:].set(jnp.inf)
        else:                    # an answer altered where it is produced
            ids = ids.at[0, 0].set((ids[0, 0] + 1) % data.shape[0])
        return r._replace(pool_ids=ids, pool_dist=dist)
    monkeypatch.setattr(search, "knn_search", broken)


FAULTS = {
    "none": lambda mp: None,
    "unchanged": _unchanged,
    "half": lambda mp: _search_fault(mp, "half"),
    "altered": lambda mp: _search_fault(mp, "altered"),
}
CASES = [("estimate", f) for f in FAULTS] + \
        [(k, f) for k in ("batched", "single")
         for f in ("none", "half", "altered")]


@pytest.mark.parametrize("kind,fault", CASES)
def test_a_broken_timed_path_comes_out_not_correct(kind, fault, monkeypatch):
    import jax
    FAULTS[fault](monkeypatch)
    spec = _tiny_spec(kind)
    res = run.run_cell(spec, BIG_SEED, 0.5, False, jax.devices()[:1],
                       peaklib.peaks("TPU v5 lite"))
    assert res["correct"] is (fault == "none"), res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {m["name"] for m in spec["end_to_end"]}


@pytest.mark.parametrize("kind", ["batched", "estimate"])
def test_the_control_comes_out_not_correct(kind):
    import control
    spec = _tiny_spec(kind)
    r = control.readings(spec, BIG_SEED, 0.5, warm=kind != "estimate")
    assert r["program_correct"], r
    assert not r["control_correct"], r
    assert r["control"]["pool_dist_err"] > 3 * r["program"]["pool_dist_err"]
