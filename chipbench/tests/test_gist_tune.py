"""CPU tests of the ``gist-tune`` cell: GIST1M-shaped tuning at d=960.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_gist_tune.py -q

The cell runs through ``run.run_cell`` at the published width (d=960)
with the corpus cut to 800 rows and 100 queries, and is compared with the
host reference under the cell's own limits: the program comes out as
correct; a planted build that returns its state unchanged does not, nor
do outputs with one distance in a thousand moved by 1e-5 of the norms;
and the bfloat16x3 control reads above the limits on the error's fit to
bfloat16x3 rounding while the program reads below them.
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [p for p in (BENCH, os.path.join(ROOT, "src"))
                if p not in sys.path]

import peaks as peaklib  # noqa: E402
import run  # noqa: E402

BIG_SEED = 2**31 + 9601
N, NQ = 800, 100
GIST_METRICS = ["build_s_per_cfg", "eval_s_per_cfg", "gather_roofline",
                "idle_share", "build_hops_per_batch", "beam_occupancy",
                "window_compiles", "build_dist_per_insert"]
WIDEST_LIMITS = ("edge_dist_err", "pool_dist_err")
FIT_LIMITS = ("edge_bf16x3_fit", "pool_bf16x3_fit")
SPARSE_EVERY, SPARSE_SHIFT = 1000, 1e-5


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def _tiny_spec() -> dict:
    spec = run.cell_spec("gist-tune")
    spec["config"]["n"]["estimate_fit"] = N
    spec["config"]["queries"]["estimate_fit"] = NQ
    return spec


def _unchanged(monkeypatch):
    import control
    from repro.core import build
    monkeypatch.setattr(build, "fused_vamana_pass", build.fused_vamana_pass)
    control.plant_unchanged()


def _shift_every(x_rows, y_rows, dist):
    """Move every SPARSE_EVERY-th listed distance by SPARSE_SHIFT of the
    pair's norms."""
    a = x_rows[::SPARSE_EVERY].astype("float64")
    b = y_rows[::SPARSE_EVERY].astype("float64")
    dist[::SPARSE_EVERY] += SPARSE_SHIFT * ((a * a).sum(-1) + (b * b).sum(-1))


def _sparse(monkeypatch):
    """A sparse distance fault: the released outputs with one compared
    distance in SPARSE_EVERY wrong by SPARSE_SHIFT of the norms."""
    import estimate
    release = estimate.Cell.release

    def faulty(self):
        release(self)
        x, q, nodes = self.x_host, self.q_host, self.nodes
        graphs = []
        for ids, dist, degrees in self.out["graphs"]:
            dist = dist.copy()
            for g in range(ids.shape[0]):
                sub, ok = dist[g][nodes], ids[g][nodes] >= 0
                rows = np.broadcast_to(nodes[:, None], ok.shape)[ok]
                flat = sub[ok]
                _shift_every(x[rows], x[ids[g][nodes][ok]], flat)
                sub[ok] = flat
                dist[g][nodes] = sub
            graphs.append((ids, dist, degrees))
        pools = []
        for per_cfg in self.out["pools"]:
            per = []
            for ef, ids, dist in per_cfg:
                top, d = ids[:, :self.k], dist[:, :self.k].copy()
                ok = top >= 0
                rows = np.broadcast_to(np.arange(top.shape[0])[:, None],
                                       top.shape)[ok]
                flat = d[ok]
                _shift_every(q[rows], x[top[ok]], flat)
                d[ok] = flat
                per.append((ef, top, d))
            pools.append(per)
        self.out = dict(self.out, graphs=graphs, pools=pools)

    monkeypatch.setattr(estimate.Cell, "release", faulty)


def test_gist_tune_resolves_with_its_own_limits_and_metrics():
    spec = run.cell_spec("gist-tune")
    assert spec["chips"] == 1
    assert spec["config"]["d"] == 960 and spec["config"]["metric"] == "l2"
    assert spec["config"]["n"] == {"estimate_fit": 10000}
    assert spec["config"]["queries"] == {"estimate_fit": 1000}
    assert spec["limits"] == _json("limits", "gist-tune.json")
    assert set(WIDEST_LIMITS + FIT_LIMITS) <= set(spec["limits"])
    assert dict(spec["mix"], kind="estimate") == _json("traffic", "tune8.json")
    assert [m["name"] for m in spec["end_to_end"]] == ["tune_cfgs_per_s",
                                                        "setup_s"]
    assert [m["name"] for m in spec["per_layer"]] == \
        [m + ".gist" for m in GIST_METRICS]
    for m in spec["per_layer"]:
        assert hasattr(run.reader(m["name"]), "read")

    sift = run.cell_spec("sift-tune")
    assert sift["config"]["d"] == 128
    assert sift["mix"] == _json("traffic", "tune8.json")
    assert sift["limits"] == _json("limits", "sift-tune.json")
    assert [m["name"] for m in sift["end_to_end"]] == ["tune_cfgs_per_s",
                                                        "setup_s"]
    assert all(m["name"].endswith(".tune") for m in sift["per_layer"])
    assert len(sift["per_layer"]) == 8


@pytest.mark.parametrize("fault", ["none", "unchanged", "sparse"])
def test_gist_tune_against_the_reference(fault, monkeypatch):
    import jax
    if fault == "unchanged":
        _unchanged(monkeypatch)
    if fault == "sparse":
        _sparse(monkeypatch)
    spec = _tiny_spec()
    res = run.run_cell(spec, BIG_SEED, 0.5, False, jax.devices()[:1],
                       peaklib.peaks("TPU v5 lite"))
    assert res["correct"] is (fault == "none"), res["checks"]
    assert set(res["metrics"]) == {"tune_cfgs_per_s", "setup_s"}
    assert res["records"]["d"] == 960
    for name in WIDEST_LIMITS + FIT_LIMITS:
        assert name in res["checks"]
    if fault == "sparse":
        # the widest gaps catch it
        over = {name for name, (v, lim) in res["checks"].items() if v > lim}
        assert set(WIDEST_LIMITS) <= over, res["checks"]


def test_the_control_reads_above_the_distance_limits():
    import control
    spec = _tiny_spec()
    r = control.readings(spec, BIG_SEED, 0.5, warm=True)
    assert r["program_correct"], r
    assert not r["control_correct"], r
    for name in FIT_LIMITS:
        limit = spec["limits"][name]
        assert r["program"][name] < limit < r["control"][name], (name, r)
