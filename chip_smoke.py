#!/usr/bin/env python3
"""Run FastPGT's tune → build → serve path once on a TPU, and check it.

    python chip_smoke.py               # one chip: kernels, tune, serve
    python chip_smoke.py --chips 4     # four chips: sharded serving only

Everything runs in this one process, through the entry points a user
calls (``ops``, ``fastpgt.tune``, ``retrieval.build_index`` and the
serving search), on data generated from ``--seed``.  The corpus has the
SIFT1M shape (ann-benchmarks: d=128, l2): ``estimator.make_dataset``
draws a clustered corpus in INTRINSIC_D dimensions, and a seeded
orthonormal map embeds it in d=128, so the kernels see 128-wide vectors
while the neighbour structure stays low-dimensional as SIFT's is (its
local intrinsic dimensionality is far below 128; Aumüller & Ceccarello,
"The role of local intrinsic dimensionality in benchmarking nearest
neighbor search", 2019).  Isotropic 128-d clusters would be a different
workload: equidistant neighbours within a cluster, and well-separated
clusters that a graph built from its search pools does not connect.
Recall is measured against exact neighbours computed on the host in
NumPy float64, independent of the code under test.

Phases (one chip):
  kernels  the four distance kernels (fp32 and sq8, pairwise and gather)
           at d=128 and d=960 against NumPy float64, the prune stage's
           candidate distances likewise, and the jitted search program's
           HLO checked for ``tpu_custom_call`` (Pallas, not ``ref.py``).
  tune     ``fastpgt.tune("vamana", mode="fastpgt", budget=8, batch=4)``
           at n=TUNE_N; the configuration with the best recall is rebuilt
           and must reach recall@10 >= 0.9 on the host's truth.
  serve    that configuration built at n=SERVE_N with
           ``build_index(metric="l2", quantize="sq8")`` and searched with
           the serving defaults (hash visited set, W=4) on 1,000 queries,
           fp32 and sq8, at the smallest ef in SERVE_EFS where fp32
           recall@10 >= 0.9; sq8 must be within 0.02 of it.

Phase ``sharded`` (``--chips 4``, and nothing else): the same corpus built
with ``num_shards=4, assign="kmeans"`` on a 4-device ``search_mesh``,
searched scatter-gather and routed with p in {1, 2, 4}; its shards must
sit on 4 distinct devices, its pools must equal those of the same index
on a 1-device mesh (where routed searches take the fused path), and its
recall is measured against the host's truth.

Each phase prints one JSON line (cold seconds, compilation included;
recall; n_dist).  The last line of standard output is
``{"ok": true, "device": {...}}``.  Without a TPU the script exits
non-zero before any phase and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

SEED = 0
D = 128                       # SIFT1M width
INTRINSIC_D = 16              # dimensions the clustered corpus is drawn in
CLUSTERS = 32
SPREAD = 1.0                  # cluster-centre scale: clusters overlap
NQ = 1000
K = 10
SIFT1M_N = 1_000_000
# SIFT1M's n cut to what one run finishes well inside 1200 s on one v5e:
# the fused Vamana build runs at roughly 10^3 rows/s there (one query per
# gather-kernel grid step), a tune at n=100,000 did not finish in 1200 s,
# and one tune builds 8 graphs
TUNE_N = 10_000
SERVE_N = 100_000
SERVE_EF = 64
# serving ef >= 64: the tuned graph is served at the smallest of these
# that reaches RECALL_FLOOR (a graph tuned at TUNE_N may need a wider beam
# at SERVE_N)
SERVE_EFS = (SERVE_EF, 2 * SERVE_EF, 4 * SERVE_EF)
RECALL_FLOOR = 0.9
SQ8_SLACK = 0.02
SHARDS = 4


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


def preflight(chips: int):
    """The device the run will use, or exit non-zero naming the cause."""
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        fail(f"no src/repro next to {os.path.basename(__file__)}: run it "
             f"from a checkout of the repository")
    if os.environ.get("REPRO_PALLAS_INTERPRET"):
        fail("REPRO_PALLAS_INTERPRET is set: the Pallas kernels would run "
             "in interpret mode instead of on the chip; unset it")
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        fail(f"no TPU: jax.default_backend() is {backend!r}; this script "
             f"measures nothing on another backend")
    devices = jax.devices()
    if len(devices) < chips:
        fail(f"--chips {chips} needs {chips} TPU devices, found "
             f"{len(devices)}")
    log(f"device kind {devices[0].device_kind!r}, count {len(devices)}")
    return devices


def emit(phase: str, t0: float, **fields) -> None:
    print(json.dumps({"phase": phase,
                      "seconds": round(time.perf_counter() - t0, 3),
                      **fields}), flush=True)


# ---------------------------------------------------------------------------
# host reference (NumPy float64)
# ---------------------------------------------------------------------------

def host_sqdist(q, x):
    """Exact squared L2 distances (nq, nx) in float64."""
    import numpy as np
    q = np.asarray(q, np.float64)
    x = np.asarray(x, np.float64)
    return ((q * q).sum(1)[:, None] + (x * x).sum(1)[None, :]
            - 2.0 * q @ x.T)


def host_knn(data, queries, k: int = K, block: int = 65536):
    """Exact top-k ids (nq, k) by squared L2, scanned in corpus blocks."""
    import numpy as np
    x, q = np.asarray(data), np.asarray(queries)
    best_d = np.empty((q.shape[0], 0))
    best_i = np.empty((q.shape[0], 0), np.int64)
    for off in range(0, x.shape[0], block):
        dd = np.concatenate([best_d, host_sqdist(q, x[off:off + block])], 1)
        ii = np.concatenate([best_i, np.broadcast_to(
            np.arange(off, min(off + block, x.shape[0])),
            (q.shape[0], dd.shape[1] - best_i.shape[1]))], 1)
        top = np.argpartition(dd, k - 1, axis=1)[:, :k]
        best_d = np.take_along_axis(dd, top, 1)
        best_i = np.take_along_axis(ii, top, 1)
    return best_i


def sift_shaped(n: int, seed: int):
    """(data f32[n, D], queries f32[NQ, D]): make_dataset's clustered
    corpus in INTRINSIC_D dimensions, embedded in D by an orthonormal map
    drawn from ``seed`` (distances are preserved, so the neighbours are
    the low-dimensional corpus's)."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.tuner import estimator
    x, q = estimator.make_dataset(n, INTRINSIC_D, NQ, seed=seed,
                                  n_clusters=CLUSTERS, spread=SPREAD)
    g = np.random.default_rng([seed, D]).normal(size=(D, INTRINSIC_D))
    basis = np.linalg.qr(g)[0]                      # (D, INTRINSIC_D)
    return tuple(jnp.asarray(np.asarray(a, np.float64) @ basis.T,
                             jnp.float32) for a in (x, q))


def recall(found, truth) -> float:
    import numpy as np
    found = np.asarray(found)[:, :K]
    hits = [len(set(f.tolist()) & set(t.tolist()))
            for f, t in zip(found, truth)]
    return float(np.mean(hits) / K)


def max_rel_err(got, want, scale) -> float:
    """max |got - want| / scale, elementwise: scale is the size of the
    terms the kernel's norm expansion cancels (‖q‖² + ‖x‖²)."""
    import numpy as np
    return float(np.max(np.abs(np.asarray(got, np.float64) - want) / scale))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

KERNEL_TOL = 1e-5     # fp32: ~sqrt(d)·eps of the cancelled norms; bf16: ~4e-3


def phase_kernels(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import metric as metric_lib
    from repro.core import prune, search
    from repro.core.tuner import estimator
    from repro.kernels import ops

    t0 = time.perf_counter()
    errs = {}
    n_dist = 0
    rng = np.random.default_rng(seed)
    for d in (D, 960):
        x, q = estimator.make_dataset(4096, d, 256, seed=seed)
        xn, qn = np.asarray(x, np.float64), np.asarray(q, np.float64)
        norms = (qn * qn).sum(1)[:, None] + (xn * xn).sum(1)[None, :]
        ids = rng.integers(0, x.shape[0], size=(q.shape[0], 128))
        gnorm = np.take_along_axis(norms, ids, 1)

        errs[f"pairwise_d{d}"] = max_rel_err(
            ops.pairwise_distance(q, x, "l2"), host_sqdist(q, x), norms)
        exact_g = np.take_along_axis(host_sqdist(q, x), ids, 1)
        cached = jnp.asarray(rng.normal(size=ids.shape), jnp.float32)
        mask = rng.random(ids.shape) < 0.7
        got = np.asarray(ops.gather_distance(q, x[jnp.asarray(ids)], cached,
                                             jnp.asarray(mask), "l2"))
        check(np.array_equal(got[~mask], np.asarray(cached)[~mask]),
              f"gather d={d}: cached entries did not pass through exactly")
        errs[f"gather_d{d}"] = max_rel_err(got[mask], exact_g[mask],
                                           gnorm[mask])

        quant = metric_lib.quantize_sq8(x)
        deq = np.asarray(quant.codes, np.float64) * np.asarray(quant.scale,
                                                               np.float64)
        exact_q = host_sqdist(q, deq)
        qnorms = (qn * qn).sum(1)[:, None] + (deq * deq).sum(1)[None, :]
        errs[f"pairwise_sq8_d{d}"] = max_rel_err(
            ops.pairwise_distance_q(q, quant, "l2"), exact_q, qnorms)
        jids = jnp.asarray(ids)
        errs[f"gather_sq8_d{d}"] = max_rel_err(
            ops.gather_distance_q(q, quant.codes[jids], quant.scale,
                                  quant.norms[jids], metric="l2"),
            np.take_along_axis(exact_q, ids, 1),
            np.take_along_axis(qnorms, ids, 1))

        # the prune stage's candidate-pair distances (an XLA dot, not Pallas)
        cand = jnp.asarray(ids[:64, :64], jnp.int32)
        pair = np.asarray(prune.pairwise_candidate_dist(x, cand, "l2"))
        c64 = xn[ids[:64, :64]]
        sq = (c64 * c64).sum(-1)
        exact_p = sq[:, :, None] + sq[:, None, :] - 2.0 * np.einsum(
            "bld,bkd->blk", c64, c64)
        errs[f"prune_pairs_d{d}"] = max_rel_err(
            pair, exact_p, sq[:, :, None] + sq[:, None, :])
        n_dist += 2 * q.shape[0] * (x.shape[0] + ids.shape[1]) + pair.size

    bad = {k: v for k, v in errs.items() if not v <= KERNEL_TOL}
    check(not bad, f"kernel error above {KERNEL_TOL} of the cancelled "
                   f"norms: {bad}")

    # the jitted serving search must carry the Pallas kernels
    n = 4096
    g = jax.ShapeDtypeStruct((n, 32), jnp.int32)
    xs = jax.ShapeDtypeStruct((n, D), jnp.float32)
    qs = jax.ShapeDtypeStruct((64, D), jnp.float32)
    hlo = jax.jit(lambda g, x, q: search.knn_search(
        g, x, q, K, SERVE_EF, 0, visited_impl="hash",
        expand_width=4).pool_ids).lower(g, xs, qs).as_text()
    check("tpu_custom_call" in hlo,
          "the jitted search program holds no tpu_custom_call: the "
          "distance kernels did not lower to Pallas")
    emit("kernels", t0, recall=None, n_dist=int(n_dist),
         max_err=errs, tpu_custom_call=True)


def phase_tune(seed: int):
    """Tune at TUNE_N; returns the VamanaParams of the best-recall config."""
    import numpy as np
    from repro.core import vamana
    from repro.core.tuner import fastpgt
    from repro.core.tuner import params as pspace
    from repro.serve import retrieval

    t0 = time.perf_counter()
    data, queries = sift_shaped(TUNE_N, seed)
    truth = host_knn(data, queries)
    res = fastpgt.tune("vamana", data, queries, mode="fastpgt", budget=8,
                       batch=4)
    log(f"tune summary {json.dumps(res.summary())}")
    best = max(range(len(res.cfgs)),
               key=lambda i: (res.objectives[i][1], res.objectives[i][0]))
    params = pspace.to_build_params("vamana", res.cfgs[best])
    check(isinstance(params, vamana.VamanaParams),
          f"tune returned a non-Vamana configuration {params!r}")
    idx = retrieval.build_index(data, data, params, metric="l2")
    _, sr = retrieval.retrieval_attention_batched(idx, queries, top_k=K,
                                                  ef=SERVE_EF)
    rec = recall(sr.pool_ids, truth)
    check(rec >= RECALL_FLOOR,
          f"tune: best configuration {res.cfgs[best]} reaches recall@10 "
          f"{rec} < {RECALL_FLOOR} on the host's exact neighbours")
    emit("tune", t0, recall=rec, n_dist=int(sr.n_computed),
         n=TUNE_N, n_cut_from=SIFT1M_N, summary=res.summary(),
         best={"L": params.L, "M": params.M, "alpha": params.alpha,
               "tuner_recall": res.objectives[best][1]})
    return params


def phase_serve(seed: int, params) -> None:
    from repro.serve import retrieval

    t0 = time.perf_counter()
    data, queries = sift_shaped(SERVE_N, seed)
    truth = host_knn(data, queries)
    idx = retrieval.build_index(data, data, params, metric="l2",
                                quantize="sq8")
    t_build = time.perf_counter() - t0
    log(f"serve: built n={SERVE_N} {params} in {t_build:.1f} s")
    by_ef = {}
    for ef in SERVE_EFS:
        out = {}
        for mode in ("none", "sq8"):
            _, sr = retrieval.retrieval_attention_batched(
                idx, queries, top_k=K, ef=ef, quantize=mode)
            out[mode] = (recall(sr.pool_ids, truth), int(sr.n_computed))
        by_ef[ef] = out["none"][0]
        if out["none"][0] >= RECALL_FLOOR:
            break
    check(out["none"][0] >= RECALL_FLOOR,
          f"serve: fp32 recall@10 {by_ef} (by ef) never reaches "
          f"{RECALL_FLOOR}")
    check(out["sq8"][0] >= out["none"][0] - SQ8_SLACK,
          f"serve: sq8 recall@10 {out['sq8'][0]} is more than {SQ8_SLACK} "
          f"below fp32 {out['none'][0]}")
    emit("serve", t0, recall=out["none"][0], n_dist=out["none"][1],
         recall_sq8=out["sq8"][0], n_dist_sq8=out["sq8"][1], n=SERVE_N,
         n_cut_from=SIFT1M_N,
         ef=ef, recall_by_ef=by_ef, build_seconds=round(t_build, 3),
         params={"L": params.L, "M": params.M, "alpha": params.alpha})


# mid-range of the tuner's Vamana space (L <= 128, M <= 16 at its scale)
SHARDED_PARAMS = dict(L=64, M=16, alpha=1.2)


def phase_sharded(seed: int, devices) -> None:
    import numpy as np
    from repro.core import graph, search, vamana
    from repro.distributed import sharding
    from repro.serve import retrieval

    t0 = time.perf_counter()
    data, queries = sift_shaped(SERVE_N, seed)
    truth = host_knn(data, queries)
    params = vamana.VamanaParams(**SHARDED_PARAMS)
    idx = retrieval.build_index(data, data, params, metric="l2",
                                num_shards=SHARDS, assign="kmeans")
    log(f"sharded: built n={SERVE_N} in {time.perf_counter() - t0:.1f} s")
    sg = idx.shards
    placed = sg.ids.sharding.device_set
    check(len(placed) == SHARDS,
          f"shards sit on {len(placed)} device(s), not {SHARDS}")
    mesh1 = sharding.search_mesh(SHARDS, devices=devices[:1])
    one = graph.place_sharded(sg, mesh1)
    kw = dict(metric=idx.kernel, visited_impl="hash", expand_width=4)
    rows = {}
    # p=S routes every query to every shard: the routed path's own
    # scatter-gather, which the 1-device mesh runs fused
    for p in (None, 1, 2, SHARDS):
        r4 = search.sharded_knn_search(sg, queries, K, SERVE_EF,
                                       routed_shards=p, **kw)
        r1 = search.sharded_knn_search(one, queries, K, SERVE_EF,
                                       routed_shards=p, mesh=mesh1, **kw)
        ids4, ids1 = np.asarray(r4.pool_ids), np.asarray(r1.pool_ids)
        name = "scatter_gather" if p is None else f"routed_p{p}"
        rows[name] = {"recall": recall(ids4, truth),
                      "recall_1dev": recall(ids1, truth),
                      "n_dist": int(r4.n_computed),
                      "rows_equal_1dev": float(np.mean(np.all(ids4 == ids1,
                                                              axis=1)))}
    emit("sharded", t0, recall=rows["scatter_gather"]["recall"],
         n_dist=rows["scatter_gather"]["n_dist"], n=SERVE_N,
         n_cut_from=SIFT1M_N,
         shard_devices=sorted(str(d) for d in placed), modes=rows,
         params=SHARDED_PARAMS)
    unequal = {k: v["rows_equal_1dev"] for k, v in rows.items()
               if v["rows_equal_1dev"] != 1.0}
    check(not unequal, f"4-device pools differ from the 1-device ones "
                       f"(fraction of equal rows): {unequal}")
    check(rows["scatter_gather"]["recall"] >= RECALL_FLOOR,
          f"sharded scatter-gather recall@10 "
          f"{rows['scatter_gather']['recall']} < {RECALL_FLOOR}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=SEED)
    args = ap.parse_args(argv)
    devices = preflight(args.chips)
    from benchmarks.common import enable_compile_cache
    enable_compile_cache()
    if args.chips == 4:
        phase_sharded(args.seed, devices[:SHARDS])
    else:
        phase_kernels(args.seed)
        params = phase_tune(args.seed)
        phase_serve(args.seed, params)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
