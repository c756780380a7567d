"""Shared benchmark scaffolding: datasets, timing, CSV rows.

Datasets are synthetic clustered Gaussians mirroring the paper's corpora
dimensionalities, scaled to this container (DESIGN.md §8).  All rows print
as ``name,us_per_call,derived`` per the harness contract; ``derived``
carries the table's key quantity (speedup, ratio, #dist, ...).
"""
from __future__ import annotations

import json
import os
import time

import jax

from repro.core.tuner import estimator

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULTS_DIR = os.path.join(REPO_ROOT, "results", "bench")


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache, so repeat runs skip
    XLA compiles.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
    the directory from it and nothing is set here; otherwise the cache
    lives at the fixed, gitignored ``<repo>/.jax_cache`` (the path is part
    of the cache key, so a moving directory would never hit)."""
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(REPO_ROOT, ".jax_cache"))


enable_compile_cache()

# paper datasets -> laptop-scale stand-ins (true dimensionalities, reduced n:
# wall-time behaviour tracks the paper only when distance compute dominates)
DATASETS = {
    "sift": dict(n=2000, d=128, nq=100, n_clusters=32),   # Sift 128d
    "glove": dict(n=2400, d=100, nq=100, n_clusters=48),  # Glove 100d
}
DEFAULT_DATASET = "sift"

TUNE_KW = dict(budget=12, batch=6, scale=0.15, build_batch_size=512,
               ef_grid=[10, 20, 40, 80], mc_samples=24, timing_reps=1)


def dataset(name: str = DEFAULT_DATASET, seed: int = 0):
    cfg = DATASETS[name]
    return estimator.make_dataset(cfg["n"], cfg["d"], cfg["nq"], seed=seed,
                                  n_clusters=cfg["n_clusters"])


def row(name: str, us_per_call: float, derived) -> str:
    line = f"{name},{us_per_call:.1f},{derived}"
    print(line, flush=True)
    return line


def save_json(name: str, obj):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, name + ".json"), "w") as f:
        json.dump(obj, f, indent=1, default=float)


def load_json(name: str):
    path = os.path.join(RESULTS_DIR, name + ".json")
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return None


class Timer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.seconds = time.perf_counter() - self.t0


def time_min(fn, *args, reps=5):
    """(seconds_per_call, warmup_result) — min over reps.

    Host wall time on this container is ±80% noisy (background load lands
    on whole reps); the min of several reps estimates the uncontended cost,
    where the mean smears contention into the signal.  The warmup result is
    returned so callers needing outputs don't re-run the function."""
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best, out


def time_interleaved(thunks, reps=5, prime=False):
    """Per-thunk (seconds, warmup_result), timed in interleaved rounds.

    Configurations being *compared* must sample host noise together:
    round r times every config back to back, so a load spike inflates one
    rep of each instead of every rep of whichever config it straddled
    (mean-of-reps sequential timing made PR 3's W=1 vs W=4 CPU comparison
    unstable).  Per-config min over rounds is the reported number — the
    policy the BENCH_*.json trajectories record as
    ``interleaved-min-of-reps``.

    ``prime=True`` runs each thunk once, untimed, immediately before its
    timed rep.  Interleaving fixes *who* precedes each config — every
    config inherits the same neighbor's cache/TLB state each round, which
    at corpus scale is systematically unfair: the routed-search row runs
    behind the S=4 scatter-gather row's ~500 MB sweep and measured 8%
    slower than the identical program self-warm, while the plain row's
    predecessor touches the very arrays it reads.  Priming gives every
    config its own working set in cache, i.e. steady-state repeated-query
    cost — the quantity a serving QPS number means — recorded as
    ``primed-interleaved-min-of-reps``."""
    outs = []
    for fn in thunks:                       # warmup/compile, untimed
        out = fn()
        jax.block_until_ready(out)
        outs.append(out)
    best = [float("inf")] * len(thunks)
    for _ in range(reps):
        for i, fn in enumerate(thunks):
            if prime:
                jax.block_until_ready(fn())
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best[i] = min(best[i], time.perf_counter() - t0)
    return list(zip(best, outs))
