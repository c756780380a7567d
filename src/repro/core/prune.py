"""RNG pruning — Algorithm 2 (Prune) and Algorithm 4 (mPrune / EPO).

The dominance recurrence is order-dependent (candidates processed ascending
by distance; accepted members prune later ones), so the inner loop is a
``lax.fori_loop`` carrying an accepted mask — bit-identical to the paper's
sequential C++ given the same candidate lists (property-tested against
Theorems 1 & 2).

EPO: when pruning graph i's candidate list after graph i-1's, any pair
(v, w) with both endpoints in the *previous accepted set* C'_{i-1}(u) was
already verified non-dominating and is skipped (Alg. 4 lines 5-6).  The skip
is sound when alpha_i >= alpha_{i-1} (dominance needs alpha*d(v,w) < d(u,v);
survival under a smaller alpha implies survival under a larger one), so
builders sort each group ascending by alpha — noted in DESIGN.md.

Counters (paper metrics):
  n_checks_base — dominance checks a standalone Alg. 2 run would perform
                  (each costs one distance computation delta(v, w)).
  n_checks      — checks actually performed after the EPO skip.

The full pairwise candidate-distance matrix is evaluated as one batched MXU
contraction (TPU-native); the counters track the paper's *logical* #dist.
The union-dedup variant (one matrix shared across the m graphs) is a §Perf
hillclimb documented in EXPERIMENTS.md.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import metric as metric_lib
from repro.core.graph import INVALID


class PruneResult(NamedTuple):
    ids: jax.Array          # int32[b, M_max] accepted neighbors, sorted by dist
    dist: jax.Array         # float32[b, M_max]
    accepted: jax.Array     # bool[b, L] acceptance mask over input candidates
    n_checks_base: jax.Array
    n_checks: jax.Array


def pairwise_candidate_dist(data: jax.Array, cand_ids: jax.Array,
                            metric: str = "l2") -> jax.Array:
    """float32[b, L, L] metric distances among each row's candidates.

    The alpha-rule's directional occlusion check compares these against the
    candidate-to-u distances, so both must be in the same metric's units
    (core/metric.py convention).
    """
    met = metric_lib.resolve(metric)
    c = met.prepare(data[jnp.maximum(cand_ids, 0)].astype(jnp.float32))
    # HIGHEST: the l2 form below cancels norms against 2·cross, and the
    # alpha rule compares the result with fp32 candidate distances; a
    # default-precision dot on the TPU MXU rounds its operands to bf16.
    cross = jnp.einsum("bld,bkd->blk", c, c,
                       precision=jax.lax.Precision.HIGHEST)       # (b, L, L)
    if met.kernel == "ip":
        # Clamp at 0: raw-ip pair distances can be negative, which would
        # invert the alpha rule (larger alpha dominating MORE) and break
        # the monotonicity EPO's pair-skip soundness needs (DESIGN.md §4).
        # Cosine pairs lie in [0, 2] already, so this only bites raw ip.
        return jnp.maximum(1.0 - cross, 0.0)
    n2 = jnp.sum(c * c, axis=-1)                                # (b, L)
    pd = n2[:, :, None] + n2[:, None, :] - 2.0 * cross
    return jnp.maximum(pd, 0.0)


@functools.partial(jax.jit, static_argnames=("m_max",))
def rng_prune(
    cand_ids: jax.Array,    # int32[b, L] ascending by distance to u
    cand_dist: jax.Array,   # float32[b, L]
    pair_dist: jax.Array,   # float32[b, L, L]
    valid: jax.Array,       # bool[b, L]
    m_limit: jax.Array,     # int32[] or [b] out-degree limit M
    alpha: jax.Array,       # float32[] pruning parameter
    skip_member: jax.Array | None = None,   # bool[b, L]: id in C'_{i-1}(u)
    *,
    m_max: int,
) -> PruneResult:
    """Alg. 2 when skip_member is None, Alg. 4 (mPrune) otherwise."""
    b, L = cand_ids.shape
    m_limit = jnp.broadcast_to(jnp.asarray(m_limit, jnp.int32), (b,))
    if skip_member is None:
        skip_member = jnp.zeros((b, L), bool)

    def body(j, st):
        accepted, count, nb, nc = st
        dj = cand_dist[:, j]                                    # (b,)
        processed = valid[:, j] & (count < m_limit)             # (b,)
        pd_j = pair_dist[:, j, :]                               # (b, L)
        check = accepted & processed[:, None]                   # PN members
        skip = skip_member & skip_member[:, j][:, None]         # EPO pair skip
        do_check = check & ~skip
        dominated = jnp.any(do_check & (alpha * pd_j < dj[:, None]), axis=-1)
        nb += jnp.sum(check).astype(jnp.int32)
        nc += jnp.sum(do_check).astype(jnp.int32)
        acc_j = processed & ~dominated
        accepted = accepted.at[:, j].set(acc_j)
        count = count + acc_j.astype(jnp.int32)
        return accepted, count, nb, nc

    init = (jnp.zeros((b, L), bool), jnp.zeros((b,), jnp.int32),
            jnp.int32(0), jnp.int32(0))
    accepted, _, nb, nc = jax.lax.fori_loop(0, L, body, init)

    # Compact accepted candidates (order-preserving) into M_max slots.
    key = jnp.where(accepted, jnp.arange(L)[None, :], L)
    order = jnp.argsort(key, axis=-1)[:, :m_max]
    sel = jnp.take_along_axis(accepted, order, axis=-1)
    ids = jnp.where(sel, jnp.take_along_axis(cand_ids, order, axis=-1),
                    INVALID)
    dist = jnp.where(sel, jnp.take_along_axis(cand_dist, order, axis=-1),
                     jnp.inf)
    return PruneResult(ids, dist, accepted, nb, nc)


def member_mask(cand_ids: jax.Array, prev_ids: jax.Array) -> jax.Array:
    """bool[b, L]: cand_ids[b, j] appears in prev_ids[b, :] (and is valid)."""
    eq = cand_ids[:, :, None] == prev_ids[:, None, :]
    return jnp.any(eq & (prev_ids != INVALID)[:, None, :], axis=-1) & (
        cand_ids != INVALID)


def multi_prune(
    data: jax.Array,
    cand_ids: jax.Array,     # int32[m, b, L] per-graph candidates (sorted)
    cand_dist: jax.Array,    # float32[m, b, L]
    valid: jax.Array,        # bool[m, b, L]
    m_limits: jax.Array,     # int32[m]
    alphas: jax.Array,       # float32[m]  (callers sort groups by alpha asc)
    *,
    m_max: int,
    use_epo: bool = True,
    metric: str = "l2",
) -> tuple[list[PruneResult], jax.Array, jax.Array]:
    """Sequentially prune the m candidate sets with EPO chaining (Alg. 4).

    Returns (per-graph PruneResults, n_checks_base total, n_checks total).
    """
    m = cand_ids.shape[0]
    results: list[PruneResult] = []
    prev_acc_ids = None
    nb_tot = jnp.int32(0)
    nc_tot = jnp.int32(0)
    for i in range(m):
        pd = pairwise_candidate_dist(data, cand_ids[i], metric)
        skip = None
        if use_epo and prev_acc_ids is not None:
            skip = member_mask(cand_ids[i], prev_acc_ids)
        res = rng_prune(cand_ids[i], cand_dist[i], pd, valid[i],
                        m_limits[i], alphas[i], skip, m_max=m_max)
        results.append(res)
        nb_tot += res.n_checks_base
        nc_tot += res.n_checks
        prev_acc_ids = res.ids
    return results, nb_tot, nc_tot
