"""Traffic kind ``estimate_fit``: the ``estimate`` kind's tuning loop,
with the distance error compared by its shape as well as its size.

The loop, the taps and what the window measures are ``estimate``'s, and
so is the comparison with the exact (float64) distances.  At a wide row
the size of the error does not tell float32 from the next precision
below: the error of a float32 norm expansion grows with the width, while
that of a bfloat16x3 cross term (``Precision.HIGH``: each operand split
into a bfloat16 pair, three products kept) shrinks relative to the norms
as 1/sqrt(d), so at d=960 their mean and widest gaps lie within 3x of
each other.  Their shapes differ: bfloat16x3 drops the products of the
split's low parts, a fixed function of the two rows that the reference
can compute.  So this kind adds, per built group's sampled edges and per
top-k pool of one configuration at one ef, how much of the observed
error follows that function::

    fit = |sum(e * s)| / sum(s * s)
    e = (distance - exact) / (|a|^2 + |b|^2)
    s = (bfloat16x3 distance - exact) / (|a|^2 + |b|^2)

It reads ~1 for distances whose cross term was taken at bfloat16x3 and
~0 for an error independent of it, as float32's rounding is.
``edge_bf16x3_fit`` and ``pool_bf16x3_fit`` are the widest over the
groups and over the pools; ``edge_dist_err_mean`` and
``pool_dist_err_mean``, the mean relative gaps (the widest of the
groups' and pools'), are readings.

A configuration states its sizes per traffic kind, so this kind reads
``n`` and ``queries`` under ``estimate_fit``.
"""
from __future__ import annotations

import numpy as np

import estimate


def _bf16(x) -> np.ndarray:
    """float32 rounded to bfloat16's 8 significant bits (to nearest even),
    kept in float32."""
    u = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32)


class Rows:
    """float64 rows with their squared norms and bfloat16 split."""

    def __init__(self, x):
        x = np.asarray(x, np.float32)
        hi = _bf16(x)
        self.x = x.astype(np.float64)
        self.norm = (self.x * self.x).sum(-1)
        self.hi = hi.astype(np.float64)
        self.lo = _bf16(x - hi).astype(np.float64)


def _dot(u, v) -> np.ndarray:
    return np.einsum("ij,ij->i", u, v)


def fit_terms(ra: Rows, ia, rb: Rows, ib, got, block: int = 8192):
    """(error e, bfloat16x3 error s), both relative to the norms, of the
    distances ``got`` between rows ra[ia] and rb[ib]."""
    e, s = [], []
    for off in range(0, len(ia), block):
        i, j = ia[off:off + block], ib[off:off + block]
        scale = np.maximum(ra.norm[i] + rb.norm[j], 1e-30)
        exact = ra.norm[i] + rb.norm[j] - 2.0 * _dot(ra.x[i], rb.x[j])
        bhi = rb.hi[j]
        cross3 = _dot(ra.hi[i], bhi + rb.lo[j]) + _dot(ra.lo[i], bhi)
        bf3 = ra.norm[i] + rb.norm[j] - 2.0 * cross3
        got_ = np.asarray(got[off:off + block], np.float64)
        e.append((got_ - exact) / scale)
        s.append((bf3 - exact) / scale)
    return np.concatenate(e), np.concatenate(s)


def fit(e, s) -> float:
    return float(abs((e * s).sum()) / max((s * s).sum(), 1e-300))


class Cell(estimate.Cell):
    def __init__(self, cfg: dict, mix: dict, seed: int, span):
        sizes = {key: {"estimate": cfg[key]["estimate_fit"]}
                 for key in ("n", "queries")}
        super().__init__(dict(cfg, **sizes), mix, seed, span)

    def release(self):
        super().release()
        # the comparison reads the top k of each pool
        self.out["pools"] = [[(ef, ids[:, :self.k], dist[:, :self.k])
                              for ef, ids, dist in per]
                             for per in self.out["pools"]]

    def compare(self) -> dict:
        numbers = super().compare()
        if numbers["outputs_missing"]:
            return numbers
        xr, qr, nodes = Rows(self.x_host), Rows(self.q_host), self.nodes
        sets = {"edge": [], "pool": []}
        for ids, dist, _ in self.out["graphs"]:
            ids, dist = ids[:, nodes], dist[:, nodes]
            ok = (ids >= 0) & (ids < self.n)
            rows = np.broadcast_to(nodes[:, None], ids.shape)[ok]
            sets["edge"].append(fit_terms(xr, rows, xr, ids[ok], dist[ok]))
        for pools in self.out["pools"]:
            for _, ids, dist in pools:
                ok = (ids >= 0) & (ids < self.n)
                rows = np.broadcast_to(np.arange(ids.shape[0])[:, None],
                                       ids.shape)[ok]
                sets["pool"].append(fit_terms(qr, rows, xr, ids[ok],
                                              dist[ok]))
        for name, terms in sets.items():
            numbers[name + "_bf16x3_fit"] = max(fit(e, s) for e, s in terms)
            numbers[name + "_dist_err_mean"] = max(
                float(np.abs(e).mean()) if e.size else 0.0 for e, _ in terms)
        return numbers
