"""Public jit'd wrappers around the Pallas kernels.

Dispatch policy:
  * TPU backend           -> compiled Pallas kernel.
  * CPU (this container)  -> pure-jnp reference (fast, same semantics), unless
                             ``REPRO_PALLAS_INTERPRET=1`` forces interpret-mode
                             Pallas (used by tests to validate the kernels).

All wrappers pad to kernel block alignment and strip padding on the way out,
so callers never see alignment constraints.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.core import metric as metric_lib
from repro.kernels import flash_attention as _fa
from repro.kernels import gather_distance as _gd
from repro.kernels import l2_distance as _l2
from repro.kernels import ref


def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def _use_interpret() -> bool:
    return os.environ.get("REPRO_PALLAS_INTERPRET", "0") == "1"


def _pad_to(x: jax.Array, axis: int, mult: int, value=0.0) -> jax.Array:
    n = x.shape[axis]
    rem = (-n) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad, constant_values=value)


def pairwise_distance(q: jax.Array, x: jax.Array,
                      metric: "str | metric_lib.Metric" = "l2") -> jax.Array:
    """Pairwise metric distances: (nq, d), (nx, d) -> (nq, nx) f32.

    ``metric`` may be any registered metric; cosine unit-normalizes both
    sides here so the kernel stays a fused matmul (callers that own the
    dataset normalize once via ``Metric.prepare`` and pass the "ip" kernel
    form instead — re-normalizing unit vectors is a numeric no-op).
    """
    met = metric_lib.resolve(metric)
    if met.normalize:
        q = metric_lib.normalize(q)
        x = metric_lib.normalize(x)
    if not (_use_pallas() or _use_interpret()):
        return ref.pairwise_distance_ref(q, x, met.kernel)
    nq, nx = q.shape[0], x.shape[0]
    bq = min(_l2.DEFAULT_BQ, max(8, nq))
    bx = min(_l2.DEFAULT_BX, max(8, nx))
    qp = _pad_to(_pad_to(q, 0, bq), 1, 128)
    xp = _pad_to(_pad_to(x, 0, bx), 1, 128)
    out = _l2.pairwise_distance(qp, xp, kernel=met.kernel, bq=bq, bx=bx,
                                interpret=_use_interpret())
    return out[:nq, :nx]


def l2_distance(q: jax.Array, x: jax.Array) -> jax.Array:
    """Pairwise squared L2: (nq, d), (nx, d) -> (nq, nx) f32."""
    return pairwise_distance(q, x, "l2")


def gather_distance(u, c, cached=None, mask=None,
                    metric: "str | metric_lib.Metric" = "l2") -> jax.Array:
    """V_delta-aware gathered distances: see kernels/gather_distance.py."""
    met = metric_lib.resolve(metric)
    if met.normalize:
        u = metric_lib.normalize(u)
        c = metric_lib.normalize(c)
    b, k = c.shape[0], c.shape[1]
    if cached is None:
        cached = jnp.zeros((b, k), jnp.float32)
        mask = jnp.ones((b, k), dtype=bool)
    if not (_use_pallas() or _use_interpret()):
        return ref.gather_distance_ref(u, c, cached, mask, met.kernel)
    bk = _gd.gather_block(k, c.dtype)
    cp = _pad_to(_pad_to(c, 1, bk), 2, 128)
    cachedp = _pad_to(cached, 1, bk)
    maskp = _pad_to(mask, 1, bk, value=True)
    up = _pad_to(u, 1, 128)
    out = _gd.gather_distance(up, cp, cachedp, maskp, kernel=met.kernel,
                              bk=bk, interpret=_use_interpret())
    return out[:, :k]


def pairwise_distance_q(q: jax.Array, quant,
                        metric: "str | metric_lib.Metric" = "l2"
                        ) -> jax.Array:
    """Pairwise distances against an SQ8 corpus (DESIGN.md §16).

    ``quant`` is a ``metric.QuantizedData`` over prepared-space vectors;
    the query stays fp32 (cosine normalizes it here) and is pre-scaled by
    the SQ scale once (ADC).  Returns (nq, nx) f32 distances to the
    dequantized corpus.
    """
    met = metric_lib.resolve(metric)
    if met.normalize:
        q = metric_lib.normalize(q)
    codes, scale, cnorms = quant.codes, quant.scale, quant.norms
    if not (_use_pallas() or _use_interpret()):
        return ref.pairwise_distance_sq8_ref(q, codes, scale, cnorms,
                                             met.kernel)
    q = q.astype(jnp.float32)
    qs = q * scale[None, :]
    qn = jnp.sum(q * q, axis=-1, keepdims=True)
    nq, nx = q.shape[0], codes.shape[0]
    bq = min(_l2.DEFAULT_BQ, max(8, nq))
    bx = min(_l2.DEFAULT_BX, max(8, nx))
    qsp = _pad_to(_pad_to(qs, 0, bq), 1, 128)
    qnp = _pad_to(qn, 0, bq)
    cp = _pad_to(_pad_to(codes, 0, bx), 1, 128)
    cnp = _pad_to(cnorms[:, None], 0, bx)
    out = _l2.pairwise_distance_sq8(qsp, qnp, cp, cnp, kernel=met.kernel,
                                    bq=bq, bx=bx,
                                    interpret=_use_interpret())
    return out[:nq, :nx]


def gather_distance_q(u, codes, scale, cnorms, cached=None, mask=None,
                      metric: "str | metric_lib.Metric" = "l2") -> jax.Array:
    """V_delta-aware gathered distances against SQ8 codes (DESIGN.md §16).

    ``u`` (b, d) fp32 queries, ``codes`` (b, k, d) int8 gathered candidate
    codes, ``scale`` (d,), ``cnorms`` (b, k) dequantized-row norms;
    cache semantics as in ``gather_distance``.
    """
    met = metric_lib.resolve(metric)
    if met.normalize:
        u = metric_lib.normalize(u)
    b, k = codes.shape[0], codes.shape[1]
    if cached is None:
        cached = jnp.zeros((b, k), jnp.float32)
        mask = jnp.ones((b, k), dtype=bool)
    if not (_use_pallas() or _use_interpret()):
        return ref.gather_distance_sq8_ref(u, codes, scale, cnorms, cached,
                                           mask, met.kernel)
    u = u.astype(jnp.float32)
    qs = u * scale[None, :]
    qn = jnp.sum(u * u, axis=-1, keepdims=True)
    bk = _gd.gather_block(k, codes.dtype)
    cp = _pad_to(_pad_to(codes, 1, bk), 2, 128)
    cnp = _pad_to(cnorms, 1, bk)
    cachedp = _pad_to(cached, 1, bk)
    maskp = _pad_to(mask, 1, bk, value=True)
    qsp = _pad_to(qs, 1, 128)
    out = _gd.gather_distance_sq8(qsp, qn, cp, cnp, cachedp, maskp,
                                  kernel=met.kernel, bk=bk,
                                  interpret=_use_interpret())
    return out[:, :k]


def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    scale=None, q_offset=0) -> jax.Array:
    """(b, h, sq, dh) x (b, h, sk, dh) -> (b, h, sq, dh).

    Heads must already be GQA-repeated to match q's head count.
    """
    if not (_use_pallas() or _use_interpret()):
        if k.shape[2] > 1024:     # memory-bounded path for long sequences
            return ref.flash_attention_chunked(
                q, k, v, causal=causal, window=window, softcap=softcap,
                scale=scale, q_offset=q_offset)
        return ref.flash_attention_ref(
            q, k, v, causal=causal, window=window, softcap=softcap,
            scale=scale, q_offset=q_offset)
    b, h, sq, dh = q.shape
    sk = k.shape[2]
    bq = min(_fa.DEFAULT_BQ, max(8, sq))
    bk = min(_fa.DEFAULT_BK, max(8, sk))
    qf = _pad_to(q.reshape(b * h, sq, dh), 1, bq)
    kf = _pad_to(k.reshape(b * h, sk, dh), 1, bk)
    vf = _pad_to(v.reshape(b * h, sk, dh), 1, bk)
    out = _fa.flash_attention(
        qf, kf, vf, causal=causal, window=window, softcap=softcap,
        scale=scale, q_offset=q_offset, bq=bq, bk=bk, kv_len=sk,
        interpret=_use_interpret())
    return out[:, :sq].reshape(b, h, sq, dh)
