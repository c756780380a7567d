"""Pure-jnp reference oracles for every Pallas kernel in this package.

Each function here defines the *semantics* a kernel must match; tests sweep
shapes/dtypes and ``assert_allclose`` kernel output against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import metric as metric_lib


def pairwise_distance_ref(q: jax.Array, x: jax.Array,
                          kernel: str = "l2") -> jax.Array:
    """Pairwise distances under a kernel form (core/metric.py convention).

    Args:
      q: (nq, d) queries.
      x: (nx, d) base vectors.
      kernel: "l2" -> squared L2; "ip" -> 1 - <q, x> (cosine = "ip" over
              unit-normalized inputs, handled at the ops boundary).
    Returns:
      (nq, nx) float32 distances.
    """
    q = q.astype(jnp.float32)
    x = x.astype(jnp.float32)
    cross = q @ x.T                                      # (nq, nx)
    if kernel == "ip":
        return 1.0 - cross
    qn = jnp.sum(q * q, axis=-1, keepdims=True)          # (nq, 1)
    xn = jnp.sum(x * x, axis=-1, keepdims=True).T        # (1, nx)
    d2 = qn + xn - 2.0 * cross
    return jnp.maximum(d2, 0.0)


def l2_distance_ref(q: jax.Array, x: jax.Array) -> jax.Array:
    """Back-compat wrapper: squared-L2 form of ``pairwise_distance_ref``."""
    return pairwise_distance_ref(q, x, "l2")


def gather_distance_ref(
    u: jax.Array,
    c: jax.Array,
    cached: jax.Array | None = None,
    mask: jax.Array | None = None,
    kernel: str = "l2",
) -> jax.Array:
    """Distances from each query to its own gathered candidates.

    This oracle is also the CPU dispatch path (ops.py), so its numerics
    define host-side search results bit-for-bit; the MXU kernel's l2
    norm-expansion (kernels/gather_distance.py) matches it to float
    tolerance only.

    Args:
      u: (b, d) queries.
      c: (b, k, d) per-query candidate vectors (already gathered).
      cached: optional (b, k) previously computed distances.
      mask: optional (b, k) bool; True = "must compute" (cache miss).
            Where False, ``cached`` is passed through unchanged. This encodes
            the paper's V_delta reuse semantics (FastPGT Alg. 3 line 6-9).
      kernel: "l2" or "ip" (see ``pairwise_distance_ref``).
    Returns:
      (b, k) float32 distances.
    """
    u = u.astype(jnp.float32)
    c = c.astype(jnp.float32)
    d2 = metric_lib.kernel_distance(c, u[:, None, :], kernel)
    if mask is not None:
        assert cached is not None
        d2 = jnp.where(mask, d2, cached.astype(jnp.float32))
    return d2


def pairwise_distance_sq8_ref(q: jax.Array, codes: jax.Array,
                              scale: jax.Array, cnorms: jax.Array,
                              kernel: str = "l2") -> jax.Array:
    """Pairwise distances against an int8 scalar-quantized corpus.

    The quantized forms' semantic oracle AND the CPU dispatch path
    (ops.py), mirroring ``pairwise_distance_ref``.  ADC formulation
    (DESIGN.md §16): the fp32 query is pre-scaled once, the cross term is
    one fp32 dot against the upcast codes, contracted over d zero-padded to
    the kernel's 128 lanes — the Pallas kernel's contraction, so interpret
    mode bit-matches (an unpadded dot may group the d-sum differently on
    XLA CPU: one ulp).

    Args:
      q: (nq, d) fp32 queries in prepared space.
      codes: (nx, d) int8 corpus codes.
      scale: (d,) per-dimension symmetric scale.
      cnorms: (nx,) squared norms of the dequantized rows.
      kernel: "l2" | "ip" (core/metric.py convention).
    Returns:
      (nq, nx) float32 distances to the dequantized corpus.
    """
    q = q.astype(jnp.float32)
    lanes = ((0, 0), (0, -q.shape[1] % 128))
    qs = jnp.pad(q * scale[None, :], lanes)
    cross = jax.lax.dot_general(
        qs, jnp.pad(codes.astype(jnp.float32), lanes),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                  # (nq, nx)
    if kernel == "ip":
        return 1.0 - cross
    qn = jnp.sum(q * q, axis=-1, keepdims=True)              # (nq, 1)
    return jnp.maximum((cnorms[None, :] + qn) - 2.0 * cross, 0.0)


def gather_distance_sq8_ref(
    u: jax.Array,
    codes: jax.Array,
    scale: jax.Array,
    cnorms: jax.Array,
    cached: jax.Array | None = None,
    mask: jax.Array | None = None,
    kernel: str = "l2",
) -> jax.Array:
    """Gathered distances against int8 codes, V_delta cache semantics.

    The quantized twin of ``gather_distance_ref`` — also the CPU dispatch
    path.  Same ADC formulation as ``pairwise_distance_sq8_ref``; the
    Pallas tile computes the same math as a (bk, d) gemm, which interpret
    mode matches to fp32 accumulation tolerance (tests/test_kernels.py).

    Args:
      u: (b, d) fp32 queries in prepared space.
      codes: (b, k, d) int8 per-query gathered candidate codes.
      scale: (d,) per-dimension symmetric scale.
      cnorms: (b, k) squared norms of the dequantized candidates.
      cached/mask: V_delta reuse as in ``gather_distance_ref``.
    Returns:
      (b, k) float32 distances to the dequantized candidates.
    """
    u = u.astype(jnp.float32)
    qs = u * scale[None, :]
    cross = jax.lax.dot_general(
        codes.astype(jnp.float32), qs,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)                  # (b, k)
    if kernel == "ip":
        d2 = 1.0 - cross
    else:
        qn = jnp.sum(u * u, axis=-1, keepdims=True)          # (b, 1)
        d2 = jnp.maximum((cnorms + qn) - 2.0 * cross, 0.0)
    if mask is not None:
        assert cached is not None
        d2 = jnp.where(mask, d2, cached.astype(jnp.float32))
    return d2


def _window_mask(sq: int, sk: int, q_off: int, causal: bool, window: int) -> jax.Array:
    """Boolean (sq, sk) mask; True = attend."""
    qi = q_off + jnp.arange(sq)[:, None]
    ki = jnp.arange(sk)[None, :]
    m = jnp.ones((sq, sk), dtype=bool)
    if causal:
        m &= ki <= qi
    if window > 0:
        m &= ki > qi - window
    return m


def flash_attention_chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    q_offset: int = 0,
    chunk: int = 1024,
) -> jax.Array:
    """XLA flash attention: lax.scan over KV chunks with online softmax.

    Same computation graph the Pallas kernel performs, expressed in pure
    jnp — the memory-bounded path used for long sequences on backends
    without Pallas (dry-run lowering, CPU tests).  Matches
    flash_attention_ref to float tolerance.
    """
    orig_dtype = q.dtype
    b, h, sq, dh = q.shape
    sk = k.shape[2]
    s = (1.0 / (dh ** 0.5)) if scale is None else scale
    chunk = min(chunk, sk)
    pad = (-sk) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nkc = (sk + pad) // chunk
    q32 = q.astype(jnp.float32) * s
    kc = jnp.moveaxis(k.astype(jnp.float32).reshape(b, h, nkc, chunk, dh),
                      2, 0)
    vc = jnp.moveaxis(v.astype(jnp.float32).reshape(b, h, nkc, chunk, dh),
                      2, 0)
    qpos = q_offset + jnp.arange(sq)

    def step(carry, inp):
        m, l, acc, j = carry
        kj, vj = inp
        logits = jnp.einsum("bhqd,bhkd->bhqk", q32, kj)
        if softcap > 0.0:
            logits = softcap * jnp.tanh(logits / softcap)
        kpos = j * chunk + jnp.arange(chunk)
        mask = kpos[None, :] < sk
        if causal:
            mask = mask & (kpos[None, :] <= qpos[:, None])
        if window > 0:
            mask = mask & (kpos[None, :] > qpos[:, None] - window)
        logits = jnp.where(mask[None, None], logits, -1e30)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        p = jnp.where(mask[None, None], p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vj)
        return (m_new, l_new, acc_new, j + 1), None

    m0 = jnp.full((b, h, sq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = jnp.zeros((b, h, sq, dh), jnp.float32)
    (m, l, acc, _), _ = jax.lax.scan(step, (m0, l0, a0, jnp.int32(0)),
                                     (kc, vc))
    out = acc / jnp.where(l > 0, l, 1.0)[..., None]
    return out.astype(orig_dtype)


def flash_attention_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    q_offset: int = 0,
) -> jax.Array:
    """Reference attention.

    Args:
      q: (b, h, sq, dh); k, v: (b, h, sk, dh) — GQA repeat happens *outside*.
      causal: apply causal mask (query i attends keys <= q_offset + i).
      window: if > 0, sliding local window (attend keys in (qi-window, qi]).
      softcap: if > 0, logits = softcap * tanh(logits / softcap) (gemma2-style).
      scale: logit scale; default 1/sqrt(dh).
      q_offset: absolute position of q[0] relative to k[0] (decode/prefill-chunk).
    Returns:
      (b, h, sq, dh) in q.dtype.
    """
    orig_dtype = q.dtype
    q = q.astype(jnp.float32)
    k = k.astype(jnp.float32)
    v = v.astype(jnp.float32)
    dh = q.shape[-1]
    s = (1.0 / jnp.sqrt(dh)) if scale is None else scale
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * s
    if softcap > 0.0:
        logits = softcap * jnp.tanh(logits / softcap)
    m = _window_mask(q.shape[2], k.shape[2], q_offset, causal, window)
    logits = jnp.where(m[None, None], logits, -jnp.inf)
    # Rows that are fully masked (can happen with tiny windows) -> zeros.
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return out.astype(orig_dtype)
