"""Gathered-candidate distance Pallas kernel with V_delta cache semantics.

During multi-PG construction (FastPGT Alg. 3, mKANNS) each inserted node u
expands frontiers on m graphs; with width-W multi-expansion (DESIGN.md §10)
the candidate neighbor vectors are gathered into (b, W·Mx, d) slabs and
distances to u are needed — *except* where the shared V_delta cache already
holds them.  The kernel computes

  out[b, i] = mask[b, i] ? delta(u[b], c[b, i]) : cached[b, i]

with delta the metric's distance (kernel form "l2": squared L2; "ip":
1 - <u, c>; cosine = "ip" on pre-normalized inputs — see core/metric.py).

MXU formulation: the cross term is one (1, d) · (bk, d)ᵀ row dot per tile —
  ip:  1 - <u, c>                          (pure MXU + affine)
  l2:  ‖c‖² - 2·<u, c> + ‖u‖²             (row norms as a ones-row dot)
so both the cross term and the candidate norms come out of the MXU as
(1, bk) lane rows, already in the output's layout (no column-to-row
relayout in the kernel).  kernels/ref.py remains the semantic oracle (the
l2 norm-expansion matches it to float tolerance, not bit-exactly — the CPU
ops.py dispatch uses the oracle, so host-side results are unchanged).
Every dot asks for ``Precision.HIGHEST``: an f32 dot at default precision
on the chip's MXU rounds its operands to bf16, and the norm expansion
cancels ‖c‖² against 2·<u, c>, so bf16 operands would move l2 distances by
far more than fp32 rounding.

The compute saving on real hardware comes from frontier dedup *before* the
kernel call (fewer rows); the mask keeps bit-exact cache-reuse semantics so
the paper's #dist accounting holds.

Layout and tiling: every per-query operand carries a unit middle axis —
u (b, 1, d), cached/mask/out (b, 1, k) — so each grid step (query i,
candidate tile j) reads blocks whose last two dimensions are either the
array's own or (8·, 128·)-aligned, which is what the TPU lowering accepts.
The candidate slab (1, bk, d) has bk = k rounded up to the dtype's sublane
tile (8 rows for f32, 32 for int8) when k <= 128, else 128; d is padded to
128 lanes.  ``gather_block`` picks bk and ops.py pads to it.  One query per
grid step: correct and compilable, not tuned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BK = 128
_SUBLANES = {1: 32, 2: 16, 4: 8}      # sublane tile rows by itemsize


def gather_block(k: int, dtype) -> int:
    """Candidate tile height for ``k`` gathered rows of ``dtype``: k rounded
    up to the dtype's sublane tile while that stays within DEFAULT_BK, else
    DEFAULT_BK (the caller pads k to a multiple of the returned bk)."""
    align = _SUBLANES[jnp.dtype(dtype).itemsize]
    return min(DEFAULT_BK, -(-k // align) * align)


def _row_dot(a, c):
    """(1, d) · (bk, d)ᵀ -> (1, bk) f32 on the MXU, at full f32 precision."""
    return jax.lax.dot_general(
        a, c, dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _gather_dist_kernel(u_ref, c_ref, cached_ref, mask_ref, o_ref, *,
                        kernel: str):
    u = u_ref[0].astype(jnp.float32)                   # (1, d)
    c = c_ref[0].astype(jnp.float32)                   # (bk, d)
    cross = _row_dot(u, c)                             # (1, bk)
    if kernel == "ip":
        d2 = 1.0 - cross
    else:
        cn = _row_dot(jnp.ones_like(u), c * c)         # (1, bk) ‖c‖²
        un = jnp.sum(u * u, axis=-1, keepdims=True)    # (1, 1)
        d2 = jnp.maximum(cn - 2.0 * cross + un, 0.0)
    o_ref[0] = jnp.where(mask_ref[0] != 0, d2, cached_ref[0])


def _row_spec(n: int) -> pl.BlockSpec:
    """Block (1, 1, n) of query i's (b, 1, ·) operand at tile j."""
    return pl.BlockSpec((1, 1, n), lambda i, j: (i, 0, j))


@functools.partial(jax.jit, static_argnames=("kernel", "bk", "interpret"))
def gather_distance(
    u: jax.Array,
    c: jax.Array,
    cached: jax.Array,
    mask: jax.Array,
    *,
    kernel: str = "l2",
    bk: int = DEFAULT_BK,
    interpret: bool = False,
) -> jax.Array:
    """(b, d), (b, k, d), (b, k), (b, k)bool -> (b, k) float32; k % bk == 0."""
    b, d = u.shape
    b2, k, d2 = c.shape
    assert (b, d) == (b2, d2), (u.shape, c.shape)
    assert k % bk == 0, (k, bk)
    out = pl.pallas_call(
        functools.partial(_gather_dist_kernel, kernel=kernel),
        grid=(b, k // bk),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, bk, d), lambda i, j: (i, j, 0)),
            _row_spec(bk),
            _row_spec(bk),
        ],
        out_specs=_row_spec(bk),
        out_shape=jax.ShapeDtypeStruct((b, 1, k), jnp.float32),
        interpret=interpret,
    )(u[:, None, :], c, cached.astype(jnp.float32)[:, None, :],
      mask.astype(jnp.int32)[:, None, :])
    return out[:, 0, :]


def _gather_dist_sq8_kernel(qs_ref, qn_ref, c_ref, cn_ref, cached_ref,
                            mask_ref, o_ref, *, kernel: str):
    """Int8 form of the gather kernel (DESIGN.md §16): candidate slabs
    arrive as int8 codes (4× the VMEM residency of the fp32 slab) and are
    upcast in-register; the query row is pre-scaled by the SQ scale (ADC)
    and ``cn`` carries the precomputed dequantized-row norms, so l2 prices
    exact distances to the dequantized corpus.  Cache semantics unchanged."""
    qs = qs_ref[0].astype(jnp.float32)                 # (1, d) q·scale
    c = c_ref[0].astype(jnp.float32)                   # (bk, d) int8 codes
    cross = _row_dot(qs, c)                            # (1, bk)
    if kernel == "ip":
        d2 = 1.0 - cross
    else:
        # (1, 1) ‖q‖² + (1, bk) ‖ĉ‖²
        d2 = jnp.maximum((cn_ref[0] + qn_ref[0]) - 2.0 * cross, 0.0)
    o_ref[0] = jnp.where(mask_ref[0] != 0, d2, cached_ref[0])


@functools.partial(jax.jit, static_argnames=("kernel", "bk", "interpret"))
def gather_distance_sq8(
    qs: jax.Array,
    qn: jax.Array,
    codes: jax.Array,
    cn: jax.Array,
    cached: jax.Array,
    mask: jax.Array,
    *,
    kernel: str = "l2",
    bk: int = DEFAULT_BK,
    interpret: bool = False,
) -> jax.Array:
    """Gathered distances against int8 codes; k % bk == 0.

    Shapes: qs (b, d) f32 pre-scaled queries, qn (b, 1) f32 query norms,
    codes (b, k, d) int8, cn (b, k) f32, cached/mask (b, k) -> (b, k) f32.
    """
    b, d = qs.shape
    b2, k, d2 = codes.shape
    assert (b, d) == (b2, d2), (qs.shape, codes.shape)
    assert k % bk == 0, (k, bk)
    out = pl.pallas_call(
        functools.partial(_gather_dist_sq8_kernel, kernel=kernel),
        grid=(b, k // bk),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, bk, d), lambda i, j: (i, j, 0)),
            _row_spec(bk),
            _row_spec(bk),
            _row_spec(bk),
        ],
        out_specs=_row_spec(bk),
        out_shape=jax.ShapeDtypeStruct((b, 1, k), jnp.float32),
        interpret=interpret,
    )(qs[:, None, :], qn[:, None, :], codes, cn[:, None, :],
      cached.astype(jnp.float32)[:, None, :],
      mask.astype(jnp.int32)[:, None, :])
    return out[:, 0, :]
