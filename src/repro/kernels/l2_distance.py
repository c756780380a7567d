"""Blocked pairwise distance Pallas kernel — the Search hot spot.

FastPGT's parameter-estimation cost is dominated by distance computations in
the beam-search (Search) phase of PG construction.  On TPU both kernel forms
reduce to one MXU matmul per tile:

  l2:  ||q - x||^2 = ||q||^2 + ||x||^2 - 2 q.x   (cross term on the MXU)
  ip:  1 - q.x                                    (pure MXU + affine)

Cosine is ip over unit-normalized inputs; normalization happens at the
``ops.py`` boundary (or once per dataset in the builders) so the kernel
stays a fused matmul.  The kernel tiles (nq, nx, d) into VMEM-resident
blocks:

  grid = (nq/bq, nx/bx)
  q tile   : (bq, d)   VMEM
  x tile   : (bx, d)   VMEM
  out tile : (bq, bx)  VMEM

``d`` stays un-blocked (PG datasets have d <= 1024; a 128x1024 f32 tile is
512 KiB, well within the ~16 MiB VMEM budget at the default block sizes).
Block sizes default to MXU-aligned 128x128; the ops.py wrapper pads inputs.
The cross term asks for ``Precision.HIGHEST``: the l2 form cancels
||q||^2 + ||x||^2 against 2 q.x, so bf16-rounded MXU operands would move
distances by far more than fp32 rounding (kernels/gather_distance.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BQ = 128
DEFAULT_BX = 128


def _dist_kernel(q_ref, x_ref, o_ref, *, kernel: str):
    q = q_ref[...].astype(jnp.float32)                    # (bq, d)
    x = x_ref[...].astype(jnp.float32)                    # (bx, d)
    # MXU: (bq, d) @ (d, bx)
    cross = jax.lax.dot_general(
        q, x,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    if kernel == "ip":
        o_ref[...] = 1.0 - cross
    else:
        qn = jnp.sum(q * q, axis=-1, keepdims=True)       # (bq, 1)
        xn = jnp.sum(x * x, axis=-1, keepdims=True)       # (bx, 1)
        o_ref[...] = jnp.maximum(qn + xn.T - 2.0 * cross, 0.0)


@functools.partial(jax.jit,
                   static_argnames=("kernel", "bq", "bx", "interpret"))
def pairwise_distance(
    q: jax.Array,
    x: jax.Array,
    *,
    kernel: str = "l2",
    bq: int = DEFAULT_BQ,
    bx: int = DEFAULT_BX,
    interpret: bool = False,
) -> jax.Array:
    """Pairwise distances via pallas_call; ``kernel`` in {"l2", "ip"}.

    Shapes must be pre-padded: nq % bq == 0, nx % bx == 0.
    Returns (nq, nx) float32.
    """
    nq, d = q.shape
    nx, d2 = x.shape
    assert d == d2, (d, d2)
    assert nq % bq == 0 and nx % bx == 0, (nq, nx, bq, bx)
    grid = (nq // bq, nx // bx)
    return pl.pallas_call(
        functools.partial(_dist_kernel, kernel=kernel),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bx, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bx), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nq, nx), jnp.float32),
        interpret=interpret,
    )(q, x)


def l2_distance(q: jax.Array, x: jax.Array, **kw) -> jax.Array:
    """Back-compat wrapper: squared-L2 form of ``pairwise_distance``."""
    return pairwise_distance(q, x, kernel="l2", **kw)


def _dist_sq8_kernel(qs_ref, qn_ref, c_ref, cn_ref, o_ref, *, kernel: str):
    """Int8 MXU form (DESIGN.md §16): the corpus tile arrives as int8
    codes (4× less HBM→VMEM traffic than fp32 — the point of SQ8) and is
    upcast in-register; the query tile is already pre-scaled by the
    per-dimension SQ scale (ADC), so the cross term prices distances to
    the dequantized corpus exactly.  l2 uses the precomputed dequantized
    row norms (``cn``) instead of re-deriving them from the codes."""
    qs = qs_ref[...].astype(jnp.float32)                  # (bq, d) q·scale
    c = c_ref[...].astype(jnp.float32)                    # (bx, d) int8 codes
    # MXU: (bq, d) @ (d, bx)
    cross = jax.lax.dot_general(
        qs, c,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    if kernel == "ip":
        o_ref[...] = 1.0 - cross
    else:
        qn = qn_ref[...]                                  # (bq, 1) ‖q‖²
        cn = cn_ref[...]                                  # (bx, 1) ‖ĉ‖²
        o_ref[...] = jnp.maximum((cn.T + qn) - 2.0 * cross, 0.0)


@functools.partial(jax.jit,
                   static_argnames=("kernel", "bq", "bx", "interpret"))
def pairwise_distance_sq8(
    qs: jax.Array,
    qn: jax.Array,
    codes: jax.Array,
    cn: jax.Array,
    *,
    kernel: str = "l2",
    bq: int = DEFAULT_BQ,
    bx: int = DEFAULT_BX,
    interpret: bool = False,
) -> jax.Array:
    """Pairwise distances against int8 codes via pallas_call.

    Args (pre-padded: nq % bq == 0, nx % bx == 0):
      qs: (nq, d) f32 pre-scaled queries (q · scale).
      qn: (nq, 1) f32 squared query norms (l2 form; pass zeros for ip).
      codes: (nx, d) int8 corpus codes.
      cn: (nx, 1) f32 dequantized-row squared norms.
    Returns (nq, nx) float32.
    """
    nq, d = qs.shape
    nx, d2 = codes.shape
    assert d == d2, (d, d2)
    assert nq % bq == 0 and nx % bx == 0, (nq, nx, bq, bx)
    grid = (nq // bq, nx // bx)
    return pl.pallas_call(
        functools.partial(_dist_sq8_kernel, kernel=kernel),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bx, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bx, 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bx), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nq, nx), jnp.float32),
        interpret=interpret,
    )(qs, qn, codes, cn)
