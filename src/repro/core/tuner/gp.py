"""Pure-JAX Gaussian-process regression (VDTuner's surrogate model).

RBF kernel with ARD lengthscales; hyperparameters (log lengthscales, log
signal variance, log noise) fit by Adam on the exact log marginal likelihood.
Inputs live in the unit hypercube (ParamSpace.encode); targets are
standardized internally.  Everything is f64-free and Cholesky-based with a
jitter floor, sized for the O(100) observations a tuning run produces.

``fit``, ``predict`` and ``sample`` run at ``highest`` matmul precision:
an f32 dot at default precision on the TPU's MXU rounds its operands to
bf16, which moves the posterior mean by ~6e-2 and leaves the predictive
covariance without a Cholesky factor (measured on a v5e; the CPU is
unaffected).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp


@dataclasses.dataclass
class GPState:
    x: jax.Array          # (n, d) observed inputs in [0, 1]^d
    y: jax.Array          # (n,) raw targets
    log_ls: jax.Array     # (d,)
    log_sf: jax.Array     # ()
    log_sn: jax.Array     # ()
    y_mean: jax.Array
    y_std: jax.Array
    chol: jax.Array       # (n, n) cholesky of K + sn I
    alpha: jax.Array      # (n,) K^-1 (y - mean)/std


def _kernel(x1, x2, log_ls, log_sf):
    ls = jnp.exp(log_ls)
    a = x1 / ls
    b = x2 / ls
    d2 = (jnp.sum(a * a, -1, keepdims=True) + jnp.sum(b * b, -1)
          - 2.0 * (a @ b.T))
    return jnp.exp(log_sf) * jnp.exp(-0.5 * jnp.maximum(d2, 0.0))


def _nll(params, x, y):
    log_ls, log_sf, log_sn = params
    n = x.shape[0]
    k = _kernel(x, x, log_ls, log_sf) + (jnp.exp(log_sn) + 1e-6) * jnp.eye(n)
    chol = jnp.linalg.cholesky(k)
    alpha = jax.scipy.linalg.cho_solve((chol, True), y)
    return (0.5 * y @ alpha + jnp.sum(jnp.log(jnp.diag(chol)))
            + 0.5 * n * jnp.log(2 * jnp.pi))


@functools.partial(jax.jit, static_argnames=("steps",))
def _fit_params(x, y, log_ls0, log_sf0, log_sn0, *, steps: int = 80):
    params = (log_ls0, log_sf0, log_sn0)
    adam_m = jax.tree_util.tree_map(jnp.zeros_like, params)
    adam_v = jax.tree_util.tree_map(jnp.zeros_like, params)
    lr, b1, b2, eps = 0.08, 0.9, 0.999, 1e-8
    grad_fn = jax.grad(_nll)

    def body(i, st):
        params, m, v = st
        g = grad_fn(params, x, y)
        m = jax.tree_util.tree_map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
        v = jax.tree_util.tree_map(lambda a, b: b2 * a + (1 - b2) * b * b,
                                   v, g)
        t = i + 1.0
        def upd(p, mi, vi):
            mh = mi / (1 - b1 ** t)
            vh = vi / (1 - b2 ** t)
            return p - lr * mh / (jnp.sqrt(vh) + eps)
        params = jax.tree_util.tree_map(upd, params, m, v)
        return params, m, v

    params, _, _ = jax.lax.fori_loop(0., float(steps), body,
                                     (params, adam_m, adam_v))
    return params


@jax.default_matmul_precision("highest")
def fit(x: jax.Array, y: jax.Array, *, steps: int = 80) -> GPState:
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    y_mean = jnp.mean(y)
    y_std = jnp.maximum(jnp.std(y), 1e-6)
    ys = (y - y_mean) / y_std
    d = x.shape[1]
    log_ls, log_sf, log_sn = _fit_params(
        x, ys, jnp.zeros((d,)) - 1.0, jnp.float32(0.0), jnp.float32(-4.0),
        steps=steps)
    n = x.shape[0]
    k = _kernel(x, x, log_ls, log_sf) + (jnp.exp(log_sn) + 1e-6) * jnp.eye(n)
    chol = jnp.linalg.cholesky(k)
    alpha = jax.scipy.linalg.cho_solve((chol, True), ys)
    return GPState(x=x, y=y, log_ls=log_ls, log_sf=log_sf, log_sn=log_sn,
                   y_mean=y_mean, y_std=y_std, chol=chol, alpha=alpha)


@jax.default_matmul_precision("highest")
def predict(gp: GPState, xq: jax.Array, *, full_cov: bool = False
            ) -> tuple[jax.Array, jax.Array]:
    """Posterior mean (q,) and variance (q,) — or covariance (q, q)."""
    xq = jnp.asarray(xq, jnp.float32)
    ks = _kernel(gp.x, xq, gp.log_ls, gp.log_sf)          # (n, q)
    mean = gp.y_mean + gp.y_std * (ks.T @ gp.alpha)
    v = jax.scipy.linalg.solve_triangular(gp.chol, ks, lower=True)
    if full_cov:
        kq = _kernel(xq, xq, gp.log_ls, gp.log_sf)
        cov = (kq - v.T @ v) * gp.y_std ** 2
        cov = cov + 1e-8 * jnp.eye(xq.shape[0])
        return mean, cov
    kq = jnp.exp(gp.log_sf) * jnp.ones(xq.shape[0])
    var = jnp.maximum(kq - jnp.sum(v * v, axis=0), 1e-10) * gp.y_std ** 2
    return mean, var


@jax.default_matmul_precision("highest")
def sample(gp: GPState, xq: jax.Array, key: jax.Array, n_samples: int
           ) -> jax.Array:
    """(n_samples, q) joint posterior samples (full covariance)."""
    mean, cov = predict(gp, xq, full_cov=True)
    chol = jnp.linalg.cholesky(cov)
    z = jax.random.normal(key, (n_samples, xq.shape[0]))
    return mean[None, :] + z @ chol.T
