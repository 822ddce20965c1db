"""Plain reference check of a fitted L1-logistic path.

Judges each path point by what it says, over the whole design the
benchmark generated, with every matrix product at ``HIGHEST`` precision
and every reduction in float32. It imports nothing of the program.

For the objective ``P(beta) = sum_i log(1 + exp(-y_i m_i)) + lam |beta|_1``
(``m = X beta``) with gradient ``g = X^T r``, ``r = sigmoid(m) - (y+1)/2``,
each point gives three readings:

* ``kkt_excess``: ``max |g_j| / lam - 1`` over the coordinates with
  ``beta_j == 0``, or 0 where that is negative (optimality asks for 0;
  the path certifies ``kkt_tol`` at the chip's default precision);
* ``kkt_active``: ``max |g_j + lam sign(beta_j)| / lam`` over the others
  (optimality asks for 0);
* ``gap_rel``: the duality gap at the dual point ``u = -s r``,
  ``s = min(1, lam / max|g|)``, over the primal objective. It is summed as
  nonnegative Fenchel-Young terms, so no large numbers cancel.

A path's reading is the largest over its points.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
NAMES = ("kkt_excess", "kkt_active", "gap_rel")


def _residual(m, y):
    return jax.nn.sigmoid(m) - (y + 1.0) * 0.5


def _readings(m, y, g, beta, lam):
    """The three readings from margins, gradient and coefficients."""
    zero = beta == 0
    kkt_excess = jnp.maximum(
        jnp.max(jnp.where(zero, jnp.abs(g), 0.0)) / lam - 1.0, 0.0)
    kkt_active = jnp.max(jnp.where(
        zero, 0.0, jnp.abs(g + lam * jnp.sign(beta)))) / lam
    r = _residual(m, y)
    s = jnp.minimum(1.0, lam / jnp.maximum(jnp.max(jnp.abs(g)), 1e-30))
    a = jnp.clip(-y * s * r, 0.0, 1.0)          # dual variable in [0, 1]
    z = s * r
    loss = jax.nn.softplus(-y * m)
    conj = jax.scipy.special.xlogy(a, a) + jax.scipy.special.xlogy(
        1.0 - a, 1.0 - a)
    fy = jnp.sum(jnp.maximum(loss + conj - z * m, 0.0))
    l1 = lam * jnp.sum(jnp.abs(beta))
    reg = jnp.sum(jnp.maximum(lam * jnp.abs(beta) + s * g * beta, 0.0))
    primal = jnp.sum(loss) + l1
    return jnp.stack([kkt_excess, kkt_active, (fy + reg) / primal])


@jax.jit
def _dense_point(X, y, beta, lam):
    m = jnp.dot(X, beta, precision=HI)
    g = jnp.dot(_residual(m, y), X, precision=HI)
    return _readings(m, y, g, beta, lam)


def dense_path(data: dict, betas, lambdas) -> dict:
    """Readings of a path over a dense design ``{"X", "y"}``."""
    X, y = data["X"], data["y"]
    out = [_dense_point(X, y, jnp.asarray(betas[i], jnp.float32),
                        jnp.float32(lam))
           for i, lam in enumerate(np.asarray(lambdas))]
    return _worst(out)


def _worst(per_point) -> dict:
    arr = np.asarray(jax.device_get(jnp.stack(per_point)), np.float64)
    return {name: float(arr[:, i].max()) for i, name in enumerate(NAMES)}
