"""Pallas TPU kernel: fused logistic working statistics.

One pass over the margin cache m_i = beta^T x_i producing everything the
d-GLMNET outer iteration needs from the examples axis (paper eq. (4)):

    p = sigmoid(m) (clamped), w = p(1-p), z = ((y+1)/2 - p)/w,
    nll_partial = sum softplus(-y m)

Fusing avoids 4 separate HBM sweeps over the O(n) vectors — this matters
because the examples axis is the big one (n up to 45M in Table 2). Tiled
(1, BLOCK) over n with a grid; per-block partial NLL sums land in lane 0
of one (1, 128) row per block and are reduced by the wrapper.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

P_EPS = 1e-5
W_MIN = 1e-6


def _logistic_stats_kernel(m_ref, y_ref, w_ref, z_ref, nll_ref):
    m = m_ref[...].astype(jnp.float32)
    y = y_ref[...].astype(jnp.float32)
    p = jax.nn.sigmoid(m)
    p = jnp.clip(p, P_EPS, 1.0 - P_EPS)
    w = jnp.maximum(p * (1.0 - p), W_MIN)
    w_ref[...] = w
    z_ref[...] = ((y + 1.0) * 0.5 - p) / w
    # the block's partial NLL in lane 0 of a lane-wide (1, 128) row, a
    # block shape the TPU accepts; the wrapper sums every row
    lane = jax.lax.broadcasted_iota(jnp.int32, nll_ref.shape, 1)
    nll_ref[...] = jnp.where(lane == 0, jnp.sum(jax.nn.softplus(-y * m)), 0.0)


@partial(jax.jit, static_argnames=("block", "interpret"))
def logistic_stats_pallas(m, y, *, block: int = 4096, interpret: bool = True):
    """Returns (w, z, nll). m, y: (n,) float32."""
    n = m.shape[0]
    pad = (-n) % block
    if pad:
        # padded tail: y=+1, m=+40 -> w=W_MIN clamp, softplus ~ 0
        m = jnp.pad(m, (0, pad), constant_values=40.0)
        y = jnp.pad(y, (0, pad), constant_values=1.0)
    npad = m.shape[0]
    grid = (npad // block,)

    w, z, nll = pl.pallas_call(
        _logistic_stats_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, block), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, 128), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, npad), jnp.float32),
            jax.ShapeDtypeStruct((1, npad), jnp.float32),
            jax.ShapeDtypeStruct((1, grid[0] * 128), jnp.float32),
        ],
        interpret=interpret,
    )(m[None].astype(jnp.float32), y[None].astype(jnp.float32))
    return w[0, :n], z[0, :n], jnp.sum(nll)
