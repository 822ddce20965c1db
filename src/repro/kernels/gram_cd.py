"""Pallas TPU kernel: one cyclic coordinate-descent cycle on a Gram tile.

This is the sequential heart of d-GLMNET's Algorithm 2, restructured for the
TPU memory hierarchy (DESIGN.md §2.3): the caller computes
G = X_F^T diag(w) X_F and c = X_F^T (w r) with MXU matmuls; this kernel then
runs the O(F^2) sequential soft-threshold sweep entirely inside VMEM — the
serial chain never touches HBM or the examples axis.

VMEM budget at F=512, f32: G 1 MiB + 5 vectors ~10 KiB — far under the
~128 MiB/core v5e budget; F is kept 128-aligned for lane efficiency.

Target: pl.pallas_call with explicit BlockSpecs; validated on CPU with
interpret=True against ``ref.gram_cd_ref`` (= core.subproblem oracle).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import out_shape_struct


def _gram_cd_kernel(scal_ref, G_ref, c_ref, beta_ref, dbeta0_ref, d_ref, s_ref):
    """Refs: scal (1,2)=[lam,nu] SMEM; G (F,F); c/beta/dbeta0 (1,F) VMEM;
    out d (1,F); scratch s (1,F) = G @ d maintained incrementally."""
    f = G_ref.shape[0]
    lam = scal_ref[0, 0]
    nu = scal_ref[0, 1]

    d_ref[...] = jnp.zeros_like(d_ref)
    s_ref[...] = jnp.zeros_like(s_ref)

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, f), 1)

    def body(j, _):
        onehot = (lane == j).astype(jnp.float32)              # (1, F)
        # scalar reads via masked reductions (lane-friendly on TPU)
        g = jnp.sum((c_ref[...] - s_ref[...]) * onehot)
        g_row = G_ref[pl.ds(j, 1), :]                         # (1, F)
        h = jnp.sum(g_row * onehot) + nu                      # G[j,j] + nu
        b_old = jnp.sum((beta_ref[...] + dbeta0_ref[...] + d_ref[...]) * onehot)
        u = g + b_old * h
        b_new = jnp.sign(u) * jnp.maximum(jnp.abs(u) - lam, 0.0) / h
        delta = b_new - b_old
        s_ref[...] = s_ref[...] + delta * g_row               # s += delta * G[:,j] (G symmetric)
        d_ref[...] = d_ref[...] + delta * onehot
        return 0

    jax.lax.fori_loop(0, f, body, 0)


@partial(jax.jit, static_argnames=("interpret",))
def gram_cd_pallas(G, c, beta, dbeta0, lam, nu, *, interpret: bool = True):
    """Returns d such that dbeta <- dbeta0 + d (one CD cycle on the tile)."""
    f = G.shape[0]
    assert G.shape == (f, f) and c.shape == (f,)
    scal = jnp.stack([jnp.asarray(lam, jnp.float32), jnp.asarray(nu, jnp.float32)])[None]
    # under shard_map(check_vma=True) the out_shape must carry the varying
    # mesh axes; outputs vary like (c, beta, dbeta0) jointly
    out_shape = out_shape_struct((1, f), jnp.float32, operands=(c, beta, dbeta0, G))
    out = pl.pallas_call(
        _gram_cd_kernel,
        grid=(),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),            # scalars
            pl.BlockSpec((f, f), lambda: (0, 0)),             # G in VMEM
            pl.BlockSpec((1, f), lambda: (0, 0)),
            pl.BlockSpec((1, f), lambda: (0, 0)),
            pl.BlockSpec((1, f), lambda: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, f), lambda: (0, 0)),
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((1, f), jnp.float32)],
        interpret=interpret,
    )(scal, G.astype(jnp.float32), c.astype(jnp.float32)[None],
      beta.astype(jnp.float32)[None], dbeta0.astype(jnp.float32)[None])
    return out[0]
