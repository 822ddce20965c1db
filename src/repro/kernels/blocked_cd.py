"""Pallas TPU kernel: blocked semi-parallel CD cycle on a Gram tile.

``gram_cd`` runs d-GLMNET's within-tile cycle as F dependent scalar
soft-threshold steps — correct, but the VPU/MXU idle between steps. This
kernel breaks the chain into F/B dependent steps: each B-wide block is
updated proximal-Jacobi style from the shared gradient snapshot
``g = c - s`` (one lane-masked vector soft-threshold), then applied with a
single ``(1, F) @ (F, F)`` MXU matvec ``s += d_blk @ G`` before the next
block. The paper's Theorem-1 convergence only needs the block-separable
model plus the global line search, so the within-tile cycle is free to be
semi-parallel (Shotgun, arXiv:1105.5379; inexact block solves with a
line-search safeguard, arXiv:1405.4544).

The per-block safeguard decision is *precomputed outside the kernel* from
G alone (``core.subproblem.blocked_cycle_modes`` — a Gershgorin dominance
check, iterate-independent) and passed in as a scalar-memory mode vector:

* mode 0 — full-B Jacobi step;
* mode 1 — two sequential B/2-wide Jacobi sub-steps (halved block);
* mode 2 — the sequential scalar chain over the block (pathological
  correlation; identical math to ``gram_cd`` restricted to the block).

VMEM budget matches ``gram_cd`` (G F^2 + 6 vectors); F stays 128-aligned
in the hot paths. Validated on CPU with ``interpret=True`` against
``ref.blocked_cd_ref`` (= the core solver's own blocked cycle, which is
bit-identical to the sequential chain at B=1).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import out_shape_struct
from repro.core.subproblem import DOM_TOL, blocked_cycle_modes


def _make_blocked_cd_kernel(block: int):
    """Kernel body closure over the static block width B."""

    def kernel(scal_ref, modes_ref, G_ref, h_ref, c_ref, beta_ref,
               dbeta0_ref, d_ref, s_ref):
        """Refs: scal (1,1)=[lam] SMEM; modes (1, F/B) int32 SMEM;
        G (F,F), h (1,F)=diag+nu, c/beta/dbeta0 (1,F) VMEM; out d (1,F);
        scratch s (1,F) = G @ d maintained incrementally."""
        f = G_ref.shape[0]
        nb = f // block
        lam = scal_ref[0, 0]

        d_ref[...] = jnp.zeros_like(d_ref)
        s_ref[...] = jnp.zeros_like(s_ref)

        lane = jax.lax.broadcasted_iota(jnp.int32, (1, f), 1)

        def jacobi_step(start, width):
            # proximal-Jacobi on [start, start+width): full-lane vector
            # soft-threshold, update masked to the block
            mask = jnp.logical_and(lane >= start,
                                   lane < start + width).astype(jnp.float32)
            h = h_ref[...]
            b_old = beta_ref[...] + dbeta0_ref[...] + d_ref[...]
            u = (c_ref[...] - s_ref[...]) + b_old * h
            b_new = jnp.sign(u) * jnp.maximum(jnp.abs(u) - lam, 0.0) / h
            delta = (b_new - b_old) * mask                     # (1, F)
            # s += G @ d_blk as one MXU matvec (G symmetric)
            s_ref[...] = s_ref[...] + jnp.dot(
                delta, G_ref[...], preferred_element_type=jnp.float32)
            d_ref[...] = d_ref[...] + delta

        def seq_step(j):
            # one scalar chain step (== gram_cd's body at coordinate j)
            onehot = (lane == j).astype(jnp.float32)
            g = jnp.sum((c_ref[...] - s_ref[...]) * onehot)
            h = jnp.sum(h_ref[...] * onehot)
            b_old = jnp.sum(
                (beta_ref[...] + dbeta0_ref[...] + d_ref[...]) * onehot)
            u = g + b_old * h
            b_new = jnp.sign(u) * jnp.maximum(jnp.abs(u) - lam, 0.0) / h
            delta = b_new - b_old
            g_row = G_ref[pl.ds(j, 1), :]                       # (1, F)
            s_ref[...] = s_ref[...] + delta * g_row
            d_ref[...] = d_ref[...] + delta * onehot

        def body(b, _):
            start = b * block
            mode = modes_ref[0, b]

            @pl.when(mode == 0)
            def _():
                jacobi_step(start, block)

            if block >= 2:       # a 1-wide block is always mode 0
                @pl.when(mode == 1)
                def _():
                    jacobi_step(start, block // 2)
                    jacobi_step(start + block // 2, block // 2)

                @pl.when(mode == 2)
                def _():
                    def chain(j, carry):
                        seq_step(j)
                        return carry

                    jax.lax.fori_loop(start, start + block, chain, 0)
            return 0

        jax.lax.fori_loop(0, nb, body, 0)

    return kernel


@partial(jax.jit, static_argnames=("block", "interpret"))
def blocked_cd_pallas(G, c, beta, dbeta0, lam, nu, *, block: int = 16,
                      dom_tol: float = DOM_TOL, interpret: bool = True):
    """Returns d such that dbeta <- dbeta0 + d (one blocked CD cycle)."""
    f = G.shape[0]
    assert G.shape == (f, f) and c.shape == (f,)
    if f % block:
        raise ValueError(f"block={block} must divide the tile width F={f}")
    nb = f // block
    G = G.astype(jnp.float32)
    # safeguard decision + curvature precomputed outside the kernel: both
    # depend only on G, and the mode vector lives in scalar memory
    modes = blocked_cycle_modes(G, block, nu=nu, dom_tol=dom_tol)[None]
    h = (jnp.diagonal(G) + jnp.asarray(nu, jnp.float32))[None]
    scal = jnp.asarray(lam, jnp.float32).reshape(1, 1)
    out_shape = out_shape_struct((1, f), jnp.float32,
                                 operands=(c, beta, dbeta0, G))
    out = pl.pallas_call(
        _make_blocked_cd_kernel(block),
        grid=(),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),            # lam
            pl.BlockSpec(memory_space=pltpu.SMEM),            # modes
            pl.BlockSpec((f, f), lambda: (0, 0)),             # G in VMEM
            pl.BlockSpec((1, f), lambda: (0, 0)),             # h = diag + nu
            pl.BlockSpec((1, f), lambda: (0, 0)),
            pl.BlockSpec((1, f), lambda: (0, 0)),
            pl.BlockSpec((1, f), lambda: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, f), lambda: (0, 0)),
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((1, f), jnp.float32)],
        interpret=interpret,
    )(scal, modes.astype(jnp.int32), G, h, c.astype(jnp.float32)[None],
      beta.astype(jnp.float32)[None], dbeta0.astype(jnp.float32)[None])
    return out[0]
