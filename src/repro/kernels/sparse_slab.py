"""Pallas TPU kernels: sparse-native by-feature slab suite.

d-GLMNET's headline workloads are extremely sparse (webspam: ~0.02%
dense), and the paper's Table-1 layout stores each feature as its
``(row, value)`` nonzero list. These kernels compute the per-tile
statistics of the quadratic subproblem *directly from the slabs* —
no ``(n_loc, tile)`` densify scatter, no dense FLOPs:

* ``slab_gram_pallas`` — the weighted Gram tile ``G = X_F^T diag(w) X_F``
  and correlation ``c = X_F^T (w r)`` via a match-and-accumulate join over
  nnz slots: for each slot pair ``(k, k')`` a (T, T) broadcast compare of
  the row indices gates an outer-product FMA. Cost is O(T^2 K^2) cheap VPU
  ops against the dense path's O(n_loc T^2) MXU FLOPs + an O(nnz) HBM
  scatter — the sparse form wins when K (nnz per feature per shard) is
  small, exactly the regime the paper's datasets live in. The dispatch
  layer (``kernels.ops``) picks the dense fallback above the density
  threshold.
* ``slab_spmv_pallas`` — ``X_F @ d`` over the example axis without a
  scatter: the output is tiled over ``n_loc`` and each block accumulates
  the slots that match its row range via the same broadcast compare.

Both kernels receive *pre-gathered* weight operands (``w``/``w*r`` looked
up at the slab's row indices, zeroed at sentinels) — the XLA gather
outside the kernel is efficient on every backend, and it keeps the kernel
bodies free of dynamic indexing. Sentinel slots (row == n_loc padding)
must contribute exactly zero: the wrappers zero both the value and the
gathered-weight side, so even adversarial padding values cannot leak row
``n_loc``'s ghost weight into G, c, or the matvec.

Validated on CPU with ``interpret=True`` against ``ref.slab_gram_ref`` /
``ref.slab_spmv_ref`` (densify-based oracles).

Each ``pallas_call`` carries its wrapper's name: it names the kernel's
HLO instruction (``slab_spmv_pallas.1``), which is how a device trace
shows it, and stays put when the code around it is refactored.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import out_shape_struct

# flattened slots per slab_spmv grid step: a (1024, 1) block pads to
# (1024, 128) in VMEM, and its (1024, 256) match against one output block
# stays at 1 MiB
_SLOT_CHUNK = 1024


def _slab_gram_kernel(rowsT_ref, wvT_ref, vaT_ref, cvaT_ref, G_ref, c_ref):
    """Refs, every per-slot operand in (K, T) layout so that each dynamic
    slot slice falls on the sublane axis: rowsT int32 local rows; wvT =
    w[row] * value and vaT = value (both sentinel-zeroed); cvaT = value *
    (w r)[row]. Outs: G (T, T), c (1, T)."""
    k, t = rowsT_ref.shape
    c_ref[...] = jnp.sum(cvaT_ref[...], axis=0, keepdims=True)
    G_ref[...] = jnp.zeros_like(G_ref)

    def outer(ka, _):
        # slot ka's rows and weights as (T, 1)-wide columns, built by
        # broadcasting the (1, T) row down the sublanes and transposing:
        # no lane index is ever dynamic
        ra = jnp.broadcast_to(rowsT_ref[pl.ds(ka, 1), :], (t, t)).T
        wa = jnp.broadcast_to(wvT_ref[pl.ds(ka, 1), :], (t, t)).T

        def inner(kb, _):
            rb = rowsT_ref[pl.ds(kb, 1), :]                    # (1, T)
            vb = vaT_ref[pl.ds(kb, 1), :]                      # (1, T)
            eq = (ra == rb).astype(jnp.float32)                # (T, T) match
            G_ref[...] = G_ref[...] + (wa * eq) * vb
            return 0

        jax.lax.fori_loop(0, k, inner, 0)
        return 0

    jax.lax.fori_loop(0, k, outer, 0)


@partial(jax.jit, static_argnames=("interpret",))
def slab_gram_pallas(rows, wv, va, cva, *, interpret: bool = True):
    """Gram/correlation from one feature-tile slab.

    rows (T, K) int32 local row indices (sentinel anywhere >= n_loc);
    wv = w[rows] * values with sentinel slots zeroed; va = values with
    sentinel slots zeroed; cva = values * (w*r)[rows] sentinel-zeroed.
    Returns (G (T, T), c (T,)).
    """
    t, k = rows.shape
    out_g = out_shape_struct((t, t), jnp.float32, operands=(wv, va, cva))
    out_c = out_shape_struct((1, t), jnp.float32, operands=(wv, va, cva))
    G, c = pl.pallas_call(
        _slab_gram_kernel,
        grid=(),
        in_specs=[pl.BlockSpec((k, t), lambda: (0, 0))] * 4,
        out_specs=[
            pl.BlockSpec((t, t), lambda: (0, 0)),
            pl.BlockSpec((1, t), lambda: (0, 0)),
        ],
        out_shape=[out_g, out_c],
        interpret=interpret,
        name="slab_gram_pallas",
    )(rows.T, wv.astype(jnp.float32).T, va.astype(jnp.float32).T,
      cva.astype(jnp.float32).T)
    return G, c[0]


def _slab_spmv_kernel(rows_ref, dv_ref, out_ref):
    """Refs: rows (S, 1) int32, one chunk of the flattened slot rows; dv
    (S, 1) = value * d[feature] (sentinel-zeroed); out (1, B). Grid
    (example blocks, slot chunks): each example block accumulates the
    matching slots of every chunk."""
    b = out_ref.shape[1]

    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    base = pl.program_id(0) * b
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, b), 1) + base
    eq = (rows_ref[...] == lane).astype(jnp.float32)           # (S, B)
    out_ref[...] = out_ref[...] + jnp.sum(dv_ref[...] * eq, axis=0)[None, :]


@partial(jax.jit, static_argnames=("n_loc", "block", "interpret"))
def slab_spmv_pallas(rows, dv, *, n_loc: int, block: int = 256,
                     interpret: bool = True):
    """``X_F @ d`` over a slab without densify or scatter.

    rows (T, K) int32; dv (T, K) = values * d[:, None] with sentinel slots
    zeroed. Returns the (n_loc,) per-example product. Output rows are
    tiled ``block`` at a time and the flattened slots ``_SLOT_CHUNK`` at a
    time, so the kernel's VMEM footprint is bounded whatever T * K is.
    """
    npad = n_loc + (-n_loc) % block
    rows_col = rows.reshape(-1, 1)
    dv_col = dv.astype(jnp.float32).reshape(-1, 1)
    n_slots = rows_col.shape[0]
    chunk = min(_SLOT_CHUNK, n_slots + (-n_slots) % 8)
    spad = (-n_slots) % chunk
    if spad:
        # padding slots carry zero value at a row no output block holds
        rows_col = jnp.pad(rows_col, ((0, spad), (0, 0)), constant_values=-1)
        dv_col = jnp.pad(dv_col, ((0, spad), (0, 0)))
    grid = (npad // block, rows_col.shape[0] // chunk)
    out = pl.pallas_call(
        _slab_spmv_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((chunk, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((chunk, 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block), lambda i, j: (0, i)),
        out_shape=out_shape_struct((1, npad), jnp.float32,
                                   operands=(rows, dv)),
        interpret=interpret,
        name="slab_spmv_pallas",
    )(rows_col, dv_col)
    return out[0, :n_loc]
