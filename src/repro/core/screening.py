"""Feature screening for the regularization path.

Sequential strong rule (Tibshirani et al., JRSS-B 2012, §5) adapted to the
paper's conventions (y in {-1, +1}, margins-cached gradient):

    keep j  iff  |g_j(beta_hat(lam_prev))| >= 2*lam - lam_prev

where g = nabla L(beta) = X^T (sigmoid(m) - (y+1)/2) is the
negative-log-likelihood gradient at the warm-start point. The rule is a
heuristic (it assumes the gradient is 1-Lipschitz along the path), so every
screened solve is followed by a KKT post-check over the *discarded* set;
violations re-enter the working set and the solve repeats. For lasso-type
problems the check passes almost always, making the expected cost of a path
point proportional to the active-set size instead of p.

All predicates run on device; only the active-set *size* crosses to host
(the path driver needs it to pick a gather capacity bucket).
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import engine
from repro.core.objective import grad_nll_from_margins


def nll_grad_abs(X, y, m) -> jnp.ndarray:
    """|g_j| = |x_j^T (sigmoid(m) - (y+1)/2)| for all p features."""
    return jnp.abs(grad_nll_from_margins(m, y, X))


def _nll_residual(m, y):
    """v = sigmoid(m) - (y+1)/2, the per-example NLL gradient factor."""
    return jax.nn.sigmoid(m) - (y + 1.0) * 0.5


@jax.jit
def nll_grad_abs_sparse(row_idx, values, y, m) -> jnp.ndarray:
    """Sparse-native |g_j| over a by-feature layout (paper Table 1).

    ``row_idx``/``values`` are (p, K) with sentinel row index n; the pass
    is the kernel layer's slab correlation ``X^T v`` (a pure gather-reduce
    over the slabs, sentinel slots exact zero) — a dense (n, p) X is never
    materialized. Memory is O(nnz), the size of the slabs themselves.
    """
    from repro.kernels.ops import slab_corr

    return jnp.abs(slab_corr(row_idx, values, _nll_residual(m, y)))


@jax.jit
def strong_rule_mask(g_abs, lam, lam_prev, beta) -> jnp.ndarray:
    """Sequential-strong-rule working set at ``lam`` given the previous
    solution (gradient magnitudes ``g_abs`` and coefficients ``beta`` at
    ``lam_prev``). Ever-active features are always kept: warm starts must
    be representable in the restricted problem.

    The admission threshold is ``max(2*lam - lam_prev, lam)``: the strong
    rule alone degenerates on coarse grids (on the paper's halving grid
    ``2*lam - lam_prev = 0``, admitting everything), so it is intersected
    with the warm-start KKT activation test ``|g_j| > lam`` — features at
    their lam_prev-optimum value cannot activate at lam unless their
    gradient already exceeds lam (GLMNET's ever-active + violators
    strategy). Both halves are heuristic bounds on the gradient's path
    drift; the KKT post-check makes either safe."""
    lam = jnp.float32(lam)
    lam_prev = jnp.maximum(jnp.float32(lam_prev), lam)
    thresh = jnp.maximum(2.0 * lam - lam_prev, lam)
    return jnp.logical_or(g_abs >= thresh, beta != 0.0)


@jax.jit
def kkt_violations(g_abs, lam, mask, *, tol: float = 1e-3) -> jnp.ndarray:
    """KKT post-check on the discarded set.

    At an optimum of the full problem, every j with beta_j = 0 must satisfy
    |g_j| <= lam. Features outside ``mask`` were *forced* to zero by the
    screen, so |g_j| > lam(1+tol) there means the screen was wrong and j
    must re-enter. Returns the boolean violation mask (all-False == screen
    certified).
    """
    slack = lam * (1.0 + tol) + 1e-7
    return jnp.logical_and(jnp.logical_not(mask), g_abs > slack)


def budgeted_admission(viol, g_abs, budget: int):
    """Blitz-style violator admission: keep only the ``budget`` most-violating
    features (largest ``g_abs``) of ``viol``; the rest wait for a later
    round. Admitting every violator at once blows the capacity bucket up a
    power-of-two step (and a solver retrace) for features that frequently
    solve straight back to zero; the budget grows the working set
    incrementally instead. Ties at the cutoff are all admitted (the budget
    is a growth *rate*, not an exact count). Returns the admitted mask."""
    n_viol = int(engine.device_get(viol.sum()))
    if n_viol <= budget:
        return viol
    scores = jnp.where(viol, g_abs, -jnp.inf)
    cutoff = jax.lax.top_k(scores, budget)[0][-1]
    return jnp.logical_and(viol, scores >= cutoff)


def capacity_bucket(count: int, p: int, *, tile: int) -> int:
    """Round an active-set size up to a power-of-two multiple of ``tile``
    (min ``tile``, max ``p``). Bounds the number of distinct restricted
    shapes — and hence solver retraces — to O(log(p / tile)) per path."""
    cap = max(tile, 1)
    while cap < count:
        cap *= 2
    return min(cap, p)


def pack_indices(mask, cap: int) -> jnp.ndarray:
    """Stable front-pack of the selected indices into shape ``(cap,)``,
    sentinel ``p`` (== mask size) marking padding. The shared primitive
    behind the dense column gather here and the slab gather in
    ``data/byfeature.py``."""
    p = mask.shape[0]
    order = jnp.argsort(jnp.where(mask, jnp.arange(p), p))
    return jnp.where(jnp.arange(p) < jnp.sum(mask), order, p)[:cap]


def gather_columns(X, beta, mask, cap: int):
    """Device-side gather of the working set into a (n, cap) problem.

    Returns (X_sub, beta_sub, idx) where idx has shape (cap,) with sentinel
    ``p`` marking padding; padded columns are all-zero, so their
    coordinates provably stay at zero (soft-threshold of a zero gradient)
    and the restricted solve is exactly the masked full solve.
    """
    idx = pack_indices(mask, cap)
    X_sub = jnp.take(X, idx, axis=1, mode="fill", fill_value=0.0)
    beta_sub = jnp.take(beta, idx, mode="fill", fill_value=0.0)
    return X_sub, beta_sub, idx


def scatter_columns(beta_sub, idx, p: int):
    """Inverse of :func:`gather_columns`: restricted solution -> full
    beta (padding rows dropped via out-of-bounds scatter)."""
    return jnp.zeros(p, beta_sub.dtype).at[idx].set(beta_sub, mode="drop")


@lru_cache(maxsize=64)
def _sparse_corr_program(mesh: Mesh, n_loc: int, tile: int,
                         model_axis: str = "model"):
    """The shard_map slab-stream behind both the sparse screen and
    ``Design.correlation``: ``corr(row_idx, values, v) -> X^T v`` (signed),
    feature-sharded P(model). Un-jitted so callers can fuse it into their
    own programs; see :func:`make_sparse_corr` for the jitted form."""
    from repro.core.distributed import _data_axes

    daxes = _data_axes(mesh)
    dspec = P(daxes) if daxes else P()

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(model_axis, daxes, None), P(model_axis, daxes, None),
                  dspec),
        out_specs=P(model_axis),
    )
    def corr(row_idx, values, v):
        from repro.kernels.ops import slab_corr

        rows, vals = row_idx[:, 0, :], values[:, 0, :]
        p_loc, k = rows.shape
        assert p_loc % tile == 0, (
            f"per-shard feature count {p_loc} must be a multiple of "
            f"tile={tile} (pad the slabs upstream)"
        )

        def tile_pass(_, i):
            rt = jax.lax.dynamic_slice(rows, (i * tile, 0), (tile, k))
            vt = jax.lax.dynamic_slice(vals, (i * tile, 0), (tile, k))
            return None, slab_corr(rt, vt, v)

        _, g = jax.lax.scan(tile_pass, None, jnp.arange(p_loc // tile))
        g = g.reshape(p_loc)
        for ax in daxes:
            g = jax.lax.psum(g, ax)
        return g

    return corr


@lru_cache(maxsize=64)
def make_sparse_corr(mesh: Mesh, n_loc: int, tile: int,
                     model_axis: str = "model"):
    """Jitted distributed slab correlation ``corr(row_idx, values, v) ->
    X^T v`` over (p, DP, K) mesh slabs (sharded P(model, data, None), local
    row indices with sentinel ``n_loc``); ``v`` is example-sharded P(data).
    Per-tile memory is (tile, K) — never a dense (n, p) block. This is the
    one gradient-pass primitive: the strong-rule screen is ``|corr(...)|``
    at the NLL residual and lambda_max is ``max |corr(0.5 y)|``
    (``repro.api.lambda_max_design``)."""
    return jax.jit(_sparse_corr_program(mesh, n_loc, tile, model_axis))


@lru_cache(maxsize=64)
def make_sparse_screen(mesh: Mesh, n_loc: int, tile: int,
                       model_axis: str = "model"):
    """Distributed strong-rule gradient pass over by-feature sparse slabs.

    Builds a jitted ``screen(row_idx, values, y, m) -> g_abs``: the
    :func:`make_sparse_corr` slab stream evaluated at the per-example NLL
    residual, absolute value taken. The result feeds
    :func:`strong_rule_mask` and :func:`kkt_violations` unchanged (both are
    elementwise in g_abs), making the whole screen sparse-native.
    """
    corr = _sparse_corr_program(mesh, n_loc, tile, model_axis)

    @jax.jit
    def screen(row_idx, values, y, m):
        return jnp.abs(corr(row_idx, values, _nll_residual(m, y)))

    return screen
