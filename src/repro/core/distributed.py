"""Distributed d-GLMNET on a JAX mesh (paper Algorithm 4 -> shard_map).

Mapping (DESIGN.md §2.2):
  * feature blocks S_m  <->  `model` mesh axis (paper-faithful dimension)
  * example shards      <->  `data` (+ `pod`) mesh axes (beyond-paper 2-D)

Layout: X P(data, model); y, m P(data); beta P(model).

The quadratic subproblem needs *sequential* CD semantics, so it runs inside
``shard_map``: per feature tile, the Gram block and correlation vector are
``psum``-ed over `data` (exact row-global statistics), the tile's CD cycle
runs replicated on every data shard, and the local residual advances with a
dense matmul. ``dm = X @ dbeta`` is ``psum``-ed over `model` inside the map —
this is the paper's MPI_AllReduce of (dbeta, dbeta^T x_i), with the same
O(n + p) payload per device.

The outer loop is the shared device-resident engine (core/engine.py): the
shard_map subproblem is plugged into the same jitted while_loop program the
single-process ``fit`` uses, so ``fit_distributed`` performs no per-iteration
host synchronization either — sharded state stays on the mesh until the one
``device_get`` at the end of the solve.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import lru_cache, partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import engine
from repro.core.dglmnet import DGLMNETOptions
from repro.core.subproblem import make_tile_solver


def _data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def local_subproblem(X_loc, w_loc, r, beta_loc, lam, *, tile: int, nu: float,
                     data_axes: Tuple[str, ...], use_kernel: bool = False,
                     cycle_mode: str = "sequential", block: int = 16):
    """Per-(data, model)-shard subproblem body. Runs under shard_map.

    X_loc: (n_loc, p_loc); w_loc/r: (n_loc,); beta_loc: (p_loc,).
    Returns (dbeta_loc, r_final). ``cycle_mode``/``block`` pick the
    within-tile CD cycle (sequential chain vs the blocked semi-parallel
    cycle) via the shared ``make_tile_solver`` resolution.
    """
    n_loc, p_loc = X_loc.shape
    assert p_loc % tile == 0, (p_loc, tile)
    nt = p_loc // tile
    if not use_kernel:
        # r becomes varying over the model axis once tile updates land; mark
        # it so the scan carry type is stable (shard_map vma tracking). The
        # Pallas-kernel path runs with check_vma=False (interpret-mode scan
        # internals mix varying axes), where pcast is unavailable.
        r = jax.lax.pcast(r, "model", to="varying")
    tile_solver = make_tile_solver(cycle_mode=cycle_mode, tile=tile,
                                   block=block, use_kernel=use_kernel)

    def tile_step(carry, idx):
        r, dbeta = carry
        Xf = jax.lax.dynamic_slice(X_loc, (0, idx * tile), (n_loc, tile))
        wXf = w_loc[:, None] * Xf
        G = Xf.T @ wXf                                   # (F, F) local rows
        c = wXf.T @ r                                    # (F,)  local rows
        for ax in data_axes:                             # exact row-global stats
            G = jax.lax.psum(G, ax)
            c = jax.lax.psum(c, ax)
        b_f = jax.lax.dynamic_slice(beta_loc, (idx * tile,), (tile,))
        db_f = jax.lax.dynamic_slice(dbeta, (idx * tile,), (tile,))
        d = tile_solver(G, c, b_f, db_f, lam, nu)
        r = r - Xf @ d                                   # local-row residual
        dbeta = jax.lax.dynamic_update_slice(dbeta, db_f + d, (idx * tile,))
        return (r, dbeta), None

    from repro.sharding.ctx import unroll_enabled

    if unroll_enabled():
        # dry-run cost pass: make every tile visible to HloCostAnalysis
        carry = (r, jnp.zeros_like(beta_loc))
        for i in range(nt):
            carry, _ = tile_step(carry, jnp.int32(i))
        r, dbeta = carry
    else:
        (r, dbeta), _ = jax.lax.scan(
            tile_step, (r, jnp.zeros_like(beta_loc)), jnp.arange(nt)
        )
    return dbeta, r


def local_subproblem_sparse(row_idx, values, w_loc, r, beta_loc, lam, *,
                            tile: int, nu: float, data_axes: Tuple[str, ...],
                            cycle_mode: str = "sequential", block: int = 16):
    """Sparse by-feature variant (paper Table 1 layout at webspam scale).

    row_idx/values: (p_loc, K) — per local feature, its local-example rows
    (sentinel n_loc) and values. Each feature tile's weighted Gram block
    and correlation come straight from the slab via the sparse-native
    kernel layer (``kernels.slab_gram``: a match-and-accumulate join over
    nnz slots) and the residual advances with the O(nnz) slab SpMV — no
    ``(n_loc, tile)`` densify scatter anywhere. Sentinel slots contribute
    exactly zero for every slab capacity, including all-padding
    (empty-feature) slabs. Callers in the dense-density regime should
    densify once per solve instead (``fit_distributed_sparse`` does, per
    ``kernels.prefer_slab_gram``) — this body is the K << n_loc path.
    """
    from repro.kernels import ops as kops

    n_loc = r.shape[0]
    p_loc, k = row_idx.shape
    assert p_loc % tile == 0, (p_loc, tile)
    nt = p_loc // tile
    r = jax.lax.pcast(r, "model", to="varying")
    tile_solver = make_tile_solver(cycle_mode=cycle_mode, tile=tile,
                                   block=block)

    def tile_step(carry, idx):
        r, dbeta = carry
        rows = jax.lax.dynamic_slice(row_idx, (idx * tile, 0), (tile, k))
        vals = jax.lax.dynamic_slice(values, (idx * tile, 0), (tile, k))
        G, c = kops.slab_gram(rows, vals, w_loc, r)
        for ax in data_axes:
            G = jax.lax.psum(G, ax)
            c = jax.lax.psum(c, ax)
        b_f = jax.lax.dynamic_slice(beta_loc, (idx * tile,), (tile,))
        db_f = jax.lax.dynamic_slice(dbeta, (idx * tile,), (tile,))
        d = tile_solver(G, c, b_f, db_f, lam, nu)
        r = r - kops.slab_spmv(rows, vals, d, n_loc=n_loc)
        dbeta = jax.lax.dynamic_update_slice(dbeta, db_f + d, (idx * tile,))
        return (r, dbeta), None

    (r, dbeta), _ = jax.lax.scan(
        tile_step, (r, jnp.zeros_like(beta_loc)), jnp.arange(nt)
    )
    return dbeta, r


def _data_extent(mesh: Mesh) -> int:
    ddim = 1
    for ax in _data_axes(mesh):
        ddim *= mesh.shape[ax]
    return ddim


def check_slab_shapes(row_idx, values, mesh: Mesh, n: int) -> int:
    """Validate (p, DP, K) by-feature slabs against the mesh and example
    count. Returns n_loc (= local examples per data shard)."""
    if row_idx.shape != values.shape or row_idx.ndim != 3:
        raise ValueError(
            f"slab shapes must match and be (p, DP, K); got row_idx "
            f"{row_idx.shape} vs values {values.shape}"
        )
    ddim = _data_extent(mesh)
    if row_idx.shape[1] != ddim:
        raise ValueError(
            f"slab data dimension {row_idx.shape[1]} must equal the mesh "
            f"data extent {ddim}"
        )
    if n % ddim:
        raise ValueError(
            f"data extent {ddim} must divide n={n} (trim or pad upstream)"
        )
    n_loc = n // ddim
    # local row indices beyond the sentinel would be silently dropped by
    # the scatter-adds downstream — catch a slab/y example-count mismatch
    # here instead of converging to a wrong solution
    max_row = int(row_idx.max()) if row_idx.size else 0
    if max_row > n_loc:
        raise ValueError(
            f"slab row index {max_row} exceeds the local example count "
            f"{n_loc} implied by n={n} on data extent {ddim} — were the "
            f"slabs built for a different n?"
        )
    return n_loc


def make_distributed_iteration_sparse(mesh: Mesh, opts: DGLMNETOptions, *,
                                      model_axis: str = "model"):
    """The by-feature sparse mesh subproblem in the engine's
    ``iteration_fn`` signature, with ``data = (row_idx, values)``.

    row_idx/values are (p, DP, K): feature-major, one slab per data shard
    (local example indices, sentinel = n_loc); sharded P(model, data, -).
    This is what makes webspam (p = 16.6M, dense X = 10.5 TB) fit the mesh.
    """
    daxes = _data_axes(mesh)
    dspec = P(daxes) if daxes else P()

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(model_axis, daxes, None), P(model_axis, daxes, None),
                  P(model_axis), dspec, dspec, P()),
        out_specs=(P(model_axis), dspec),
    )
    def subproblem_sharded(row_idx, values, beta, w, z, lam):
        dbeta, r = local_subproblem_sparse(
            row_idx[:, 0, :], values[:, 0, :], w, z, beta, lam[0],
            tile=opts.tile, nu=opts.nu, data_axes=daxes,
            cycle_mode=opts.cycle_mode, block=opts.block,
        )
        dm = jax.lax.psum(z - r, model_axis)
        return dbeta, dm

    def iteration(data, y, beta, m, lam, w, z):
        row_idx, values = data
        lam_arr = jnp.asarray(lam, jnp.float32)[None]
        dbeta, dm = subproblem_sharded(row_idx, values, beta, w, z, lam_arr)
        grad_dot = jnp.dot(jax.nn.sigmoid(m) - (y + 1.0) * 0.5, dm)
        return dbeta, dm, grad_dot

    return iteration


def make_dglmnet_step_sparse(mesh: Mesh, opts: DGLMNETOptions, *,
                             model_axis: str = "model"):
    """Jitted distributed d-GLMNET outer iteration over by-feature slabs:
    ``step(row_idx, values, y, beta, m, lam) -> (beta', m', f', alpha)``."""
    step_core = engine.make_step(
        make_distributed_iteration_sparse(mesh, opts, model_axis=model_axis)
    )

    @jax.jit
    def step(row_idx, values, y, beta, m, lam):
        return step_core((row_idx, values), y, beta, m, lam)

    return step


@lru_cache(maxsize=64)
def make_slab_margins(mesh: Mesh, n_loc: int, model_axis: str = "model"):
    """Sharded sparse matvec ``margins(row_idx, values, beta) -> m`` over
    (p, DP, K) slabs: each (model, data) shard runs the slab SpMV kernel
    over its features (``kernels.slab_spmv`` — O(nnz), sentinel slots
    exact zero), then a psum over ``model`` assembles X @ beta exactly —
    no dense X, no densify."""
    from repro.kernels import ops as kops

    daxes = _data_axes(mesh)
    dspec = P(daxes) if daxes else P()

    @jax.jit
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(model_axis, daxes, None), P(model_axis, daxes, None),
                  P(model_axis)),
        out_specs=dspec,
    )
    def slab_margins(row_idx, values, beta):
        rows, vals = row_idx[:, 0, :], values[:, 0, :]
        m_loc = kops.slab_spmv(rows, vals, beta, n_loc=n_loc)
        return jax.lax.psum(m_loc, model_axis)

    return slab_margins


@lru_cache(maxsize=64)
def make_slab_densifier(mesh: Mesh, n_loc: int, model_axis: str = "model"):
    """One-shot on-mesh densify ``(row_idx, values) -> X`` (P(data, model))
    — the dense-Gram fallback setup for slabs above the sparse-win density
    (``kernels.prefer_slab_gram``). The scatter runs once per solve at
    O(nnz) and the solve then rides the dense MXU subproblem, instead of
    paying a per-tile densify on every outer iteration; a dense (n, p_sub)
    block only ever exists sharded on the mesh, never on host."""
    daxes = _data_axes(mesh)

    @jax.jit
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(model_axis, daxes, None), P(model_axis, daxes, None)),
        out_specs=P(daxes, model_axis),
    )
    def densify(row_idx, values):
        rows, vals = row_idx[:, 0, :], values[:, 0, :]
        p_loc = rows.shape[0]
        va = jnp.where(rows < n_loc, vals, 0.0).astype(jnp.float32)
        out = jnp.zeros((p_loc, n_loc + 1), jnp.float32)
        out = out.at[jnp.arange(p_loc)[:, None],
                     jnp.minimum(rows, n_loc)].add(va)
        return out[:, :n_loc].T

    return densify


def make_distributed_iteration(mesh: Mesh, opts: DGLMNETOptions, *,
                               model_axis: str = "model"):
    """The mesh subproblem in the engine's ``iteration_fn`` signature:
    ``iteration(X, y, beta, m, lam) -> (dbeta, dm, grad_dot)``."""
    daxes = _data_axes(mesh)
    dspec = P(daxes) if daxes else P()

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(daxes, model_axis), P(model_axis), dspec, dspec, P()),
        out_specs=(P(model_axis), dspec),
        check_vma=not opts.use_kernel,
    )
    def subproblem_sharded(X, beta, w, z, lam):
        dbeta, r = local_subproblem(
            X, w, z, beta, lam[0], tile=opts.tile, nu=opts.nu,
            data_axes=daxes, use_kernel=opts.use_kernel,
            cycle_mode=opts.cycle_mode, block=opts.block,
        )
        # paper Alg. 4 step 3: AllReduce of per-block margin deltas over blocks
        dm = z - r                                       # X_loc @ dbeta_loc
        dm = jax.lax.psum(dm, model_axis)
        return dbeta, dm

    def iteration(X, y, beta, m, lam, w, z):
        lam_arr = jnp.asarray(lam, jnp.float32)[None]
        dbeta, dm = subproblem_sharded(X, beta, w, z, lam_arr)
        # grad(L)^T dbeta from margins (global sharded arrays; XLA reduces)
        grad_dot = jnp.dot(jax.nn.sigmoid(m) - (y + 1.0) * 0.5, dm)
        return dbeta, dm, grad_dot

    return iteration


def make_dglmnet_step(mesh: Mesh, opts: DGLMNETOptions, *, model_axis: str = "model"):
    """Builds a jitted distributed d-GLMNET outer iteration.

    step(X, y, beta, m, lam) -> (beta', m', f', alpha)
    """
    return engine.make_step(
        make_distributed_iteration(mesh, opts, model_axis=model_axis)
    )


def _build_solver(mesh: Mesh, opts: DGLMNETOptions, model_axis: str,
                  *, sparse: bool, fault=None):
    make_iter = (make_distributed_iteration_sparse if sparse
                 else make_distributed_iteration)
    return engine.make_solver(
        make_iter(mesh, opts, model_axis=model_axis),
        max_iters=opts.max_iters,
        rel_tol=opts.rel_tol,
        snap_tol=opts.snap_tol,
        fault=fault,
    )


@lru_cache(maxsize=64)
def _cached_solver(mesh: Mesh, opts: DGLMNETOptions, model_axis: str,
                   sparse: bool):
    return _build_solver(mesh, opts, model_axis, sparse=sparse)


def _solver_for(mesh: Mesh, opts: DGLMNETOptions, model_axis: str):
    """Cached mesh solver; an armed ``repro.resilience`` engine fault gets
    an uncached poisoned build instead (fault programs never enter — or
    evict from — the healthy cache)."""
    from repro.resilience import arm_engine_fault

    fault = arm_engine_fault()
    if fault is not None:
        return _build_solver(mesh, opts, model_axis, sparse=False,
                             fault=fault)
    return _cached_solver(mesh, opts, model_axis, False)


def _solver_sparse_for(mesh: Mesh, opts: DGLMNETOptions, model_axis: str):
    """Sparse-slab twin of :func:`_solver_for` (same fault-bypass rule)."""
    from repro.resilience import arm_engine_fault

    fault = arm_engine_fault()
    if fault is not None:
        return _build_solver(mesh, opts, model_axis, sparse=True,
                             fault=fault)
    return _cached_solver(mesh, opts, model_axis, True)


@dataclass
class DistributedFitResult:
    """Mirror of ``FitResult`` for mesh solves — same epilogue telemetry
    (the engine state carries it on device either way), plus the final
    sharded margin cache ``m`` (P(data)), which the distributed path driver
    reuses for its KKT pass instead of re-deriving X @ beta."""
    beta: jnp.ndarray
    f: float
    n_iters: int
    objective_history: list
    alpha_history: list = field(default_factory=list)
    unit_step_frac: float = 0.0
    converged: bool = False
    m: Optional[jnp.ndarray] = None
    # engine.STATUS_* code; non-OK means the solve tripped a guardrail and
    # beta/f are the last certified iterate, not the final proposed step
    status: int = 0

    @property
    def nnz(self) -> int:
        return int(jnp.sum(jnp.abs(self.beta) > 0))

    @property
    def status_name(self) -> str:
        return engine.status_name(self.status)

    @property
    def ok(self) -> bool:
        return self.status == engine.STATUS_OK


def fit_distributed(
    X,
    y,
    lam: float,
    mesh: Mesh,
    *,
    beta0: Optional[jnp.ndarray] = None,
    opts: DGLMNETOptions = DGLMNETOptions(),
    verbose: bool = False,
) -> DistributedFitResult:
    """Device-resident outer loop over the sharded subproblem (CPU-testable
    with fake devices; same code lowers on the production mesh). The whole
    solve is one jitted while_loop on the mesh — identical driver code to
    the single-process ``fit`` (core/engine.py).

    Legacy shim: delegates to the ``repro.api`` front door
    (``LogisticL1`` over ``ShardedDesign(DenseDesign(X), mesh)``), which
    owns the solve body; results are bit-identical to the pre-API driver."""
    from repro.api import DenseDesign, LogisticL1, ShardedDesign

    design = ShardedDesign(DenseDesign(X), mesh, tile=opts.tile)
    return LogisticL1(opts=opts).fit(design, y, lam, beta0=beta0,
                                     verbose=verbose)


def _finish(state, p: int, pad: int, verbose: bool,
            tag: str) -> DistributedFitResult:
    """Shared solve epilogue: the one d2h transfer + result assembly."""
    host, hist, alphas = engine.fetch(state)
    it = int(host.it)
    if verbose:
        for k in range(1, it + 1):
            print(f"  [{tag}] iter {k} f={hist[k]:.6f}")
    beta_out = state.beta[:p] if pad else state.beta
    return DistributedFitResult(
        beta=beta_out, f=hist[-1], n_iters=it, objective_history=hist,
        alpha_history=alphas,
        unit_step_frac=int(host.unit_steps) / max(it, 1),
        converged=bool(host.converged),
        m=state.m,
        status=int(host.status),
    )


def fit_distributed_sparse(
    row_idx,
    values,
    y,
    lam: float,
    mesh: Mesh,
    *,
    beta0: Optional[jnp.ndarray] = None,
    opts: DGLMNETOptions = DGLMNETOptions(),
    verbose: bool = False,
    densify: Optional[bool] = None,
) -> DistributedFitResult:
    """``fit_distributed`` over by-feature sparse slabs (p, DP, K) — the
    webspam-scale layout where a dense X can never exist on any machine.
    Same device-resident engine loop. The subproblem implementation is
    picked by the nnz-density heuristic (``kernels.prefer_slab_gram``,
    overridable via ``densify``):

    * sparse-native (K << n_loc): every Gram tile and residual update
      comes straight from the slabs via the ``kernels.slab_gram`` /
      ``slab_spmv`` suite — no densify anywhere, O(nnz)-dominated work;
    * dense fallback (denser slabs): one O(nnz) on-mesh densify *per
      solve* builds the sharded (n, p) block and the solve rides the
      dense MXU subproblem — instead of the old per-tile, per-iteration
      densify scatter that dominated the hot loop.

    Legacy shim: delegates to the ``repro.api`` front door
    (``LogisticL1`` over ``ShardedDesign(SlabDesign(...), mesh)``), which
    owns the solve body; results are bit-identical to the pre-API driver.
    """
    from repro.api import LogisticL1, ShardedDesign, SlabDesign

    design = ShardedDesign(
        SlabDesign(row_idx, values, int(y.shape[0])), mesh, tile=opts.tile)
    return LogisticL1(opts=opts).fit(design, y, lam, beta0=beta0,
                                     verbose=verbose, densify=densify)
