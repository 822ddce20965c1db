"""Device-resident solver engine (core/engine.py): the jitted while_loop
outer loop must reproduce the seed's host-driven trajectory exactly and
perform no per-iteration host synchronization."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DGLMNETOptions, fit, fit_python_loop, lambda_max
from repro.core import engine
from repro.core.dglmnet import _solver_for
from repro.core.objective import margins


@pytest.mark.parametrize("opts", [
    DGLMNETOptions(num_blocks=1, method="gram", tile=32, max_iters=60),
    DGLMNETOptions(num_blocks=4, method="gram", tile=32, max_iters=60),
    DGLMNETOptions(num_blocks=4, method="residual", max_iters=60),
])
def test_fit_matches_python_loop_trajectory(small_glm, opts):
    """Engine vs seed Python loop: same objective trajectory within 1e-5,
    same iteration count, same alphas (they run the same jitted math, just
    with the loop on device)."""
    X, y = small_glm.X_train, small_glm.y_train
    lam = float(lambda_max(X, y)) / 32

    ref = fit_python_loop(X, y, lam, opts=opts)
    eng = fit(X, y, lam, opts=opts)

    assert eng.n_iters == ref.n_iters
    assert eng.converged == ref.converged
    h_ref = np.asarray(ref.objective_history)
    h_eng = np.asarray(eng.objective_history)
    assert h_ref.shape == h_eng.shape
    np.testing.assert_allclose(h_eng, h_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(eng.alpha_history), np.asarray(ref.alpha_history),
        rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(eng.beta), np.asarray(ref.beta), rtol=1e-4, atol=1e-5)
    assert eng.nnz == ref.nnz


def test_fit_warmstart_matches_python_loop(small_glm):
    X, y = small_glm.X_train, small_glm.y_train
    lam = float(lambda_max(X, y)) / 16
    opts = DGLMNETOptions(num_blocks=4, tile=32, max_iters=60)
    warm = fit(X, y, lam * 2, opts=opts).beta
    ref = fit_python_loop(X, y, lam, beta0=warm, opts=opts)
    eng = fit(X, y, lam, beta0=warm, opts=opts)
    np.testing.assert_allclose(
        np.asarray(eng.objective_history), np.asarray(ref.objective_history),
        rtol=1e-5)


def test_fit_single_host_transfer(small_glm, monkeypatch):
    """The whole solve performs exactly one device->host transfer (the
    final ``device_get`` of the solver state) — the seed synced the
    objective every outer iteration."""
    X, y = small_glm.X_train, small_glm.y_train
    lam = float(lambda_max(X, y)) / 32
    opts = DGLMNETOptions(num_blocks=4, tile=32, max_iters=60)
    fit(X, y, lam, opts=opts)  # warm the compile cache

    calls = []
    real = engine.device_get
    monkeypatch.setattr(engine, "device_get", lambda x: calls.append(1) or real(x))
    res = fit(X, y, lam, opts=opts)
    assert len(calls) == 1, f"expected 1 device_get per solve, saw {len(calls)}"
    assert res.n_iters > 1  # multiple outer iterations, still one transfer


def test_solver_outer_loop_is_single_while(small_glm):
    """The solver jaxpr is one program whose outer loop is a lax.while_loop
    — no per-iteration dispatch, no callbacks to host."""
    X, y = small_glm.X_train, small_glm.y_train
    lam = float(lambda_max(X, y)) / 32
    opts = DGLMNETOptions(num_blocks=2, tile=32, max_iters=10)
    beta = jnp.zeros(X.shape[1], jnp.float32)
    m = margins(X, beta)
    solve = _solver_for(opts)
    jaxpr = jax.make_jaxpr(solve)(X, y, beta, m, lam).jaxpr
    if [e.primitive.name for e in jaxpr.eqns] == ["jit"]:
        jaxpr = jaxpr.eqns[0].params["jaxpr"].jaxpr  # descend into the jit
    prims = [eqn.primitive.name for eqn in jaxpr.eqns]
    assert prims.count("while") == 1, prims
    assert not any("callback" in p for p in prims), prims


def test_solver_reuses_compilation_across_lambdas(small_glm):
    """lam is a traced operand: a whole regularization path hits one
    compiled executable."""
    X, y = small_glm.X_train, small_glm.y_train
    lmax = float(lambda_max(X, y))
    opts = DGLMNETOptions(num_blocks=4, tile=32, max_iters=20)
    solve = _solver_for(opts)
    fit(X, y, lmax / 4, opts=opts)  # compile once
    misses0 = solve._cache_size()
    for div in (8, 16, 32, 64):
        fit(X, y, lmax / div, opts=opts)
    assert solve._cache_size() == misses0


def test_engine_respects_max_iters(small_glm):
    X, y = small_glm.X_train, small_glm.y_train
    lam = float(lambda_max(X, y)) / 64
    res = fit(X, y, lam, opts=DGLMNETOptions(max_iters=3))
    assert res.n_iters <= 3
    assert len(res.objective_history) == res.n_iters + 1
    assert len(res.alpha_history) == res.n_iters


def test_snapback_epilogue_records_applied_step(small_glm):
    """The snap-back epilogue applies alpha=1; the reported telemetry must
    describe that applied step — alpha_history ends in 1.0, the snapped
    unit step is counted, and f_hist[-1] is the objective at the returned
    beta (engine and python-loop oracle agree on all of it)."""
    from repro.core.objective import objective

    X, y = small_glm.X_train, small_glm.y_train
    lam = float(lambda_max(X, y)) / 64
    # a huge snap_tol forces the snap on the final step regardless of the
    # line search's alpha, so the pre-fix misreport is always exercised
    opts = DGLMNETOptions(num_blocks=4, tile=32, max_iters=4, snap_tol=10.0)
    eng = fit(X, y, lam, opts=opts)
    ref = fit_python_loop(X, y, lam, opts=opts)

    assert eng.alpha_history[-1] == 1.0
    assert ref.alpha_history[-1] == 1.0
    np.testing.assert_allclose(eng.alpha_history, ref.alpha_history,
                               rtol=1e-5, atol=1e-6)
    assert eng.unit_step_frac == ref.unit_step_frac
    # the applied final step is a unit step, so at least one was counted
    assert round(eng.unit_step_frac * eng.n_iters) >= 1
    f_at_beta = float(objective(margins(X, eng.beta), y, eng.beta, lam))
    np.testing.assert_allclose(eng.objective_history[-1], f_at_beta,
                               rtol=1e-5)


def test_make_step_matches_manual_iteration(small_glm):
    """engine.make_step == subproblem + line search + apply, one iteration."""
    from repro.core.dglmnet import _iteration
    from repro.core import line_search
    from repro.core.dglmnet import dglmnet_iteration

    X, y = small_glm.X_train, small_glm.y_train
    lam = float(lambda_max(X, y)) / 16
    opts = DGLMNETOptions(num_blocks=4, tile=32)
    beta = jnp.zeros(X.shape[1], jnp.float32)
    m = margins(X, beta)

    step = engine.make_step(
        lambda X, y, b, mm, l, w, z: _iteration(X, y, b, mm, l, opts, w, z))
    b1, m1, f1, a1 = step(X, y, beta, m, lam)

    dbeta, dm, gd = dglmnet_iteration(X, y, beta, m, lam, opts)
    res = line_search(m, dm, y, beta, dbeta, lam, gd)
    np.testing.assert_allclose(np.asarray(b1), np.asarray(beta + res.alpha * dbeta),
                               atol=1e-6)
    np.testing.assert_allclose(float(f1), float(res.f_new), rtol=1e-6)
