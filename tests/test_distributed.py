"""Distributed (shard_map) d-GLMNET: equivalence with the single-process
simulation, run in subprocesses with 8 fake CPU devices (tests themselves
must see 1 device, per the dry-run isolation rule)."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    return subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )


def test_distributed_equals_local():
    r = _run("""
        import jax, jax.numpy as jnp
        from repro.configs.base import GLMConfig
        from repro.core import DGLMNETOptions, fit, fit_distributed, lambda_max
        from repro.data.synthetic import make_glm_dataset
        from repro.launch.mesh import make_dev_mesh

        cfg = GLMConfig(name='d', num_examples=2560, num_features=256, density=1.0)
        ds = make_glm_dataset(cfg, jax.random.key(0))
        X, y = ds.X_train, ds.y_train
        lam = float(lambda_max(X, y)) / 32
        opts = DGLMNETOptions(num_blocks=4, method='gram', tile=32, max_iters=40)
        res_local = fit(X, y, lam, opts=opts)
        mesh = make_dev_mesh(2, 4)
        res_dist = fit_distributed(X, y, lam, mesh, opts=opts)
        rel = abs(res_local.f - res_dist.f) / abs(res_local.f)
        assert rel < 1e-4, (res_local.f, res_dist.f)
        print('OK', res_local.f, res_dist.f)
    """)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_divisibility_and_slab_guards():
    """The guard messages state the actual requirement (the data extent
    must divide n, not the reverse), and the sparse step validates its slab
    shapes against the mesh before any device work."""
    r = _run("""
        import jax, jax.numpy as jnp
        from repro.core import DGLMNETOptions, fit_distributed, fit_distributed_sparse
        from repro.launch.mesh import make_dev_mesh

        mesh = make_dev_mesh(2, 4)
        X = jnp.ones((17, 16)); y = jnp.ones(17)   # 17 % 2 != 0
        try:
            fit_distributed(X, y, 1.0, mesh)
            raise AssertionError('dense guard did not fire')
        except ValueError as e:
            assert 'data extent 2 must divide n=17' in str(e), str(e)

        rows = jnp.zeros((16, 3, 4), jnp.int32)    # DP=3 != data extent 2
        vals = jnp.zeros((16, 3, 4), jnp.float32)
        try:
            fit_distributed_sparse(rows, vals, jnp.ones(18), 1.0, mesh)
            raise AssertionError('slab DP guard did not fire')
        except ValueError as e:
            assert 'must equal the mesh data extent 2' in str(e), str(e)

        rows = jnp.zeros((16, 2, 4), jnp.int32)
        try:
            fit_distributed_sparse(rows, vals, jnp.ones(18), 1.0, mesh)
            raise AssertionError('slab shape guard did not fire')
        except ValueError as e:
            assert 'must match and be (p, DP, K)' in str(e), str(e)

        vals = jnp.zeros((16, 2, 4), jnp.float32)
        try:
            fit_distributed_sparse(rows, vals, jnp.ones(17), 1.0, mesh)
            raise AssertionError('sparse n guard did not fire')
        except ValueError as e:
            assert 'data extent 2 must divide n=17' in str(e), str(e)

        # slabs built for a larger n than y implies: local rows out of range
        rows = jnp.full((16, 2, 4), 30, jnp.int32)   # n_loc from y is 9
        try:
            fit_distributed_sparse(rows, vals, jnp.ones(18), 1.0, mesh)
            raise AssertionError('slab row-range guard did not fire')
        except ValueError as e:
            assert 'exceeds the local example count 9' in str(e), str(e)
        print('OK guards')
    """)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_distributed_telemetry_parity():
    """DistributedFitResult surfaces the engine epilogue telemetry
    (alpha_history, unit_step_frac, converged) exactly like FitResult —
    same jitted program, same numbers."""
    r = _run("""
        import numpy as np
        import jax, jax.numpy as jnp
        from repro.configs.base import GLMConfig
        from repro.core import DGLMNETOptions, fit, fit_distributed, lambda_max
        from repro.data.synthetic import make_glm_dataset
        from repro.launch.mesh import make_dev_mesh

        cfg = GLMConfig(name='t', num_examples=1024, num_features=128, density=1.0)
        ds = make_glm_dataset(cfg, jax.random.key(3))
        X, y = ds.X_train, ds.y_train
        lam = float(lambda_max(X, y)) / 32
        opts = DGLMNETOptions(num_blocks=4, tile=32, max_iters=40)
        loc = fit(X, y, lam, opts=opts)
        dist = fit_distributed(X, y, lam, make_dev_mesh(2, 4), opts=opts)
        assert dist.n_iters == loc.n_iters, (dist.n_iters, loc.n_iters)
        assert dist.converged == loc.converged
        assert dist.unit_step_frac == loc.unit_step_frac, (
            dist.unit_step_frac, loc.unit_step_frac)
        np.testing.assert_allclose(np.asarray(dist.alpha_history),
                                   np.asarray(loc.alpha_history),
                                   rtol=1e-5, atol=1e-6)
        assert dist.m is not None and dist.m.shape == y.shape
        np.testing.assert_allclose(np.asarray(dist.m), np.asarray(X @ dist.beta),
                                   rtol=1e-4, atol=1e-4)
        print('OK telemetry')
    """)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_distributed_sparse_regpath_matches_single_process():
    """The tentpole acceptance: the distributed screened path over
    by-feature sparse slabs on a 2x4 fake-device mesh matches the
    single-process screened path per lambda, every point KKT-certified —
    and the driver never sees a dense (n, p) X (only the reference does)."""
    r = _run("""
        import numpy as np
        import jax, jax.numpy as jnp
        from repro.configs.base import GLMConfig
        from repro.core import (DGLMNETOptions, regularization_path,
                                regularization_path_distributed)
        from repro.core.objective import margins
        from repro.core.screening import nll_grad_abs
        from repro.data.byfeature import to_by_feature, to_slabs
        from repro.data.synthetic import make_glm_dataset
        from repro.launch.mesh import make_dev_mesh

        cfg = GLMConfig(name='sp', num_examples=1024, num_features=96, density=0.3)
        ds = make_glm_dataset(cfg, jax.random.key(11))
        X, y = ds.X_train, ds.y_train
        n = (X.shape[0] // 2) * 2
        X, y = X[:n], y[:n]
        opts = DGLMNETOptions(num_blocks=4, tile=16, max_iters=60, rel_tol=1e-7)
        mesh = make_dev_mesh(2, 4)

        bf = to_by_feature(X)
        slabs = to_slabs(bf, 2)[:2]
        pts_ref = regularization_path(X, y, path_len=6, opts=opts, screen=True)
        pts_dist = regularization_path_distributed(slabs, y, mesh, path_len=6,
                                                   opts=opts)
        assert len(pts_dist) == 6
        for pr, pd in zip(pts_ref, pts_dist):
            rel = abs(pd.f - pr.f) / max(abs(pr.f), 1e-9)
            assert rel < 1e-4, (pd.lam, pd.f, pr.f)
            assert abs(pd.nnz - pr.nnz) <= 2, (pd.lam, pd.nnz, pr.nnz)
            br, bd = np.abs(np.asarray(pr.beta)), np.abs(np.asarray(pd.beta))
            disagree = (br > 0) != (bd > 0)
            assert np.all(np.maximum(br, bd)[disagree] < 1e-2), pd.lam
            np.testing.assert_allclose(np.asarray(pd.beta), np.asarray(pr.beta),
                                       rtol=1e-2, atol=1e-3)
            # KKT certificate at the returned distributed solution
            g = nll_grad_abs(X, y, margins(X, pd.beta))
            inactive = np.asarray(pd.beta) == 0
            assert bool(jnp.all(g[inactive] <= pd.lam * (1 + 2e-3) + 1e-5)), pd.lam
        assert any(p.screen['active'] < X.shape[1] for p in pts_dist)
        print('OK sparse distributed path')
    """)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_sparse_fit_densify_fallback_equivalence():
    """fit_distributed_sparse must produce the same solve whether the
    nnz-density heuristic picks the sparse-native slab kernels or the
    once-per-solve on-mesh densify fallback — and both must match the
    dense fit. Low density so the slab-native path is the natural one."""
    r = _run("""
        import numpy as np
        import jax, jax.numpy as jnp
        from repro.configs.base import GLMConfig
        from repro.core import DGLMNETOptions, fit, lambda_max
        from repro.core.distributed import fit_distributed_sparse
        from repro.data.byfeature import to_by_feature, to_slabs
        from repro.data.synthetic import make_glm_dataset
        from repro.launch.mesh import make_dev_mesh

        # K per feature per data shard ~ Poisson(819 * 0.0025 = 2): its
        # max stays far below the slab-regime bound sqrt(819 / 8) ~ 10
        cfg = GLMConfig(name='fb', num_examples=2048, num_features=64,
                        density=0.0025)
        ds = make_glm_dataset(cfg, jax.random.key(8))
        X, y = ds.X_train, ds.y_train
        n = (X.shape[0] // 2) * 2
        X, y = X[:n], y[:n]
        lam = float(lambda_max(X, y)) / 16
        opts = DGLMNETOptions(tile=16, max_iters=30)
        mesh = make_dev_mesh(2, 4)
        row_idx, values, n_loc = to_slabs(to_by_feature(X), 2)
        from repro.kernels.ops import prefer_slab_gram
        assert prefer_slab_gram(n_loc, row_idx.shape[2]), (
            'density too high for the slab-native regime', row_idx.shape)

        ref = fit(X, y, lam, opts=opts)
        res_auto = fit_distributed_sparse(row_idx, values, y, lam, mesh,
                                          opts=opts)
        res_sparse = fit_distributed_sparse(row_idx, values, y, lam, mesh,
                                            opts=opts, densify=False)
        res_dense = fit_distributed_sparse(row_idx, values, y, lam, mesh,
                                           opts=opts, densify=True)
        for res in (res_auto, res_sparse, res_dense):
            assert abs(res.f - ref.f) / abs(ref.f) < 1e-4, (res.f, ref.f)
            np.testing.assert_allclose(np.asarray(res.beta),
                                       np.asarray(ref.beta),
                                       rtol=1e-2, atol=1e-3)
        # the two mesh paths solve the *same* block partition: bitwise-tight
        np.testing.assert_allclose(np.asarray(res_sparse.beta),
                                   np.asarray(res_dense.beta),
                                   rtol=1e-5, atol=1e-6)
        print('OK densify fallback equivalence')
    """)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_bucketed_regpath_matches_single_process():
    """The distributed screened path over the nnz-bucketed SlabBuckets
    layout == the single-process screened path per lambda, with betas
    mapped back to the original feature order."""
    r = _run("""
        import numpy as np
        import jax, jax.numpy as jnp
        from repro.configs.base import GLMConfig
        from repro.core import (DGLMNETOptions, regularization_path,
                                regularization_path_distributed)
        from repro.data.byfeature import to_by_feature, to_slab_buckets
        from repro.data.synthetic import make_glm_dataset
        from repro.launch.mesh import make_dev_mesh

        cfg = GLMConfig(name='bk', num_examples=1024, num_features=96,
                        density=0.08)
        ds = make_glm_dataset(cfg, jax.random.key(11))
        X, y = ds.X_train, ds.y_train
        n = (X.shape[0] // 2) * 2
        X, y = X[:n], y[:n]
        opts = DGLMNETOptions(num_blocks=4, tile=16, max_iters=60,
                              rel_tol=1e-7)
        mesh = make_dev_mesh(2, 4)
        slabs = to_slab_buckets(to_by_feature(X), 2)
        assert len(slabs.buckets) >= 2, 'want multiple K classes'
        pts_ref = regularization_path(X, y, path_len=5, opts=opts,
                                      screen=True)
        pts = regularization_path_distributed(slabs, y, mesh, path_len=5,
                                              opts=opts)
        for pr, pb in zip(pts_ref, pts):
            rel = abs(pb.f - pr.f) / max(abs(pr.f), 1e-9)
            assert rel < 1e-4, (pb.lam, pb.f, pr.f)
            np.testing.assert_allclose(np.asarray(pb.beta),
                                       np.asarray(pr.beta),
                                       rtol=1e-2, atol=1e-3)

        # pre-built slabs with sentinel slots interleaved among live ones
        # (legal input; nothing ever promised front-packing): the K-trim
        # must be disabled, not silently drop live entries
        from repro.data.byfeature import to_slabs
        row_idx, values, n_loc = to_slabs(to_by_feature(X), 2)
        ri, vv = np.array(row_idx), np.array(values)
        rng = np.random.default_rng(0)
        for j in range(ri.shape[0]):
            for s in range(ri.shape[1]):
                perm = rng.permutation(ri.shape[2])
                ri[j, s], vv[j, s] = ri[j, s][perm], vv[j, s][perm]
        pts_shuf = regularization_path_distributed(
            (jnp.asarray(ri), jnp.asarray(vv)), y, mesh, path_len=5,
            opts=opts)
        for pr, pb in zip(pts_ref, pts_shuf):
            assert abs(pb.f - pr.f) / max(abs(pr.f), 1e-9) < 1e-4, (
                pb.lam, pb.f, pr.f)
            np.testing.assert_allclose(np.asarray(pb.beta),
                                       np.asarray(pr.beta),
                                       rtol=1e-2, atol=1e-3)
        print('OK bucketed path')
    """)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


@pytest.mark.slow
def test_distributed_dense_regpath_matches_single_process():
    """Dense-X flavor of the distributed screened path: restricted solves
    are fit_distributed; per-lambda agreement with the single-process
    engine on a model x data mesh."""
    r = _run("""
        import numpy as np
        import jax, jax.numpy as jnp
        from repro.configs.base import GLMConfig
        from repro.core import (DGLMNETOptions, regularization_path,
                                regularization_path_distributed)
        from repro.data.synthetic import make_glm_dataset
        from repro.launch.mesh import make_dev_mesh

        cfg = GLMConfig(name='dd', num_examples=1280, num_features=128, density=1.0)
        ds = make_glm_dataset(cfg, jax.random.key(12))
        X, y = ds.X_train, ds.y_train
        opts = DGLMNETOptions(num_blocks=4, tile=16, max_iters=60, rel_tol=1e-7)
        mesh = make_dev_mesh(2, 4)
        pts_ref = regularization_path(X, y, path_len=6, opts=opts, screen=True)
        pts_dist = regularization_path_distributed(X, y, mesh, path_len=6,
                                                   opts=opts)
        for pr, pd in zip(pts_ref, pts_dist):
            rel = abs(pd.f - pr.f) / max(abs(pr.f), 1e-9)
            assert rel < 1e-4, (pd.lam, pd.f, pr.f)
            np.testing.assert_allclose(np.asarray(pd.beta), np.asarray(pr.beta),
                                       rtol=1e-2, atol=1e-3)
        print('OK dense distributed path')
    """)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


@pytest.mark.slow
def test_distributed_model_axis_only():
    """Paper-faithful 1-D split (features only): data axis of size 1."""
    r = _run("""
        import jax, jax.numpy as jnp
        from repro.configs.base import GLMConfig
        from repro.core import DGLMNETOptions, fit, fit_distributed, lambda_max
        from repro.data.synthetic import make_glm_dataset
        from repro.launch.mesh import make_dev_mesh

        cfg = GLMConfig(name='d', num_examples=1024, num_features=128, density=1.0)
        ds = make_glm_dataset(cfg, jax.random.key(1))
        X, y = ds.X_train, ds.y_train
        lam = float(lambda_max(X, y)) / 16
        opts = DGLMNETOptions(num_blocks=8, method='gram', tile=16, max_iters=30)
        mesh = make_dev_mesh(1, 8)
        res = fit_distributed(X, y, lam, mesh, opts=opts)
        res_l = fit(X, y, lam, opts=opts)
        rel = abs(res.f - res_l.f) / abs(res_l.f)
        assert rel < 1e-4, (res.f, res_l.f)
        print('OK')
    """)
    assert r.returncode == 0, r.stderr[-3000:]


@pytest.mark.slow
def test_distributed_blocked_cycle_equals_local():
    """The blocked semi-parallel cycle through both distributed restricted
    paths (dense shard_map + by-feature sparse slabs, plus the Pallas
    blocked_cd kernel inside shard_map) matches the single-process blocked
    fit — the same tile math runs either way."""
    r = _run("""
        import jax, jax.numpy as jnp
        from repro.configs.base import GLMConfig
        from repro.core import (DGLMNETOptions, fit, fit_distributed,
                                fit_distributed_sparse, lambda_max)
        from repro.data.byfeature import to_by_feature, to_slabs
        from repro.data.synthetic import make_glm_dataset
        from repro.launch.mesh import make_dev_mesh

        cfg = GLMConfig(name='d', num_examples=1024, num_features=128,
                        density=0.2)
        ds = make_glm_dataset(cfg, jax.random.key(3))
        X, y = ds.X_train, ds.y_train
        n = (X.shape[0] // 2) * 2
        X, y = X[:n], y[:n]
        lam = float(lambda_max(X, y)) / 16
        mesh = make_dev_mesh(2, 4)
        opts = DGLMNETOptions(num_blocks=4, tile=32, max_iters=25,
                              cycle_mode='blocked', block=8)
        ref = fit(X, y, lam, opts=opts)
        dist = fit_distributed(X, y, lam, mesh, opts=opts)
        assert abs(dist.f - ref.f) / abs(ref.f) < 1e-5, (dist.f, ref.f)
        row_idx, values, _ = to_slabs(to_by_feature(X), 2)
        sp = fit_distributed_sparse(row_idx, values, y, lam, mesh,
                                    opts=opts, densify=False)
        assert abs(sp.f - ref.f) / abs(ref.f) < 1e-4, (sp.f, ref.f)
        kopts = DGLMNETOptions(num_blocks=4, tile=32, max_iters=10,
                               cycle_mode='blocked', block=8,
                               use_kernel=True)
        k = fit_distributed(X, y, lam, mesh, opts=kopts)
        kref = fit(X, y, lam, opts=DGLMNETOptions(
            num_blocks=4, tile=32, max_iters=10, cycle_mode='blocked',
            block=8))
        assert abs(k.f - kref.f) / abs(kref.f) < 1e-4, (k.f, kref.f)
        print('OK blocked distributed == local')
    """)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


@pytest.mark.slow
def test_distributed_with_kernel():
    """Pallas gram_cd kernel inside shard_map (interpret mode)."""
    r = _run("""
        import jax, jax.numpy as jnp
        from repro.configs.base import GLMConfig
        from repro.core import DGLMNETOptions, fit_distributed, fit, lambda_max
        from repro.data.synthetic import make_glm_dataset
        from repro.launch.mesh import make_dev_mesh

        cfg = GLMConfig(name='d', num_examples=1024, num_features=64, density=1.0)
        ds = make_glm_dataset(cfg, jax.random.key(2))
        X, y = ds.X_train, ds.y_train
        lam = float(lambda_max(X, y)) / 16
        opts = DGLMNETOptions(num_blocks=4, tile=16, max_iters=15, use_kernel=True)
        mesh = make_dev_mesh(2, 4)
        res = fit_distributed(X, y, lam, mesh, opts=opts)
        ref = fit(X, y, lam, opts=DGLMNETOptions(num_blocks=4, tile=16, max_iters=15))
        assert abs(res.f - ref.f) / abs(ref.f) < 1e-3
        print('OK')
    """)
    assert r.returncode == 0, r.stderr[-3000:]


@pytest.mark.slow
def test_flash_decode_equals_gather_decode():
    """Seq-parallel flash-decode must match the gather path numerically."""
    r = _run("""
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.configs.base import AttentionConfig
        from repro.models.attention import attention_forward, init_attention, init_kv_cache
        from repro.launch.mesh import make_dev_mesh
        from repro.sharding.ctx import mesh_context

        cfg = AttentionConfig(num_heads=8, num_kv_heads=2, head_dim=16)
        d_model = 128
        key = jax.random.key(0)
        p = init_attention(key, cfg, d_model, jnp.float32)
        b, cache_len = 2, 32
        x = jax.random.normal(jax.random.fold_in(key, 1), (b, 1, d_model))
        cache = init_kv_cache(cfg, d_model, b, cache_len, jnp.float32)
        kf = jax.random.normal(jax.random.fold_in(key, 2), (b, 12, 2, 16))
        vf = jax.random.normal(jax.random.fold_in(key, 3), (b, 12, 2, 16))
        cache = {'k': cache['k'].at[:, :12].set(kf), 'v': cache['v'].at[:, :12].set(vf)}
        pos = jnp.full((b, 1), 12, jnp.int32)

        def decode(seq_par):
            def f(p, x, cache):
                y, _ = attention_forward(
                    p, x, cfg=cfg, d_model=d_model, positions=pos, mode='decode',
                    cache=cache, cache_index=jnp.asarray(12, jnp.int32),
                    seq_parallel_decode=seq_par)
                return y
            return f

        y_ref = jax.jit(decode(False))(p, x, cache)
        mesh = make_dev_mesh(2, 4)
        with mesh_context(mesh):
            y_fd = jax.jit(decode(True))(p, x, cache)
        np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_fd),
                                   atol=2e-5)
        print('OK flash-decode == gather decode')
    """)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


@pytest.mark.slow
def test_dev_mesh_dryrun_lowering():
    """dryrun.py end-to-end on the dev mesh (8 devices) for one arch/shape
    per kind — proves the launcher machinery without the 512-dev cost."""
    env = dict(os.environ)
    env["REPRO_DRYRUN_DEVICES"] = "8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    for arch, shape in [("tinyllama-1.1b", "train_4k"),
                        ("mamba2-2.7b", "decode_32k")]:
        r = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
             "--shape", shape, "--mesh", "dev"],
            capture_output=True, text=True, timeout=900, env=env,
        )
        assert r.returncode == 0, (arch, shape, r.stdout[-2000:], r.stderr[-2000:])
        assert "1 ok, 0 skip, 0 error" in r.stdout


@pytest.mark.slow
def test_dryrun_screened_path_lowering():
    """--glm-screened: the sparse screen + blocked-cycle steps lower on a
    mesh (dev size here; the 16x16 production form is the same code with
    REPRO_DRYRUN_DEVICES=512)."""
    env = dict(os.environ)
    env["REPRO_DRYRUN_DEVICES"] = "8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--glm-screened",
         "--mesh", "dev"],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    assert "3 ok, 0 skip, 0 error" in r.stdout


@pytest.mark.slow
def test_sparse_subproblem_equals_dense():
    """By-feature sparse distributed step == dense distributed step."""
    r = _run("""
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.configs.base import GLMConfig
        from repro.core import DGLMNETOptions, lambda_max, margins, objective
        from repro.core.distributed import (
            make_dglmnet_step, make_dglmnet_step_sparse)
        from repro.data.synthetic import make_glm_dataset
        from repro.launch.mesh import make_dev_mesh

        cfg = GLMConfig(name='s', num_examples=1024, num_features=64, density=0.2)
        ds = make_glm_dataset(cfg, jax.random.key(5))
        X, y = ds.X_train, ds.y_train
        n = (X.shape[0] // 2) * 2
        X, y = X[:n], y[:n]
        lam = float(lambda_max(X, y)) / 16
        mesh = make_dev_mesh(2, 4)
        opts = DGLMNETOptions(tile=16)

        # build the (p, DP, K) by-feature slabs with LOCAL row indices
        Xn = np.asarray(X)
        dp, p = 2, X.shape[1]
        n_loc = n // dp
        K = max(int((Xn[s*n_loc:(s+1)*n_loc, j] != 0).sum())
                for s in range(dp) for j in range(p))
        row_idx = np.full((p, dp, K), n_loc, np.int32)
        values = np.zeros((p, dp, K), np.float32)
        for s in range(dp):
            for j in range(p):
                rows = np.nonzero(Xn[s*n_loc:(s+1)*n_loc, j])[0]
                row_idx[j, s, :len(rows)] = rows
                values[j, s, :len(rows)] = Xn[s*n_loc + rows, j]

        beta = jnp.zeros(p); m = margins(X, beta)
        dense = make_dglmnet_step(mesh, opts)
        sparse = make_dglmnet_step_sparse(mesh, opts)
        b1, m1, f1, a1 = dense(X, y, beta, m, lam)
        b2, m2, f2, a2 = sparse(jnp.asarray(row_idx), jnp.asarray(values),
                                y, beta, m, lam)
        np.testing.assert_allclose(np.asarray(b1), np.asarray(b2), atol=1e-4)
        np.testing.assert_allclose(float(f1), float(f2), rtol=1e-5)
        print('OK sparse == dense')
    """)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
