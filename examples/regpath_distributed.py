"""End-to-end driver (the paper's kind): distributed d-GLMNET vs distributed
online learning via truncated gradient, full regularization path, on a mesh
of 8 simulated devices (2 data x 4 model). The same code lowers on the
production 16x16 mesh (see repro/launch/dryrun.py).

Everything runs through the one front door: ``repro.api.LogisticL1`` over
``ShardedDesign``-wrapped layouts. Each distributed solve is one jitted
while_loop on the mesh (core/engine.py) — no per-iteration host sync. The
closing sections run the *distributed screened path* (strong rule + KKT
post-check around mesh restricted solves): the active-set gather reshards
the feature axis into a capacity-bucketed P(model) layout, and in the
sparse flavor the screen streams by-feature (row_idx, values) slabs so no
dense (n, p) X ever exists — the paper's webspam regime — while per-lambda
AUPRC streams from the mesh through a sharded *test* design
(``make_design_eval``) instead of a replicated test matrix.

    python examples/regpath_distributed.py      # sets XLA flags itself
"""
import os

if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    )

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.api import (  # noqa: E402
    DenseDesign,
    LogisticL1,
    ShardedDesign,
    SlabDesign,
    lambda_max_design,
    make_design_eval,
)
from repro.configs.base import GLMConfig  # noqa: E402
from repro.core import DGLMNETOptions, TGOptions  # noqa: E402
from repro.core.truncated_gradient import truncated_gradient_fit  # noqa: E402
from repro.data.synthetic import make_glm_dataset  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_dev_mesh  # noqa: E402
from repro.train.metrics import auprc  # noqa: E402


def main():
    enable_compile_cache()
    cfg = GLMConfig(name="dist", num_examples=16384, num_features=1024,
                    density=0.2)
    ds = make_glm_dataset(cfg, jax.random.key(0))
    X, y = ds.X_train, ds.y_train
    n_trim = (X.shape[0] // 2) * 2
    X, y = X[:n_trim], y[:n_trim]
    mesh = make_dev_mesh(2, 4)
    design = ShardedDesign(DenseDesign(X), mesh, tile=64)
    lmax = float(lambda_max_design(design, y))
    print(f"mesh={dict(mesh.shape)}  n={X.shape[0]}  p={X.shape[1]}")

    print("\n-- d-GLMNET path (feature-sharded over `model`, examples over `data`)")
    est = LogisticL1(opts=DGLMNETOptions(tile=64, max_iters=40),
                     warm_start=True)
    best_d = 0.0
    for i in range(1, 9):
        lam = lmax * 2.0 ** (-i)
        res = est.fit(design, y, lam)           # warm-started from beta_
        ap = auprc(ds.X_test @ res.beta[: ds.X_test.shape[1]], ds.y_test)
        best_d = max(best_d, ap)
        nnz = int((jnp.abs(res.beta) > 0).sum())
        print(f"  lambda={lam:9.3f} nnz={nnz:5d} f={res.f:12.2f} "
              f"iters={res.n_iters:3d} AUPRC={ap:.4f}")

    print("\n-- truncated-gradient baseline (example-sharded, averaged)")
    best_tg = 0.0
    for lr in (0.1, 0.5):
        snaps = truncated_gradient_fit(
            X, y, lmax / 64,
            opts=TGOptions(num_machines=8, passes=6, learning_rate=lr),
            key=jax.random.key(1))
        for pass_idx, b in snaps:
            ap = auprc(ds.X_test @ b, ds.y_test)
            best_tg = max(best_tg, ap)
        print(f"  lr={lr}: best-so-far AUPRC={best_tg:.4f}")

    print(f"\nd-GLMNET best {best_d:.4f} vs TG best {best_tg:.4f} "
          f"-> {'d-GLMNET wins' if best_d >= best_tg else 'TG wins'} "
          f"(paper Figure 1 conclusion)")

    print("\n-- distributed screened path (strong rule + KKT around mesh "
          "restricted solves)")
    import time

    opts = DGLMNETOptions(tile=64, max_iters=40)
    est = LogisticL1(opts=opts)
    t0 = time.perf_counter()
    pts = est.path(design, y, path_len=8)
    dt = time.perf_counter() - t0
    for pt in pts:
        print(f"  lambda={pt.lam:9.3f} nnz={pt.nnz:5d} "
              f"active={pt.screen['active']:5d}/{X.shape[1]} "
              f"kkt_rounds={pt.screen['kkt_rounds']}")
    print(f"  path wall-clock {dt:.2f}s (restricted solves stay on the "
          f"mesh, one compiled while_loop per capacity bucket)")

    print("\n-- same path over by-feature sparse slabs (no dense X anywhere),"
          "\n   per-lambda AUPRC streamed from the mesh via a sharded test "
          "design")
    dp = 2  # data extent of the dev mesh
    slab_design = ShardedDesign(SlabDesign.from_dense(X, dp), mesh, tile=64)
    n_test = (ds.X_test.shape[0] // dp) * dp
    eval_fn = make_design_eval(
        SlabDesign.from_dense(ds.X_test[:n_test], dp), ds.y_test[:n_test],
        mesh=mesh, tile=64)
    t0 = time.perf_counter()
    pts_sp = est.path(slab_design, y, path_len=8, eval_fn=eval_fn)
    dt = time.perf_counter() - t0
    for pt, pt_sp in zip(pts, pts_sp):
        drift = abs(pt_sp.f - pt.f) / max(abs(pt.f), 1e-9)
        print(f"  lambda={pt_sp.lam:9.3f} nnz={pt_sp.nnz:5d} "
              f"active={pt_sp.screen['active']:5d} "
              f"AUPRC={pt_sp.metrics['auprc']:.4f} "
              f"|f-f_dense|/|f|={drift:.2e}")
    print(f"  sparse path wall-clock {dt:.2f}s "
          f"(screen streams (row_idx, values) slabs, psum over data axes)")


if __name__ == "__main__":
    main()
