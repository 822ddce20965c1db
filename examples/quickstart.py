"""Quickstart: L1-regularized logistic regression through the one front
door (``repro.api.LogisticL1`` over a ``Design``), with the path solve
traced through ``repro.obs`` (per-lambda phase report at the end).

    PYTHONPATH=src python examples/quickstart.py
"""
import jax

from repro.api import DenseDesign, LogisticL1, SlabDesign, lambda_max_design
from repro.configs.base import GLMConfig
from repro.core import DGLMNETOptions
from repro.data.synthetic import make_glm_dataset
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import observe, render_summary
from repro.train.metrics import glm_eval_fn


def main():
    enable_compile_cache()
    cfg = GLMConfig(name="quickstart", num_examples=8192, num_features=256,
                    density=1.0)
    ds = make_glm_dataset(cfg, jax.random.key(0))
    design = DenseDesign(ds.X_train)
    y = ds.y_train
    lmax = float(lambda_max_design(design, y))
    n, p = design.shape
    print(f"n={n}  p={p}  lambda_max={lmax:.2f}")

    # single solve, simulating 8 machines (feature blocks)
    est = LogisticL1(opts=DGLMNETOptions(num_blocks=8, method="gram", tile=32))
    res = est.fit(design, y, lmax / 64, verbose=True)
    print(f"\nfit: status={res.status_name}  f={res.f:.4f}  nnz={res.nnz}/{p}"
          f"  iters={res.n_iters}  unit-step={res.unit_step_frac:.0%}")

    # the same solve from the by-feature slab layout — one front door,
    # any Design; the strategy resolver picks the execution
    res_slab = est.fit(SlabDesign.from_dense(ds.X_train), y, lmax / 64)
    print(f"slab layout: f={res_slab.f:.4f} (same solve, different Design)")

    # regularization path (paper Algorithm 5) with test metrics, traced:
    # observe() activates repro.obs for the block, so the driver's spans
    # (screen rounds, restricted solves, KKT checks) land in a summary
    print("\nregularization path:")
    est = LogisticL1(opts=DGLMNETOptions(num_blocks=8, tile=32))
    with observe() as obs:
        pts = est.path(design, y, path_len=8,
                       eval_fn=glm_eval_fn(ds.X_test, ds.y_test),
                       verbose=True)
    best = max(pts, key=lambda pt: pt.metrics["auprc"])
    print(f"\nbest: lambda={best.lam:.3f} nnz={best.nnz} "
          f"AUPRC={best.metrics['auprc']:.4f}")

    # score through the estimator (margins via the Design)
    proba = est.predict_proba(DenseDesign(ds.X_test), beta=best.beta)
    print(f"test P(y=+1) range: [{float(proba.min()):.3f}, "
          f"{float(proba.max()):.3f}]")

    # where did the path spend its time? (same report as
    # `python -m repro.obs.report <file>` on an exported summary)
    print("\nobservability — per-phase path report:")
    print(render_summary(obs.summary()))


if __name__ == "__main__":
    main()
