"""Sparse L1 logistic probe on a frozen backbone — the paper's technique
integrated with the model zoo. Classify sequences (synthetic task: does the
sequence contain a marker token) from pooled hidden features of any
assigned architecture.

    PYTHONPATH=src python examples/sparse_probe.py --arch tinyllama-1.1b
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import MODEL_CONFIGS
from repro.core.dglmnet import DGLMNETOptions
from repro.core.probe import extract_features, probe_path
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.train.metrics import glm_eval_fn


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b", choices=list(MODEL_CONFIGS))
    ap.add_argument("--n", type=int, default=1024)
    ap.add_argument("--seq", type=int, default=64)
    args = ap.parse_args()

    cfg = MODEL_CONFIGS[args.arch].smoke()
    params = init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)

    # synthetic probe task: +1 iff the marker token appears in the sequence
    marker = 7
    tokens = rng.integers(8, cfg.vocab_size, (args.n, args.seq))
    has = rng.random(args.n) < 0.5
    pos = rng.integers(0, args.seq, args.n)
    tokens[has, pos[has]] = marker
    y = jnp.where(jnp.asarray(has), 1.0, -1.0)

    extra = None
    if cfg.frontend.kind == "vision_patches":
        extra = {"patch_embeds": jnp.asarray(
            rng.standard_normal((args.n, cfg.frontend.tokens_per_item,
                                 cfg.frontend.embed_dim)), jnp.float32)}
    elif cfg.frontend.kind == "audio_frames" and not cfg.encdec.enabled:
        extra = {"frame_embeds": jnp.asarray(
            rng.standard_normal((args.n, cfg.frontend.tokens_per_item,
                                 cfg.frontend.embed_dim)), jnp.float32)}
    if cfg.encdec.enabled:
        extra = {"frame_embeds": jnp.asarray(
            rng.standard_normal((args.n, 16, cfg.frontend.embed_dim)), jnp.float32)}

    print(f"extracting {args.n} x d={cfg.d_model} features from {cfg.name} ...")
    feats = jax.jit(lambda t: extract_features(params, cfg, t, extra_inputs=extra))(
        jnp.asarray(tokens, jnp.int32))
    feats = (feats - feats.mean(0)) / (feats.std(0) + 1e-6)

    n_train = int(args.n * 0.8)
    eval_fn = glm_eval_fn(feats[n_train:], y[n_train:])
    pts = probe_path(
        feats[:n_train], y[:n_train], path_len=8,
        opts=DGLMNETOptions(num_blocks=4, tile=32, max_iters=40),
        eval_fn=eval_fn)
    print("lambda        nnz   test-AUPRC  test-acc")
    for p in pts:
        print(f"{p.lam:10.4f} {p.nnz:6d}   {p.metrics['auprc']:.4f}     "
              f"{p.metrics['accuracy']:.4f}")
    best = max(pts, key=lambda p: p.metrics["auprc"])
    print(f"\nbest: {best.nnz}-feature sparse probe, AUPRC={best.metrics['auprc']:.4f}")


if __name__ == "__main__":
    main()
