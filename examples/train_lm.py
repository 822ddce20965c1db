"""LM pretraining driver over the architecture zoo (substrate demo).

Default: a ~100M-param llama-family model for a few hundred steps on CPU.
``--smoke`` uses the reduced config (seconds instead of hours); ``--arch``
selects any assigned architecture.

    PYTHONPATH=src python examples/train_lm.py --smoke --steps 50
"""
import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.checkpoint import save_pytree
from repro.configs import MODEL_CONFIGS
from repro.configs.base import AttentionConfig, ModelConfig
from repro.data.lm_data import batches, zipf_corpus
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import warmup_cosine
from repro.train import make_train_state, make_train_step

# ~100M params: 12L, d=768, llama-style
LM100M = ModelConfig(
    name="lm-100m", arch_type="dense",
    citation="example driver config (~100M params)",
    num_layers=12, d_model=768, d_ff=2048, vocab_size=32000,
    attention=AttentionConfig(num_heads=12, num_kv_heads=4, head_dim=64),
    param_dtype="float32", compute_dtype="float32", remat=False,
)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="lm-100m",
                    choices=["lm-100m"] + list(MODEL_CONFIGS))
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config for zoo archs")
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()

    cfg = LM100M if args.arch == "lm-100m" else MODEL_CONFIGS[args.arch]
    if args.smoke and args.arch != "lm-100m":
        cfg = cfg.smoke()
    if args.smoke and args.arch == "lm-100m":
        cfg = dataclasses.replace(cfg, num_layers=2, d_model=256, d_ff=512,
                                  vocab_size=2048, name="lm-100m-smoke")

    state = make_train_state(jax.random.key(0), cfg)
    n_params = sum(x.size for x in jax.tree.leaves(state["params"]))
    print(f"arch={cfg.name}  params={n_params/1e6:.1f}M  "
          f"steps={args.steps}  batch={args.batch}x{args.seq}")

    sched = warmup_cosine(3e-4, min(50, args.steps // 10 + 1), args.steps)
    step_fn = jax.jit(make_train_step(cfg, lr_schedule=sched))

    rng = np.random.default_rng(0)
    corpus = zipf_corpus(rng, cfg.vocab_size, 2_000_000)
    it = batches(corpus, args.batch, args.seq, cfg=cfg, rng=rng)

    t0 = time.time()
    for i in range(args.steps):
        state, metrics = step_fn(state, next(it))
        if i % max(1, args.steps // 20) == 0 or i == args.steps - 1:
            print(f"step {i:5d}  loss={float(metrics['loss']):.4f}  "
                  f"lr={float(metrics['lr']):.2e}  "
                  f"({(time.time()-t0)/(i+1):.2f}s/step)")
    if args.ckpt:
        save_pytree(state, args.ckpt, step=args.steps)
        print(f"checkpoint -> {args.ckpt}")


if __name__ == "__main__":
    main()
