"""Serving demo: prefill a batch of prompts, then greedy-decode with the KV
cache (or SSM state) — exercises the same serve_step the decode dry-run
shapes lower.

    PYTHONPATH=src python examples/serve_lm.py --arch mamba2-2.7b --tokens 32
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import MODEL_CONFIGS
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_cache, init_params
from repro.train import make_prefill_step, make_serve_step


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b", choices=list(MODEL_CONFIGS))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=32)
    args = ap.parse_args()

    cfg = MODEL_CONFIGS[args.arch].smoke()
    params = init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)

    cache_len = args.prompt_len + args.tokens
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32)

    batch = {"tokens": prompts}
    if cfg.encdec.enabled:
        batch["frame_embeds"] = jnp.asarray(
            rng.standard_normal((args.batch, 16, cfg.frontend.embed_dim)),
            jnp.float32)

    prefill = jax.jit(make_prefill_step(cfg))
    serve = jax.jit(make_serve_step(cfg))

    logits, cache = prefill(params, batch)
    # splice the prefill cache into a full-length cache
    full_cache = init_cache(cfg, args.batch, cache_len)
    full_cache = _splice(full_cache, cache, args.prompt_len)

    tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)[:, None]
    out = [tok]
    t0 = time.time()
    for i in range(args.tokens - 1):
        idx = jnp.asarray(args.prompt_len + i, jnp.int32)
        _, next_tok, full_cache = serve(params, full_cache, idx, tok)
        tok = next_tok[:, None]
        out.append(tok)
    dt = (time.time() - t0) / max(args.tokens - 1, 1)
    gen = jnp.concatenate(out, axis=1)
    print(f"arch={cfg.name}  generated {gen.shape} tokens  {dt*1e3:.1f} ms/token")
    print("sample:", np.asarray(gen[0][:16]))


def _splice(full, prefill_cache, prompt_len):
    """Copy prefill results into the front of the full-length cache."""
    import jax

    def per_leaf(f, p):
        if f.shape == p.shape:
            return p
        # seq axis differs; write p at offset 0 along that axis
        axis = next(i for i, (a, b) in enumerate(zip(f.shape, p.shape)) if a != b)
        idx = [slice(None)] * f.ndim
        idx[axis] = slice(0, p.shape[axis])
        return f.at[tuple(idx)].set(p.astype(f.dtype))

    return jax.tree.map(per_leaf, full, prefill_cache)


if __name__ == "__main__":
    main()
