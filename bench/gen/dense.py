"""Dense design generator: a logistic model over Gaussian features.

Copied from the program's ``make_glm_dataset`` model so that later changes
to the program cannot move the yardstick: X ~ N(0, 1) entries, a true
coefficient vector with ``p // 20`` nonzeros of scale ``snr / sqrt(k)``,
labels drawn from the logistic model with 5 % of them flipped. Everything
is made on the device in one jitted call, in float32.

Every seed gets the same problem in another order: the data come from the
fixed key ``BASE_SEED`` and ``--seed`` permutes the examples. A path's
work then does not depend on the seed (the feature order, which sets the
coordinate-descent order, is the same), while the inputs the program sees
differ.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

SNR = 3.0
LABEL_NOISE = 0.05
BASE_SEED = 20141124


def key_of(seed: int):
    """A PRNG key from any whole number, the bits above 32 folded in."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


@partial(jax.jit, static_argnames=("n", "p"))
def _make(base, key, *, n: int, p: int):
    """Base example ``i`` is row ``i`` of N(0, 1) entries from its own key;
    the seed's permutation places it at row ``rows^-1[i]``."""
    k_true = max(4, p // 20)
    k1, k3, k4, k5 = jax.random.split(base, 4)
    rows = jax.random.permutation(key, n)
    X = jax.vmap(lambda r: jax.random.normal(
        jax.random.fold_in(k1, r), (p,), jnp.float32))(rows)
    idx = jax.random.choice(k3, p, (k_true,), replace=False)
    vals = jax.random.normal(k4, (k_true,)) * SNR / jnp.sqrt(k_true)
    beta_true = jnp.zeros(p, jnp.float32).at[idx].set(vals)
    logits = jnp.dot(X, beta_true, precision=jax.lax.Precision.HIGHEST)
    u = jax.random.uniform(k5, (n,))[rows]
    flip = jax.random.bernoulli(jax.random.fold_in(k5, 1), LABEL_NOISE,
                                (n,))[rows]
    y = jnp.where(u < jax.nn.sigmoid(logits), 1.0, -1.0)
    return X, jnp.where(flip, -y, y).astype(jnp.float32)


def make(cfg: dict, seed: int) -> dict:
    """``{"X": (n, p) float32, "y": (n,) +-1 float32}`` on the device."""
    X, y = _make(key_of(BASE_SEED), key_of(seed),
                 n=int(cfg["train_rows"]), p=int(cfg["num_features"]))
    return {"X": X, "y": y}


def live_bytes(cfg: dict) -> int:
    """Bytes one full pass over the design reads: every float32 entry."""
    return int(cfg["train_rows"]) * int(cfg["num_features"]) * 4


def nnz(cfg: dict) -> int:
    return int(cfg["train_rows"]) * int(cfg["num_features"])
