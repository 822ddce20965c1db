"""Jitted public wrappers + backend dispatch for the kernel layer.

Every solver-facing entry point lives here so the hot paths never care
which implementation serves them:

* on TPU the Pallas kernels compile natively;
* elsewhere the same math runs as the XLA-friendly jnp form (the Pallas
  kernels are still validated on CPU with ``interpret=True`` — by the
  tests, not the solvers, because interpret mode is an emulator, not a
  fast path).

The backend probe is cached once per process (it used to re-query
``jax.default_backend()`` on every wrapper call inside traced loops) and
feeds a single ``interpret`` decision shared by all kernel wrappers.

The slab entry points implement the sparse-native by-feature suite (see
``kernels/sparse_slab.py``): Gram/correlation and SpMV straight from
``(tile, K)`` ``(row_idx, values)`` slabs with sentinel slots contributing
exactly zero. ``prefer_slab_gram`` is the nnz-density heuristic deciding
sparse-native vs the dense-Gram fallback.
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp

from repro.kernels.gram_cd import gram_cd_pallas
from repro.kernels.logistic_stats import logistic_stats_pallas
from repro.obs import trace as obs_trace

# every backend compile or persistent-cache load becomes a ``compile`` span
# of the active repro.obs tracer, under the span whose call caused it
jax.monitoring.register_event_duration_secs_listener(obs_trace.on_compile)


@lru_cache(maxsize=1)
def _on_tpu() -> bool:
    """One backend query per process — the result cannot change under a
    running JAX runtime, and the probe must never run inside a trace. A
    failing probe raises: it must never pass for "no TPU" and quietly
    switch every kernel to interpret mode."""
    return jax.default_backend() == "tpu"


@lru_cache(maxsize=1)
def interpret_default() -> bool:
    """The single interpret decision every kernel wrapper threads through:
    compile natively on TPU, interpret (CPU validation, what the tests
    run) elsewhere."""
    return not _on_tpu()


def gram_cd(G, c, beta, dbeta0, lam, nu=1e-6):
    """One CD cycle on a Gram tile; returns the within-cycle delta d."""
    return gram_cd_pallas(G, c, beta, dbeta0, lam, nu,
                          interpret=interpret_default())


def prefer_blocked_cd(f: int, block: int) -> bool:
    """Tile-size heuristic for `cycle_mode="auto"`: the blocked cycle wins
    when it meaningfully shortens the dependent-step chain — at least two
    blocks per tile and a tile wide enough (F >= 32) that the F-step
    scalar chain, not the Gram matmul, dominates the tile (CPU-measured;
    the `--cycle` bench section tracks the crossover). Below that, or at
    block=1 (== the sequential chain), dispatch stays on ``gram_cd``."""
    return block > 1 and f >= 2 * block and f >= 32


def blocked_cd(G, c, beta, dbeta0, lam, nu=1e-6, *, block: int = 16,
               dom_tol=None):
    """Blocked semi-parallel CD cycle on a Gram tile (F/B dependent steps
    instead of F); same contract as :func:`gram_cd`. The per-block
    Gershgorin safeguard (halve B, then fall back to the sequential chain)
    is resolved outside the kernel from G alone."""
    from repro.core.subproblem import DOM_TOL
    from repro.kernels.blocked_cd import blocked_cd_pallas

    return blocked_cd_pallas(
        G, c, beta, dbeta0, lam, nu, block=block,
        dom_tol=DOM_TOL if dom_tol is None else dom_tol,
        interpret=interpret_default())


def logistic_stats(m, y, *, block: int = 4096):
    """Fused (w, z, nll) from margins — one pass over the examples axis.

    This is the dispatch point the outer iteration uses (core/engine.py).
    The Pallas kernel is engaged only for *concrete* arrays on TPU: inside
    a trace (the engine's jitted while_loop, where ``m``/``y`` may be
    GSPMD-sharded global arrays) ``pallas_call`` has no partitioning rule,
    so traced call sites always get the fused jnp form — XLA fuses it into
    one sweep and partitions it like any elementwise chain. Shard-local
    TPU code that wants the kernel calls ``logistic_stats_pallas``
    directly.
    """
    if _on_tpu() and not isinstance(m, jax.core.Tracer):
        return logistic_stats_pallas(m, y, block=block, interpret=False)
    from repro.kernels.ref import logistic_stats_ref

    return logistic_stats_ref(m, y)


# ---------------------------------------------------------------------------
# sparse slab suite
# ---------------------------------------------------------------------------

def prefer_slab_gram(n_loc: int, k: int) -> bool:
    """nnz-density heuristic: sparse-native Gram when the match join
    (O(T^2 K^2) VPU ops) beats the dense path (O(nnz) scatter +
    O(n_loc T^2) MXU FLOPs). The measured crossover sits near
    K ~ sqrt(n_loc/8) with margin to spare — the paper's truly sparse
    regime (webspam K is single digits) clears it at any realistic
    n_loc, while moderate-density slabs fall back to densify-once."""
    return 8 * k * k <= n_loc


def _sentinel_zeroed(rows, vals, w, r, n_loc: int):
    """Gathered operands with sentinel slots contributing exactly zero.

    Gathers clamp the slab's row indices into range and then mask the
    result on the *original* validity predicate, so padding slots (and any
    adversarial values parked on them) can never pick up a real example's
    weight — in particular not the last row's, which is what a plain
    clamped gather would silently do.
    """
    valid = rows < n_loc
    idx = jnp.where(valid, rows, 0)
    va = jnp.where(valid, vals, 0.0).astype(jnp.float32)
    wv = jnp.where(valid, w.astype(jnp.float32)[idx], 0.0) * va
    cva = va * jnp.where(valid, (w * r).astype(jnp.float32)[idx], 0.0)
    return jnp.minimum(rows, n_loc), va, wv, cva


def slab_gram(rows, vals, w, r):
    """Weighted Gram tile and correlation straight from a feature slab.

    rows/vals: (T, K) by-feature slab, local row indices, sentinel
    ``n_loc`` (= ``w.shape[0]``) marking padding. Returns
    ``(G (T, T), c (T,))`` with G = X_F^T diag(w) X_F and c = X_F^T (w r)
    — no ``(n_loc, T)`` densify anywhere.
    """
    n_loc = w.shape[0]
    safe, va, wv, cva = _sentinel_zeroed(rows, vals, w, r, n_loc)
    if _on_tpu():
        from repro.kernels.sparse_slab import slab_gram_pallas

        return slab_gram_pallas(safe, wv, va, cva, interpret=False)
    # jnp form of the same match join: broadcast compares of the slot rows
    # gate the outer product of the weighted values
    t, k = rows.shape
    rf = safe.reshape(-1)
    wvf = wv.reshape(-1)
    if t * k <= 2048:
        # one-shot (TK, TK) match — fastest at the small K the heuristic
        # admits, and bounded to a ~16 MiB buffer
        match = (rf[:, None] == rf[None, :]).astype(jnp.float32)
        G = (wvf[:, None] * match * va.reshape(-1)[None, :]
             ).reshape(t, k, t, k).sum(axis=(1, 3))
    else:
        # chunk over the right-hand slot axis to bound the match buffer
        def step(Gacc, kp):
            mk = (rf[:, None] == safe[None, :, kp]).astype(jnp.float32)
            contrib = (wvf[:, None] * mk).reshape(t, k, t).sum(axis=1)
            return Gacc + contrib * va[None, :, kp], None

        # zeros_like keeps the operands' varying mesh axes under shard_map
        G, _ = jax.lax.scan(step, jnp.zeros_like(wv, shape=(t, t)),
                            jnp.arange(k))
    return G, jnp.sum(cva, axis=1)


def slab_spmv(rows, vals, d, *, n_loc: int):
    """``X_F @ d`` from a feature slab: (n_loc,) per-example product.

    O(nnz) work — the sparse-native residual/margin update. On TPU the
    Pallas kernel tiles the output rows with a broadcast-compare
    accumulate; elsewhere a 1-D scatter-add over nnz (3x cheaper on CPU
    than densify + matvec, and the scatter target is O(n_loc), never the
    (n_loc, T) tile).
    """
    valid = rows < n_loc
    dv = jnp.where(valid, vals, 0.0).astype(jnp.float32) * d[:, None]
    if _on_tpu():
        from repro.kernels.sparse_slab import slab_spmv_pallas

        return slab_spmv_pallas(jnp.minimum(rows, n_loc), dv, n_loc=n_loc,
                                interpret=False)
    out = jnp.zeros(n_loc + 1, jnp.float32)
    out = out.at[jnp.minimum(rows, n_loc).reshape(-1)].add(dv.reshape(-1))
    return out[:n_loc]


def slab_path_spmv(rows, vals, lam_idx, betas, *, n_loc: int):
    """Per-example-lambda slab SpMV: the batched scoring primitive of
    the mesh serving branch (``repro.serve``; local serving scores entry
    lists with :func:`entry_path_spmv`).

    rows/vals: (T, K) by-feature request slab with *local* example (=
    request row) indices, sentinel ``n_loc``; ``lam_idx`` (n_loc,) int32
    picks each example's operating point in the stacked ``betas`` (L, T)
    coefficient path. Returns the (n_loc,) scores
    ``out[i] = sum_jk vals[j,k] * betas[lam_idx[i], j] [rows[j,k] == i]``.

    The per-entry coefficient gather replaces ``d[:, None]`` in
    :func:`slab_spmv`; everything downstream (sentinel masking, the CPU
    scatter-add, the TPU Pallas row-block accumulate) is shared, so at a
    uniform ``lam_idx == l`` the scores are bit-identical to
    ``slab_spmv(rows, vals, betas[l], n_loc=n_loc)`` — the serve-vs-
    ``decision_function`` equivalence the tests pin down.
    """
    valid = rows < n_loc
    safe = jnp.minimum(rows, n_loc)
    # sentinel rows read lam_idx[0] through the clamp; their dv is zeroed
    # by the validity mask so the read value never matters
    with jax.named_scope("path_gather"):
        li = jnp.take(lam_idx, jnp.where(valid, rows, 0))        # (T, K)
        feat = jnp.arange(rows.shape[0], dtype=jnp.int32)[:, None]
        bsel = betas.astype(jnp.float32)[li, feat]               # (T, K)
    dv = jnp.where(valid, vals, 0.0).astype(jnp.float32) * bsel
    if _on_tpu():
        from repro.kernels.sparse_slab import slab_spmv_pallas

        return slab_spmv_pallas(safe, dv, n_loc=n_loc, interpret=False)
    out = jnp.zeros(n_loc + 1, jnp.float32)
    out = out.at[safe.reshape(-1)].add(dv.reshape(-1))
    return out[:n_loc]


def entry_path_spmv(rows, feats, vals, lam_idx, betas):
    """Per-row-lambda scores from a flat entry list: the local serving
    path's batched scoring primitive (``repro.serve``).

    rows/feats/vals: (N,) entries — request row, feature, value — with
    row ``n = lam_idx.shape[0]`` marking padding; ``lam_idx`` (n,) int32
    picks each row's operating point in the stacked ``betas`` (L, p)
    coefficient path. Returns the (n,) scores
    ``out[i] = sum_e vals[e] * betas[lam_idx[i], feats[e]] [rows[e] == i]``.
    O(N) work and operands, whatever the feature width.

    Padding entries are masked on the validity predicate, so any value
    parked on them scores exactly zero. The row sum is a float32 scatter-
    add in entry order: on CPU a row whose entries ascend by feature sums
    its terms in the order :func:`slab_spmv` does, so the scores are bit-
    identical to it on the same batch's slabs; on TPU the add order is
    the compiler's, so the scores agree within float32 rounding.
    """
    n = lam_idx.shape[0]
    valid = rows < n
    with jax.named_scope("entry_gather"):
        li = jnp.take(lam_idx, jnp.minimum(rows, n - 1))
        b = betas.astype(jnp.float32)[li, feats]
    c = jnp.where(valid, vals.astype(jnp.float32) * b, 0.0)
    out = jnp.zeros(n + 1, jnp.float32)
    out = out.at[jnp.minimum(rows, n)].add(c)
    return out[:n]


def slab_corr(rows, vals, v):
    """Per-feature correlation ``X_F^T v`` from a slab: the gather-reduce
    behind the sparse screen (sentinel slots masked to exact zero)."""
    n = v.shape[0]
    valid = rows < n
    va = jnp.where(valid, vals, 0.0).astype(jnp.float32)
    vg = jnp.where(valid, v.astype(jnp.float32)[jnp.where(valid, rows, 0)],
                   0.0)
    return jnp.sum(va * vg, axis=-1)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128):
    """Blocked online-softmax attention (forward)."""
    from repro.kernels.flash_attention import flash_attention_pallas

    return flash_attention_pallas(q, k, v, causal=causal, block_q=block_q,
                                  block_k=block_k,
                                  interpret=interpret_default())
