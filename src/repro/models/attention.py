"""Attention: GQA (optional QKV bias), MLA (DeepSeek-V3), sliding-window,
cross-attention, and KV-cache plumbing for prefill/decode.

Memory discipline (the part that makes 32k prefill / 512-device dry-runs
fit): full-sequence attention never materializes an (S, S) score tensor or
mask. Queries are processed in chunks (``lax.map`` over a checkpointed
body): live memory is O(S * chunk) and the backward pass recomputes each
chunk's scores instead of storing them. Masks are computed per chunk from
position vectors. Head activations are sharded over `model` via
``constrain`` (divisibility-guarded).

Modes
-----
``mode="train"/"prefill"``: full-sequence causal attention; prefill returns
the populated cache. ``mode="decode"``: one new token against a cache of
``cache_len`` entries.

MLA decode uses the *absorbed* form (w_kv_b folded into the query/output) so
the per-step cost is O(S * (kv_lora + rope_dim)) per head instead of
reconstructing per-token K/V; the latent cache is what makes deepseek-v3
decode shapes fit.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import AttentionConfig
from repro.models.layers import (
    apply_mrope,
    apply_norm,
    apply_rope,
    dense_init,
    init_norm,
    text_mrope_positions,
)
from repro.sharding.ctx import constrain, flash_decode_enabled, unroll_enabled

NEG_INF = -1e30
Q_CHUNK = 1024          # query-chunk length for full-sequence attention


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_attention(key, cfg: AttentionConfig, d_model: int, dtype):
    if cfg.use_mla:
        return _init_mla(key, cfg, d_model, dtype)
    dh = cfg.resolved_head_dim(d_model)
    h, hk = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d_model, h * dh, dtype),
        "wk": dense_init(ks[1], d_model, hk * dh, dtype),
        "wv": dense_init(ks[2], d_model, hk * dh, dtype),
        "wo": dense_init(ks[3], h * dh, d_model, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * dh,), dtype)
        p["bk"] = jnp.zeros((hk * dh,), dtype)
        p["bv"] = jnp.zeros((hk * dh,), dtype)
    return p


def _init_mla(key, cfg: AttentionConfig, d_model: int, dtype):
    h = cfg.num_heads
    dq, dkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq_a": dense_init(ks[0], d_model, dq, dtype),
        "q_norm": init_norm(dq, dtype),
        "wq_b": dense_init(ks[1], dq, h * (dn + dr), dtype),
        # kv_a projects to latent + the shared rope key
        "wkv_a": dense_init(ks[2], d_model, dkv + dr, dtype),
        "kv_norm": init_norm(dkv, dtype),
        "wkv_b": dense_init(ks[3], dkv, h * (dn + dv), dtype),
        "wo": dense_init(ks[4], h * dv, d_model, dtype),
    }


def init_cross_attention(key, cfg: AttentionConfig, d_model: int, dtype):
    # same projection structure as GQA self-attention (kv from memory)
    return init_attention(key, cfg, d_model, dtype)


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: AttentionConfig, d_model: int, batch: int, cache_len: int, dtype):
    if cfg.use_mla:
        return {
            "latent": jnp.zeros((batch, cache_len, cfg.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, cache_len, cfg.qk_rope_head_dim), dtype),
        }
    dh = cfg.resolved_head_dim(d_model)
    hk = cfg.num_kv_heads
    return {
        "k": jnp.zeros((batch, cache_len, hk, dh), dtype),
        "v": jnp.zeros((batch, cache_len, hk, dh), dtype),
    }


def _cache_write(buf, new, index):
    """Write (B, s, ...) new entries at position `index` along axis 1."""
    zeros = (0,) * (buf.ndim - 2)
    return jax.lax.dynamic_update_slice(buf, new.astype(buf.dtype), (0, index) + zeros)


# ---------------------------------------------------------------------------
# chunked scaled-dot-product attention (no (S,S) materialization)
# ---------------------------------------------------------------------------

def _mask_chunk(q_pos, k_pos, *, causal, window, kv_limit):
    """(B, C, Sk) boolean mask for one query chunk."""
    m = jnp.ones(q_pos.shape + (k_pos.shape[-1],), bool)
    if causal:
        m = jnp.logical_and(m, q_pos[..., :, None] >= k_pos[..., None, :])
    if window:
        m = jnp.logical_and(m, q_pos[..., :, None] - k_pos[..., None, :] < window)
    if kv_limit is not None:
        m = jnp.logical_and(m, (k_pos <= kv_limit)[..., None, :])
    return m


def _sdpa_block(q, k, v, mask, *, scale):
    """q: (B,C,H,Dh); k/v: (B,Sk,H,Dh) (already head-expanded);
    mask (B,C,Sk) or None. Scores stay (B,H,C,Sk) — shardable on H."""
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if mask is not None:
        logits = jnp.where(mask[:, None, :, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32))
    return out.astype(v.dtype)


def sdpa(q, k, v, q_pos, k_pos, *, scale, causal=True, window=0, kv_limit=None,
         q_chunk: int = Q_CHUNK, use_flash_kernel: bool = False):
    """``use_flash_kernel`` routes plain causal/bidirectional self-attention
    through the Pallas blocked online-softmax kernel (kernels/flash_attention)
    when the shape qualifies (no window/limit, S | 128); falls back to the
    chunked jnp path otherwise. Equality tested in test_models.py."""
    if (use_flash_kernel and window == 0 and kv_limit is None
            and q.shape[1] == k.shape[1] and q.shape[1] % 128 == 0
            and q.shape[-1] == v.shape[-1]):
        from repro.kernels.ops import flash_attention

        h, hk = q.shape[2], k.shape[2]
        if hk != h:
            k = jnp.repeat(k, h // hk, axis=2)
            v = jnp.repeat(v, h // hk, axis=2)
        return flash_attention(q, k, v, causal=causal)
    return _sdpa_jnp(q, k, v, q_pos, k_pos, scale=scale, causal=causal,
                     window=window, kv_limit=kv_limit, q_chunk=q_chunk)


def _sdpa_jnp(q, k, v, q_pos, k_pos, *, scale, causal=True, window=0,
              kv_limit=None, q_chunk: int = Q_CHUNK):
    """Full attention with query chunking. q (B,Sq,H,Dh); k/v (B,Sk,Hk,Dh);
    q_pos (B,Sq); k_pos (B,Sk). Never builds an (Sq,Sk) global tensor.

    GQA: K/V are expanded to the full head count so the score tensor keeps a
    single flat head dim that shards cleanly over `model` (a grouped
    (Hk, G) layout would need one mesh axis across two dims). The expansion
    itself propagates the head sharding, so each device materializes only
    its local heads."""
    b, sq, h, dh = q.shape
    hk = k.shape[2]
    if hk != h:
        g = h // hk
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        k = constrain(k, "batch", None, "model", None)
        v = constrain(v, "batch", None, "model", None)

    if sq <= q_chunk or sq % q_chunk != 0:
        mask = _mask_chunk(q_pos, k_pos, causal=causal, window=window, kv_limit=kv_limit)
        return _sdpa_block(q, k, v, mask, scale=scale)

    nc = sq // q_chunk
    qc = q.reshape(b, nc, q_chunk, h, dh).transpose(1, 0, 2, 3, 4)
    pc = q_pos.reshape(b, nc, q_chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def body(args):
        qi, pi = args
        mask = _mask_chunk(pi, k_pos, causal=causal, window=window, kv_limit=kv_limit)
        return _sdpa_block(qi, k, v, mask, scale=scale)

    if unroll_enabled():
        # dry-run cost pass: loop bodies visible to HloCostAnalysis
        outs = [body((qc[i], pc[i])) for i in range(nc)]
        out = jnp.stack(outs)
    else:
        out = jax.lax.map(body, (qc, pc))                # (nc, B, C, H, Dv)
    return out.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, v.shape[-1])


# ---------------------------------------------------------------------------
# sequence-parallel flash-decode
# ---------------------------------------------------------------------------

def flash_decode_seq_sharded(q, ck, cv, cache_index, *, scale, window=0,
                             model_axis: str = "model"):
    """Decode attention against a cache whose SEQ dim is sharded over
    `model`: each shard computes a partial softmax over its local keys and
    the results combine with psum'd (max, denom, weighted-value) statistics
    — O(B*H*Dv) collective traffic instead of all-gathering the cache
    (which is ~20 GB/step for a 32k GQA cache with indivisible kv heads).

    q: (B,1,H,Dh) replicated; ck/cv: (B,S,H,Dh) seq-sharded (pre-expanded
    to full heads); returns (B,1,H,Dv) replicated.
    """
    from repro.sharding.ctx import current_mesh

    mesh = current_mesh()
    if mesh is None or model_axis not in mesh.axis_names:
        return None  # caller falls back to the gather path
    from jax.sharding import PartitionSpec as P
    from functools import partial as _partial

    b, _, h, dh = q.shape
    s = ck.shape[1]
    shards = mesh.shape[model_axis]
    if s % shards:
        return None
    # keep the batch dim sharded over the data axes (replicating it would
    # all-gather the whole cache over `data` — measured 8x worse, see §Perf)
    daxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dsz = 1
    for a in daxes:
        dsz *= mesh.shape[a]
    bax = daxes if (daxes and b % dsz == 0) else None

    @_partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(bax), P(bax, model_axis, None, None),
                  P(bax, model_axis, None, None), P(), P()),
        out_specs=P(bax),
    )
    def fd(qr, k_loc, v_loc, cache_idx, start_idx):
        # grouped GQA inside the explicit kernel: the cache is read once at
        # Hk heads (expanding to H first re-reads it H/Hk times — measured
        # 8x on the memory term for kv=2, see §Perf)
        s_loc, hk = k_loc.shape[1], k_loc.shape[2]
        g = qr.shape[2] // hk
        qg = qr.reshape(qr.shape[0], 1, hk, g, qr.shape[3])
        shard = jax.lax.axis_index(model_axis)
        k_pos = start_idx + shard * s_loc + jnp.arange(s_loc, dtype=jnp.int32)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                            k_loc.astype(jnp.float32)) * scale   # (B,Hk,G,1,S)
        mask = (k_pos <= cache_idx)[None, None, None, None, :]
        if window:
            mask = jnp.logical_and(
                mask, (cache_idx - k_pos < window)[None, None, None, None, :])
        logits = jnp.where(mask, logits, NEG_INF)
        m_loc = jnp.max(logits, axis=-1, keepdims=True)
        m_glb = jax.lax.pmax(m_loc, model_axis)
        w = jnp.exp(logits - m_glb)
        w = jnp.where(mask, w, 0.0)
        denom = jax.lax.psum(jnp.sum(w, axis=-1, keepdims=True), model_axis)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", w, v_loc.astype(jnp.float32))
        o = jax.lax.psum(o, model_axis)                           # (B,1,Hk,G,D)
        denom = denom.transpose(0, 3, 1, 2, 4)                    # -> (B,1,Hk,G,1)
        out = o / jnp.maximum(denom, 1e-30)
        b_, _, _, _, dv = out.shape
        return out.reshape(b_, 1, hk * g, dv).astype(v_loc.dtype)

    return fd(q, ck, cv, jnp.asarray(cache_index, jnp.int32),
              jnp.asarray(0, jnp.int32))


# ---------------------------------------------------------------------------
# GQA forward
# ---------------------------------------------------------------------------

def attention_forward(
    p,
    x: jnp.ndarray,                      # (B, S, D)
    *,
    cfg: AttentionConfig,
    d_model: int,
    positions: jnp.ndarray,              # (B, S) int32
    mode: str = "train",                 # train | prefill | decode
    cache: Optional[dict] = None,
    cache_index: Optional[jnp.ndarray] = None,   # scalar: #tokens already cached
    window: int = 0,                     # 0 = full causal
    window_slice: bool = False,          # decode: gather only the window from cache
    causal: bool = True,                 # False: bidirectional (encoder)
    seq_parallel_decode: bool = False,   # flash-decode over seq-sharded cache
):
    if cfg.use_mla:
        return _mla_forward(
            p, x, cfg=cfg, positions=positions, mode=mode, cache=cache,
            cache_index=cache_index, window=window, causal=causal,
        )
    b, s, _ = x.shape
    dh = cfg.resolved_head_dim(d_model)
    h, hk = cfg.num_heads, cfg.num_kv_heads

    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, h, dh)
    k = k.reshape(b, s, hk, dh)
    v = v.reshape(b, s, hk, dh)

    if cfg.use_mrope:
        pos3 = text_mrope_positions(positions)
        q = apply_mrope(q, pos3, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, pos3, cfg.rope_theta, cfg.mrope_sections)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    # shard head activations over `model` (falls back if indivisible)
    q = constrain(q, "batch", None, "model", None)
    k = constrain(k, "batch", None, "model", None)
    v = constrain(v, "batch", None, "model", None)

    scale = 1.0 / (dh ** 0.5)

    if mode in ("train", "prefill"):
        out = sdpa(q, k, v, positions, positions, scale=scale, causal=causal,
                   window=window)
        new_cache = {"k": k, "v": v} if mode == "prefill" else None
        y = out.reshape(b, s, h * dh) @ p["wo"]
        return y, new_cache

    # ---- decode: s == 1 ----
    assert cache is not None and cache_index is not None
    cache_len = cache["k"].shape[1]
    ck = _cache_write(cache["k"], k, cache_index)
    cv = _cache_write(cache["v"], v, cache_index)

    if (seq_parallel_decode or flash_decode_enabled()) and not (window and window_slice):
        out = flash_decode_seq_sharded(q, ck, cv, cache_index, scale=scale,
                                       window=window)
        if out is not None:
            y = out.reshape(b, s, h * out.shape[-1]) @ p["wo"]
            return y, {"k": _cache_write(cache["k"], k, cache_index),
                       "v": _cache_write(cache["v"], v, cache_index)}
        # fall through to the gather path outside a mesh context

    if window and window_slice and cache_len > 2 * window:
        # long_500k: gather only the last `window` entries; the dead prefix
        # of the cache is never read.
        start = jnp.maximum(cache_index + 1 - window, 0)
        ck_r = jax.lax.dynamic_slice_in_dim(ck, start, window, axis=1)
        cv_r = jax.lax.dynamic_slice_in_dim(cv, start, window, axis=1)
        k_pos_r = start + jnp.arange(window, dtype=jnp.int32)[None, :]
        out = sdpa(q, ck_r, cv_r, positions, k_pos_r, scale=scale, causal=True,
                   window=window, kv_limit=cache_index)
    else:
        k_pos = jnp.arange(cache_len, dtype=jnp.int32)[None, :]
        out = sdpa(q, ck, cv, positions, k_pos, scale=scale, causal=True,
                   window=window, kv_limit=cache_index)

    y = out.reshape(b, s, h * dh) @ p["wo"]
    return y, {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# MLA forward (DeepSeek-V3)
# ---------------------------------------------------------------------------

def _mla_forward(p, x, *, cfg, positions, mode, cache, cache_index, window,
                 causal=True):
    b, s, _ = x.shape
    h = cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    dkv = cfg.kv_lora_rank

    q_lat = apply_norm(p["q_norm"], x @ p["wq_a"])
    q = (q_lat @ p["wq_b"]).reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv_a = x @ p["wkv_a"]                                 # (B,S,dkv+dr)
    latent = apply_norm(p["kv_norm"], kv_a[..., :dkv])    # (B,S,dkv)
    k_rope = apply_rope(kv_a[..., dkv:], positions, cfg.rope_theta)  # shared

    scale = 1.0 / ((dn + dr) ** 0.5)
    wkv_b = p["wkv_b"].reshape(dkv, h, dn + dv)
    wk_b, wv_b = wkv_b[..., :dn], wkv_b[..., dn:]         # (dkv,H,dn), (dkv,H,dv)

    if mode in ("train", "prefill"):
        k_nope = jnp.einsum("bsk,khd->bshd", latent, wk_b)
        v = jnp.einsum("bsk,khd->bshd", latent, wv_b)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, dr))], axis=-1
        )
        qf = jnp.concatenate([q_nope, q_rope], axis=-1)
        qf = constrain(qf, "batch", None, "model", None)
        k = constrain(k, "batch", None, "model", None)
        v = constrain(v, "batch", None, "model", None)
        out = sdpa(qf, k, v, positions, positions, scale=scale, causal=causal,
                   window=window)
        y = out.reshape(b, s, h * dv) @ p["wo"]
        new_cache = {"latent": latent, "k_rope": k_rope} if mode == "prefill" else None
        return y, new_cache

    # ---- absorbed decode ----
    assert cache is not None and cache_index is not None
    lat_c = _cache_write(cache["latent"], latent, cache_index)   # (B,Sc,dkv)
    kr_c = _cache_write(cache["k_rope"], k_rope, cache_index)    # (B,Sc,dr)
    cache_len = lat_c.shape[1]
    k_pos = jnp.arange(cache_len, dtype=jnp.int32)[None, :]

    # absorb wk_b into the query: q_abs (B,1,H,dkv)
    q_abs = jnp.einsum("bshd,khd->bshk", q_nope, wk_b)
    logits = (
        jnp.einsum("bshk,bck->bhsc", q_abs.astype(jnp.float32), lat_c.astype(jnp.float32))
        + jnp.einsum("bshd,bcd->bhsc", q_rope.astype(jnp.float32), kr_c.astype(jnp.float32))
    ) * scale                                              # (B,H,1,Sc)
    mask = jnp.logical_and(
        k_pos[..., None, :] <= cache_index,
        positions[..., :, None] >= k_pos[..., None, :],
    )
    if window:
        mask = jnp.logical_and(mask, positions[..., :, None] - k_pos[..., None, :] < window)
    logits = jnp.where(mask[:, None, :, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    o_lat = jnp.einsum("bhsc,bck->bshk", w, lat_c.astype(jnp.float32))  # (B,1,H,dkv)
    out = jnp.einsum("bshk,khd->bshd", o_lat, wv_b.astype(jnp.float32))  # (B,1,H,dv)
    y = out.reshape(b, s, h * dv).astype(x.dtype) @ p["wo"]
    return y, {"latent": lat_c, "k_rope": kr_c}


# ---------------------------------------------------------------------------
# cross attention (enc-dec)
# ---------------------------------------------------------------------------

def cross_attention_forward(p, x, memory, *, cfg: AttentionConfig, d_model: int):
    """x: (B,Sq,D) decoder states; memory: (B,Sk,D) encoder output."""
    b, sq, _ = x.shape
    sk = memory.shape[1]
    dh = cfg.resolved_head_dim(d_model)
    h, hk = cfg.num_heads, cfg.num_kv_heads
    q = (x @ p["wq"]).reshape(b, sq, h, dh)
    k = (memory @ p["wk"]).reshape(b, sk, hk, dh)
    v = (memory @ p["wv"]).reshape(b, sk, hk, dh)
    if "bq" in p:
        q = q + p["bq"].reshape(h, dh)
        k = k + p["bk"].reshape(hk, dh)
        v = v + p["bv"].reshape(hk, dh)
    q_pos = jnp.zeros((b, sq), jnp.int32)
    k_pos = jnp.zeros((b, sk), jnp.int32)
    out = sdpa(q, k, v, q_pos, k_pos, scale=1.0 / (dh ** 0.5), causal=False)
    return out.reshape(b, sq, h * dh) @ p["wo"]
