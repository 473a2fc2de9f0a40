"""GQA/MQA attention with RoPE / M-RoPE, sliding windows, KV caches.

Two entry modes:
* train/prefill: full-sequence causal (or bidirectional for encoders);
* decode: one new token against a (B, S_max, n_kv, hd) cache.

TP sharding: head dims are annotated with the "model" axis by the trainer's
sharding rules (dist/sharding.py); the code itself is mesh-agnostic.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import common as KC
from repro.models import layers as L
from repro.precision import attention as PA
from repro.precision import policy as QP
from repro.serving import paged_cache as PC


class KVCache(NamedTuple):
    k: jax.Array       # (B, S_max, n_kv, hd)
    v: jax.Array
    length: jax.Array  # () int32 — tokens already cached


def attn_init(key, cfg):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nh, nkv = cfg.n_heads, cfg.n_kv_heads
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": L.dense_init(k1, d, nh * hd),
        "wk": L.dense_init(k2, d, nkv * hd),
        "wv": L.dense_init(k3, d, nkv * hd),
        "wo": L.dense_init(k4, nh * hd, d, scale=1.0 / (nh * hd) ** 0.5),
    }


def _rotary(q, k, positions, cfg, positions3=None):
    if cfg.pos == "rope":
        return (L.apply_rope(q, positions, cfg.rope_theta),
                L.apply_rope(k, positions, cfg.rope_theta))
    if cfg.pos == "mrope":
        hd = q.shape[-1]
        third = hd // 2 // 3
        sections = (hd // 2 - 2 * third, third, third)
        if positions3 is None:
            positions3 = jnp.broadcast_to(positions[None],
                                          (3,) + positions.shape)
        return (L.apply_mrope(q, positions3, cfg.rope_theta, sections),
                L.apply_mrope(k, positions3, cfg.rope_theta, sections))
    return q, k


def _sdpa(q, k, v, mask, scale):
    """Naive attention: materializes (B, KV, G, Sq, Skv) scores.  Kept as
    the §Perf baseline and for decode (Sq == 1).  GQA via head grouping;
    the value head-dim may differ from the key head-dim (MLA)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    group = H // KV
    dv = v.shape[-1]
    q = q.reshape(B, Sq, KV, group, hd)
    logits = jnp.einsum("bqkgh,bskh->bkgqs", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskv->bqkgv", probs.astype(v.dtype), v)
    return out.reshape(B, Sq, H, dv)


def flash_attention(q, k, v, scale, *, causal=True, window: int = 0,
                    q_block: int = 1024, kv_block: int = 1024):
    """Blocked attention with online softmax (FlashAttention recurrence,
    TPU-native: plain MXU matmuls over VMEM-sized tiles; blocks are
    python-unrolled so the dry-run cost analysis sees every FLOP).

    q: (B, Sq, H, dk); k: (B, Skv, KV, dk); v: (B, Skv, KV, dv).
    Causal blocks strictly above the diagonal (and outside the sliding
    window) are skipped entirely — the same work-skipping a production
    kernel does.
    """
    B, Sq, H, dk = q.shape
    KV = k.shape[2]
    G = H // KV
    dv = v.shape[-1]
    qb = min(q_block, Sq)
    kb = min(kv_block, k.shape[1])
    n_q = -(-Sq // qb)
    n_k = -(-k.shape[1] // kb)
    qf = q.astype(jnp.float32).reshape(B, Sq, KV, G, dk)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    out_blocks = []
    for i in range(n_q):
        q_i = qf[:, i * qb:(i + 1) * qb]                    # (B,qb,KV,G,dk)
        qlen = q_i.shape[1]
        m = jnp.full((B, KV, G, qlen), -jnp.inf, jnp.float32)
        l = jnp.zeros((B, KV, G, qlen), jnp.float32)
        acc = jnp.zeros((B, KV, G, qlen, dv), jnp.float32)
        q_lo = i * qb
        q_hi = q_lo + qlen - 1
        for j in range(n_k):
            k_lo = j * kb
            if causal and k_lo > q_hi:
                continue                                    # above diagonal
            k_hi = min((j + 1) * kb, k.shape[1]) - 1
            if window and k_hi < q_lo - window + 1:
                continue                                    # left of window
            k_j = kf[:, k_lo:k_hi + 1]
            v_j = vf[:, k_lo:k_hi + 1]
            s = jnp.einsum("bqkgh,bskh->bkgqs", q_i, k_j) * scale
            need_mask = (causal and k_hi > q_lo) or window
            if need_mask:
                qpos = jnp.arange(q_lo, q_hi + 1)[:, None]
                kpos = jnp.arange(k_lo, k_hi + 1)[None, :]
                mask = kpos <= qpos if causal else jnp.ones_like(
                    kpos <= qpos)
                if window:
                    mask = mask & (kpos > qpos - window)
                s = jnp.where(mask[None, None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # guard fully-masked rows (m_new == -inf)
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(jnp.isfinite(s), p, 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskv->bkgqv", p, v_j)
            m = m_new
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        out_blocks.append(out)                              # (B,KV,G,qb,dv)
    o = jnp.concatenate(out_blocks, axis=3)                 # (B,KV,G,Sq,dv)
    return jnp.moveaxis(o, 3, 1).reshape(B, Sq, H, dv).astype(q.dtype)


def causal_mask(Sq: int, Skv: int, q_offset=0, window: int = 0):
    q_pos = jnp.arange(Sq)[:, None] + q_offset
    k_pos = jnp.arange(Skv)[None, :]
    m = k_pos <= q_pos
    if window:
        m = m & (k_pos > q_pos - window)
    return m


def attn_apply(params, x, positions, cfg, *, causal=True,
               cache: Optional[KVCache] = None,
               positions3=None,
               return_kv: bool = False,
               cache_len: Optional[int] = None,
               quant=None) -> Tuple[jax.Array, Optional[KVCache]]:
    """x: (B, S, D). With ``cache`` given, S is the new-token count (decode).
    ``quant``: optional QuantCtx — routes the q/k/v/o projections through
    the rounded-GEMM path, the attention op itself through the rounded
    flash kernels (policy attn_qk/attn_av/attn_out sites), and KV-cache
    appends through the ``kv_cache_fmt`` storage grid (optionally packed).
    ``cache_len``: capacity of the cache emitted under ``return_kv`` —
    decode appends past S need it, since ``dynamic_update_slice`` clamps
    (and silently overwrites) at an exhausted capacity."""
    B, S, D = x.shape
    hd = cfg.resolved_head_dim
    nh, nkv = cfg.n_heads, cfg.n_kv_heads
    dtype = x.dtype
    scale = 1.0 / hd ** 0.5
    pol = quant.policy if quant is not None else None
    kv_fmt = pol.kv_cache_fmt if pol is not None else None
    kv_packed = kv_fmt is not None and pol.kv_cache_packed

    q = L.qdense(x, params["wq"], quant, QP.TAG_ATTN_Q).reshape(B, S, nh, hd)
    k = L.qdense(x, params["wk"], quant, QP.TAG_ATTN_K).reshape(B, S, nkv, hd)
    v = L.qdense(x, params["wv"], quant, QP.TAG_ATTN_V).reshape(B, S, nkv, hd)
    q, k = _rotary(q, k, positions, cfg, positions3)

    if isinstance(cache, PC.PagedKVCache):
        # serving: append into the shared page pool through the slot's
        # block table, attend through the paged kernel (single-token) or
        # the gathered logical view (chunked prefill / identity sites).
        # All rounding is request-keyed off ``cache.words`` — never the
        # batch slot, the physical pages, or ``quant.words`` — so a
        # request's stream is bit-identical across batching schedules.
        spec = PA.kv_cache_spec(pol)
        w_kv = PA.fold_words_vec(cache.words, jnp.uint32(QP.TAG_ATTN_KV))
        k_st = PA.round_kv_request(k, spec, w_kv, cache.lengths, stream=0)
        v_st = PA.round_kv_request(v, spec, w_kv, cache.lengths, stream=1)
        if kv_packed:
            k_st = KC.pack_block(k_st, spec.fmt)
            v_st = KC.pack_block(v_st, spec.fmt)
        with jax.named_scope("attn.kv_append"):
            k_pages = PC.paged_append(cache.k_pages, cache.tables,
                                      cache.lengths, cache.append, k_st)
            v_pages = PC.paged_append(cache.v_pages, cache.tables,
                                      cache.lengths, cache.append, v_st)
        new_len = cache.lengths + jnp.where(cache.append, S, 0).astype(
            jnp.int32)
        new_cache = cache._replace(k_pages=k_pages, v_pages=v_pages,
                                   lengths=new_len)
        if S == 1 and pol is not None and not pol.attn_sites_identity:
            with jax.named_scope("attn.paged_decode"):
                out = PA.qattn_decode_paged(
                    q, k_pages, v_pages, new_len, cache.tables, cache.words,
                    pol, scale=scale, window=cfg.sliding_window,
                    kv_fmt=spec.fmt if kv_packed else None)
        else:
            k_f = PC.paged_gather(k_pages, cache.tables)
            v_f = PC.paged_gather(v_pages, cache.tables)
            if kv_packed:
                k_f = KC.unpack_block(k_f, spec.fmt)
                v_f = KC.unpack_block(v_f, spec.fmt)
            Skv = k_f.shape[1]
            # per-slot, per-row causality: each appended row attends to
            # its own logical prefix (and sliding window) only
            q_pos = cache.lengths[:, None] + jnp.arange(S)       # (B, S)
            k_pos = jnp.arange(Skv)
            valid = k_pos[None, None, :] <= q_pos[:, :, None]
            if cfg.sliding_window:
                valid = valid & (k_pos[None, None, :]
                                 > q_pos[:, :, None] - cfg.sliding_window)
            out = _sdpa(q, k_f.astype(dtype), v_f.astype(dtype), valid,
                        scale)
    elif cache is not None:
        # decode: append new k/v at cache.length, attend to the full prefix
        start = cache.length
        k_st = PA.kv_store(k, quant, pos0=start, stream=0)
        v_st = PA.kv_store(v, quant, pos0=start, stream=1)
        k_all = jax.lax.dynamic_update_slice(
            cache.k, k_st.astype(cache.k.dtype), (0, start, 0, 0))
        v_all = jax.lax.dynamic_update_slice(
            cache.v, v_st.astype(cache.v.dtype), (0, start, 0, 0))
        Skv = k_all.shape[1]
        new_cache = KVCache(k=k_all, v=v_all, length=start + S)
        if S == 1 and pol is not None and not pol.attn_identity:
            # single-token decode through the Pallas flash-decode kernel:
            # packed caches are decoded on load, in-kernel
            out = PA.qattn_decode(
                q, k_all, v_all, start + S, quant, scale=scale,
                window=cfg.sliding_window,
                kv_fmt=PA.kv_cache_spec(pol).fmt if kv_packed else None,
                kv_block=getattr(cfg, "attn_kv_block", 1024))
        else:
            if kv_packed:
                kv_spec = PA.kv_cache_spec(pol)
                k_f = KC.unpack_block(k_all, kv_spec.fmt)
                v_f = KC.unpack_block(v_all, kv_spec.fmt)
            else:
                k_f, v_f = k_all, v_all
            # per-row positions: appended tokens stay causal *within* the
            # chunk, and the sliding-window lower bound moves with each row
            # (a single chunk-level bound would let appended tokens attend
            # to each other acausally)
            q_pos = start + jnp.arange(S)
            k_pos = jnp.arange(Skv)
            valid = k_pos[None, :] <= q_pos[:, None]
            if cfg.sliding_window:
                valid = valid & (k_pos[None, :]
                                 > q_pos[:, None] - cfg.sliding_window)
            mask = jnp.broadcast_to(valid[None], (B, S, Skv))
            out = _sdpa(q, k_f.astype(dtype), v_f.astype(dtype), mask,
                        scale)
    else:
        if pol is not None and not pol.attn_sites_identity:
            out = PA.qattention(q, k, v, quant, scale=scale, causal=causal,
                                window=cfg.sliding_window,
                                q_block=getattr(cfg, "attn_q_block", 1024),
                                kv_block=getattr(cfg, "attn_kv_block", 1024))
        elif getattr(cfg, "attn_impl", "flash") == "flash":
            out = flash_attention(q, k, v, scale, causal=causal,
                                  window=cfg.sliding_window,
                                  q_block=getattr(cfg, "attn_q_block", 1024),
                                  kv_block=getattr(cfg, "attn_kv_block", 1024))
        else:
            if causal:
                m = causal_mask(S, S, window=cfg.sliding_window)
            else:
                m = jnp.ones((S, S), bool)
            mask = jnp.broadcast_to(m[None], (B, S, S))
            out = _sdpa(q, k, v, mask, scale)
        new_cache = None
        if return_kv:   # prefill: emit the cache this pass produced,
            # padded to an explicit capacity — an unpadded (B, S, ...)
            # cache makes the next decode's update_slice clamp at start=S
            # and silently overwrite the last prefill token
            cap = S if cache_len is None else int(cache_len)
            if cap < S:
                raise ValueError(
                    f"cache_len={cap} is smaller than the prefill "
                    f"length {S}")
            if kv_fmt is not None:
                k_st = PA.kv_store(k, quant, pos0=0, stream=0)
                v_st = PA.kv_store(v, quant, pos0=0, stream=1)
            else:
                k_st = k.astype(jnp.bfloat16)
                v_st = v.astype(jnp.bfloat16)
            pad = ((0, 0), (0, cap - S), (0, 0), (0, 0))
            new_cache = KVCache(k=jnp.pad(k_st, pad), v=jnp.pad(v_st, pad),
                                length=jnp.full((), S, jnp.int32))

    y = L.qdense(out.reshape(B, S, nh * hd), params["wo"], quant,
                 QP.TAG_ATTN_O)
    return y, new_cache


def cross_attn_init(key, cfg):
    return attn_init(key, cfg)


def cross_attn_apply(params, x, enc_out, cfg, quant=None):
    """Decoder cross-attention (no cache for enc k/v recompute simplicity)."""
    B, S, D = x.shape
    hd = cfg.resolved_head_dim
    nh, nkv = cfg.n_heads, cfg.n_kv_heads
    dtype = x.dtype
    enc_out = enc_out.astype(dtype)
    q = L.qdense(x, params["wq"], quant, QP.TAG_CROSS_Q).reshape(B, S, nh, hd)
    k = L.qdense(enc_out, params["wk"], quant, QP.TAG_CROSS_K).reshape(
        B, enc_out.shape[1], nkv, hd)
    v = L.qdense(enc_out, params["wv"], quant, QP.TAG_CROSS_V).reshape(
        B, enc_out.shape[1], nkv, hd)
    mask = jnp.ones((B, S, enc_out.shape[1]), bool)
    out = _sdpa(q, k, v, mask, 1.0 / hd ** 0.5)
    return L.qdense(out.reshape(B, S, nh * hd), params["wo"], quant,
                    QP.TAG_CROSS_O)


def cache_dtype(cfg, dtype=jnp.bfloat16):
    """Storage dtype the policy dictates for KV caches: packed code words
    (uint8/uint16) for a packed ``kv_cache_fmt``, float32 grid values for
    an unpacked one, else the caller's ``dtype``."""
    pol = QP.resolve_policy(getattr(cfg, "gemm_policy", None))
    spec = PA.kv_cache_spec(pol)
    if spec is None:
        return dtype
    if pol.kv_cache_packed:
        return KC.pack_dtype(spec.fmt)
    return jnp.float32


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16,
               n_layers: Optional[int] = None) -> KVCache:
    """Stacked (over layers) KV cache for decode.  The storage dtype
    follows ``cfg.gemm_policy``'s ``kv_cache_fmt`` (packed uint8 cache:
    4x the decode batch at fixed HBM)."""
    nl = n_layers if n_layers is not None else cfg.n_layers
    hd = cfg.resolved_head_dim
    shape = (nl, batch, max_len, cfg.n_kv_heads, hd)
    dt = cache_dtype(cfg, dtype)
    # length carried per layer so stacked caches slice/scan uniformly
    return KVCache(k=jnp.zeros(shape, dt), v=jnp.zeros(shape, dt),
                   length=jnp.zeros((nl,), jnp.int32))
