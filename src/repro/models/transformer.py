"""Block assembly: heterogeneous layer plans executed with scan-over-layers
on homogeneous segments (compile-time O(segments), not O(layers)).

Block types ("plan entries"):
  attn        — pre-norm attention + pre-norm FFN (MoE FFN if cfg.moe)
  attn_dense  — attention + *dense* FFN inside an MoE model (first layers)
  mamba       — pre-norm Mamba2/SSD block
  rwkv        — pre-norm RWKV6 time-mix + channel-mix
  shared_attn — hybrid (Zamba2): one shared attention+FFN block whose single
                parameter set is applied at every occurrence

Caches for decode are stacked per type; segments slice them in lockstep with
the params.
"""
from __future__ import annotations

import functools
import zlib
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.dist.sharding import shard_act
from repro.models import attention, ffn, layers as L, mla, moe, rwkv, ssm
from repro.precision.policy import ctx_for


# ---------------------------------------------------------------- segments --
def plan_segments(plan) -> List[Tuple[str, int, int]]:
    """[(type, start_occurrence, n)] with maximal same-type runs."""
    segs = []
    counts: Dict[str, int] = {}
    i = 0
    while i < len(plan):
        t = plan[i]
        j = i
        while j < len(plan) and plan[j] == t:
            j += 1
        n = j - i
        segs.append((t, counts.get(t, 0), n))
        counts[t] = counts.get(t, 0) + n
        i = j
    return segs


def plan_counts(plan) -> Dict[str, int]:
    c: Dict[str, int] = {}
    for t in plan:
        c[t] = c.get(t, 0) + 1
    return c


# ------------------------------------------------------------- block init --
def _attn_block_init(key, cfg, dense_ffn: bool):
    k1, k2 = jax.random.split(key)
    p: Dict[str, Any] = {"norm1": jnp.zeros((cfg.d_model,), jnp.float32),
                         "norm2": jnp.zeros((cfg.d_model,), jnp.float32)}
    if cfg.mla is not None:
        p["mla"] = mla.mla_init(k1, cfg)
    else:
        p["attn"] = attention.attn_init(k1, cfg)
    if cfg.moe is not None and not dense_ffn:
        p["moe"] = moe.moe_init(k2, cfg)
    else:
        p["mlp"] = ffn.ffn_init(k2, cfg.d_model, cfg.d_ff, cfg.ffn_act)
    return p


def _dec_attn_block_init(key, cfg):
    """Decoder block with cross-attention (encoder–decoder models)."""
    k1, k2, k3 = jax.random.split(key, 3)
    return {"norm1": jnp.zeros((cfg.d_model,), jnp.float32),
            "attn": attention.attn_init(k1, cfg),
            "norm_x": jnp.zeros((cfg.d_model,), jnp.float32),
            "cross_attn": attention.cross_attn_init(k2, cfg),
            "norm2": jnp.zeros((cfg.d_model,), jnp.float32),
            "mlp": ffn.ffn_init(k3, cfg.d_model, cfg.d_ff, cfg.ffn_act)}


def _mamba_block_init(key, cfg):
    return {"norm1": jnp.zeros((cfg.d_model,), jnp.float32),
            "ssm": ssm.ssm_init(key, cfg)}


def _rwkv_block_init(key, cfg):
    return {"norm1": jnp.zeros((cfg.d_model,), jnp.float32),
            "norm2": jnp.zeros((cfg.d_model,), jnp.float32),
            "rwkv": rwkv.rwkv_init(key, cfg)}


_BLOCK_INIT = {
    "attn": lambda k, c: _attn_block_init(k, c, dense_ffn=False),
    "attn_dense": lambda k, c: _attn_block_init(k, c, dense_ffn=True),
    "dec_attn": _dec_attn_block_init,
    "mamba": _mamba_block_init,
    "rwkv": _rwkv_block_init,
}


def init_blocks(key, cfg, plan) -> Dict[str, Any]:
    """Stacked params per block type (leading dim = #occurrences).  Each
    type folds a CRC of its name into the key: a stable tag, where
    Python's ``hash`` of a string changes with every process."""
    counts = plan_counts(plan)
    out: Dict[str, Any] = {}
    for t, n in counts.items():
        tkey = jax.random.fold_in(key, zlib.crc32(t.encode()) % (2 ** 31))
        if t == "shared_attn":
            out["shared"] = _attn_block_init(tkey, cfg, dense_ffn=True)
            continue
        keys = jax.random.split(tkey, n)
        stacked = [ _BLOCK_INIT[t](k, cfg) for k in keys ]
        out[t] = jax.tree.map(lambda *xs: jnp.stack(xs), *stacked)
    return out


# ------------------------------------------------------------ block apply --
def _apply_attn_block(p, x, positions, cfg, cache, positions3, rkey,
                      causal=True, collect=False, cache_len=None):
    """Returns (x, aux_loss, new_cache).  The block's quantized-GEMM
    context (cfg.gemm_policy + seed words from the per-layer key) is
    derived here and threaded into every weight GEMM below."""
    qc = ctx_for(cfg, rkey)
    h = L.rms_norm(x, p["norm1"])
    if cfg.mla is not None:
        a, new_cache = mla.mla_apply(p["mla"], h, positions, cfg,
                                     causal=causal, cache=cache,
                                     return_kv=collect, cache_len=cache_len,
                                     quant=qc)
    else:
        a, new_cache = attention.attn_apply(
            p["attn"], h, positions, cfg, causal=causal, cache=cache,
            positions3=positions3, return_kv=collect, cache_len=cache_len,
            quant=qc)
    x = x + a
    h2 = L.rms_norm(x, p["norm2"])
    if "moe" in p:
        y, aux = moe.moe_apply(p["moe"], h2, cfg, router_key=rkey, quant=qc)
    else:
        y, aux = ffn.ffn_apply(p["mlp"], h2, cfg.ffn_act,
                               quant=qc), jnp.float32(0.0)
    x = shard_act(x + y, "hidden")
    return x, aux, new_cache


def _apply_dec_attn_block(p, x, positions, cfg, cache, enc_out, key,
                          collect=False, cache_len=None):
    qc = ctx_for(cfg, key)
    h = L.rms_norm(x, p["norm1"])
    a, new_cache = attention.attn_apply(p["attn"], h, positions, cfg,
                                        causal=True, cache=cache,
                                        return_kv=collect,
                                        cache_len=cache_len, quant=qc)
    x = x + a
    hx = L.rms_norm(x, p["norm_x"])
    x = x + attention.cross_attn_apply(p["cross_attn"], hx, enc_out, cfg,
                                       quant=qc)
    h2 = L.rms_norm(x, p["norm2"])
    x = shard_act(x + ffn.ffn_apply(p["mlp"], h2, cfg.ffn_act, quant=qc),
                  "hidden")
    return x, jnp.float32(0.0), new_cache


def _apply_mamba_block(p, x, cfg, cache, rkey, collect=False):
    qc = ctx_for(cfg, rkey)
    h = L.rms_norm(x, p["norm1"])
    y, new_cache = ssm.ssm_apply(p["ssm"], h, cfg, cache=cache,
                                 return_state=collect, quant=qc)
    return shard_act(x + y, "hidden"), jnp.float32(0.0), new_cache


def _apply_rwkv_block(p, x, cfg, cache: Optional[rwkv.RWKVCache], rkey,
                      collect=False):
    qc = ctx_for(cfg, rkey)
    h = L.rms_norm(x, p["norm1"])
    y, tm_shift, state = rwkv.rwkv_time_mix(
        p["rwkv"], h, cfg, cache=cache, return_state=collect, quant=qc)
    x = x + y
    h2 = L.rms_norm(x, p["norm2"])
    y2, cm_shift = rwkv.rwkv_channel_mix(p["rwkv"], h2, cfg, cache=cache,
                                         quant=qc)
    x = shard_act(x + y2, "hidden")
    new_cache = None
    if cache is not None or (collect and state is not None):
        new_cache = rwkv.RWKVCache(tm_shift=tm_shift, cm_shift=cm_shift,
                                   state=state)
    return x, jnp.float32(0.0), new_cache


def _segment_caches(caches, t, i0, n):
    if caches is None or t not in caches:
        return None
    return jax.tree.map(
        lambda c: jax.lax.slice_in_dim(c, i0, i0 + n, axis=0), caches[t])


def _maybe_remat(fn, cfg):
    if cfg.remat == "none":
        return fn
    policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
              if cfg.remat == "dots" else None)
    return jax.checkpoint(fn, policy=policy)


def apply_blocks(blocks, x, positions, cfg, plan, *, caches=None,
                 positions3=None, rng=None, causal=True, enc_out=None,
                 collect_cache=False, cache_len=None):
    """Run the whole plan.  Returns (x, total_aux, new_caches).

    ``collect_cache=True`` (prefill) makes every block emit the cache its
    forward pass produced (KV / compressed-KV / SSM state / RWKV state);
    ``cache_len`` sets the capacity the emitted KV caches are padded to
    (default: exactly the prefill length — no room for decode appends)."""
    total_aux = jnp.float32(0.0)
    new_caches: Dict[str, List] = {}
    rng = rng if rng is not None else jax.random.PRNGKey(0)

    for seg_idx, (t, i0, n) in enumerate(plan_segments(plan)):
        seg_rng = jax.random.fold_in(rng, seg_idx)
        if t == "shared_attn":
            # shared params applied n times sequentially (occurrence cache
            # slots are still distinct)
            for occ in range(n):
                cache = _segment_caches(caches, t, i0 + occ, 1)
                cache = jax.tree.map(lambda c: c[0], cache) if cache else None
                body = _maybe_remat(
                    lambda p_, x_, c_: _apply_attn_block(
                        p_, x_, positions, cfg, c_, positions3,
                        jax.random.fold_in(seg_rng, occ), causal,
                        collect_cache, cache_len), cfg)
                x, aux, nc = body(blocks["shared"], x, cache)
                total_aux += aux
                if nc is not None:
                    new_caches.setdefault(t, []).append(nc)
            continue

        params_seg = jax.tree.map(
            lambda a: jax.lax.slice_in_dim(a, i0, i0 + n, axis=0), blocks[t])
        caches_seg = _segment_caches(caches, t, i0, n)

        def seg_body(carry, inp):
            x_, aux_ = carry
            p_, c_, k_ = inp
            if t in ("attn", "attn_dense"):
                x_, a_, nc = _apply_attn_block(p_, x_, positions, cfg, c_,
                                               positions3, k_, causal,
                                               collect_cache, cache_len)
            elif t == "dec_attn":
                x_, a_, nc = _apply_dec_attn_block(p_, x_, positions, cfg,
                                                   c_, enc_out, k_,
                                                   collect_cache, cache_len)
            elif t == "mamba":
                x_, a_, nc = _apply_mamba_block(p_, x_, cfg, c_, k_,
                                                collect_cache)
            elif t == "rwkv":
                x_, a_, nc = _apply_rwkv_block(p_, x_, cfg, c_, k_,
                                               collect_cache)
            else:
                raise ValueError(f"unknown block type {t!r}")
            return (x_, aux_ + a_), nc

        body = _maybe_remat(seg_body, cfg)
        keys = jax.random.split(seg_rng, n)
        if getattr(cfg, "scan_layers", True):
            (x, total_aux), ncs = jax.lax.scan(
                body, (x, total_aux), (params_seg, caches_seg, keys))
        else:
            # unrolled execution (analysis probes: every FLOP visible to
            # the compiled cost analysis — no while-loop undercounting)
            ncs_list = []
            for li in range(n):
                inp = jax.tree.map(lambda a: a[li],
                                   (params_seg, caches_seg, keys))
                (x, total_aux), nc_i = body((x, total_aux), inp)
                ncs_list.append(nc_i)
            ncs = (jax.tree.map(lambda *xs: jnp.stack(xs), *ncs_list)
                   if ncs_list and ncs_list[0] is not None else None)
        if ncs is not None and (caches_seg is not None or collect_cache):
            new_caches.setdefault(t, []).append(ncs)

    # reassemble stacked caches per type
    out_caches = None
    if caches is not None or collect_cache:
        out_caches = {}
        for t, parts in new_caches.items():
            if t == "shared_attn":
                out_caches[t] = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *parts)
            else:
                out_caches[t] = (parts[0] if len(parts) == 1 else
                                 jax.tree.map(
                                     lambda *xs: jnp.concatenate(xs, 0),
                                     *parts))
    return x, total_aux, out_caches
