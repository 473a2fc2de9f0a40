"""Pallas flash attention with in-kernel stochastic rounding + packed KV.

The attention op gets the same treatment qmatmul gave the dense GEMMs:
three rounding **sites** per op — the QKᵀ logits (``qk``), each kv-block's
P·V partial product (``av``), and the normalized output (``out``) — each
with its own :class:`~repro.core.rounding.RoundingSpec` and its own seed
word pair, drawn in-kernel (no bits operands in HBM; see kernels/common).

Kernel family
  * :func:`flash_fwd_p` — train/prefill forward, online softmax over kv
    blocks, emits ``(out, m, l)`` so the backward can recompute the rounded
    logits bit-exactly.
  * :func:`flash_bwd_dq_p` / :func:`flash_bwd_dkv_p` — the two backward
    kernels (dq gridded over q blocks, dk/dv over kv blocks).  Rounding is
    straight-through w.r.t. the forward's rounding; the recomputed ``s``
    uses the *same* qk seed words, stream and global coordinates as the
    forward, so the softmax is differentiated at exactly the forward's
    rounded logits.  The dq/dk contributions round on the qk spec, dv on
    the av spec, each under a site-fold of the forward words.
  * :func:`flash_decode_p` — single-token decode over a packed or float KV
    cache: ``kv_fmt`` names a packable grid and the kernel decodes the
    uint8/uint16 code words on load (``common.unpack_block``), so the cache
    never materializes in float in HBM.

Randomness discipline: the qk draw is keyed by the element's global
``(q position, k position)`` and the out draw by ``(q position, column)``
— both independent of the block partition, like ``qmatmul``.  The av draw
necessarily happens once per kv *block* (that is where the partial product
exists), so its stream index is the kv-block index: av bits depend on
``kv_block`` but not on ``q_block``.

Every ``*_p`` kernel has a ``*_reference`` twin: plain-jnp replays of the
identical blocked math (literally the same `_fwd_block` / `_bwd_p_ds`
helpers) on zero-padded operands, drawing the identical counter bits.
Under ``interpret=True`` (CPU CI) kernel == reference **bit-for-bit**,
masks, tails and all — that is the oracle contract tests/test_flash_kernels
enforces.  On real TPU the draws come from the hardware PRNG instead and
the contract is statistical (eqs. 3-5), exactly as for qmatmul.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.rounding import RoundingSpec
from repro.kernels import common

# Seed-word column of each rounding site inside the seeds operand:
# fwd/decode carry [qk | av | out] pairs, bwd-dq [qk | dq], bwd-dkv
# [qk | dk | dv] — site ``s`` reads words ``seeds[.., 2s:2s+2]``.
SITE_QK, SITE_AV, SITE_OUT = 0, 1, 2
SITE_BWD_A, SITE_BWD_B = 1, 2

_DEF_BLOCK = 512
_SEMANTICS = ("parallel", "parallel", "arbitrary")
# Scoped-VMEM cap for the flash kernels.  The default 16 MiB is too small
# for the dk/dv backward at 1024x1024 blocks (its f32 s, p and ds tiles
# and their transposes); v5e has 128 MiB of VMEM per core.
_VMEM_LIMIT = 64 * 2 ** 20


class AttnSpecs(NamedTuple):
    """One RoundingSpec per forward attention site."""
    qk: RoundingSpec
    av: RoundingSpec
    out: RoundingSpec


def _kv_of(bh, n_heads: int, n_kv: int):
    """Query-head block index -> kv-head block index (grouped GQA)."""
    return bh // n_heads * n_kv + (bh % n_heads) // (n_heads // n_kv)


def _position_mask(shape, q0, k0, *, q_len, kv_len, causal: bool,
                   window: int):
    """Validity of each (query row, key col) of one block, in *global*
    positions; also bounds both sequence tails (ragged last blocks read
    undefined memory — NaN under interpret)."""
    qpos = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + q0
    kpos = jax.lax.broadcasted_iota(jnp.int32, shape, 1) + k0
    valid = (qpos < q_len) & (kpos < kv_len)
    if causal:
        valid &= kpos <= qpos
    if window:
        valid &= kpos > qpos - window
    return valid


def _decode_mask(shape, k0, length, window: int):
    """Single-token decode mask: rows are query heads of one kv group, the
    query position is ``length - 1`` for every row."""
    kpos = jax.lax.broadcasted_iota(jnp.int32, shape, 1) + k0
    valid = kpos < length
    if window:
        valid &= kpos > length - 1 - window
    return valid


def _zero_tail_rows(x, r0, limit):
    """Zero rows at global positions >= limit (they hold undefined data in
    a ragged last block and would turn 0·NaN into NaN inside a dot)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) + r0
    return jnp.where(rows < limit, x, jnp.float32(0.0))


def _fwd_block(specs: AttnSpecs, scale, q_blk, k_blk, v_blk, valid, r0, c0,
               kv_limit, av_stream, draw, m, l, acc):
    """One (q_block, kv_block) online-softmax update.  Shared verbatim by
    the kernel body and the jnp reference — the bit-exactness contract.

    ``draw(site, shape, row0, col0, stream, rand_bits)`` returns uint32
    bits; ``r0``/``c0`` are the block's global (row, col) offsets.
    """
    s = jnp.dot(q_blk, k_blk.T, preferred_element_type=jnp.float32) \
        * jnp.float32(scale)
    bits = draw(SITE_QK, s.shape, r0, c0, 0, specs.qk.rand_bits) \
        if specs.qk.stochastic else None
    s = common.apply_spec_block(specs.qk, s, bits)
    s = jnp.where(valid, s, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, jnp.float32(0.0))
    p = jnp.exp(s - m_safe)
    p = jnp.where(jnp.isfinite(s), p, jnp.float32(0.0))
    corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), jnp.float32(0.0))
    pv = jnp.dot(p, _zero_tail_rows(v_blk, c0, kv_limit),
                 preferred_element_type=jnp.float32)
    bits = draw(SITE_AV, pv.shape, r0, 0, av_stream, specs.av.rand_bits) \
        if specs.av.stochastic else None
    pv = common.apply_spec_block(specs.av, pv, bits)
    l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
    return m_new, l_new, acc * corr + pv


def _fwd_finish(specs: AttnSpecs, acc, l, r0, draw):
    out = acc / jnp.maximum(l, jnp.float32(1e-30))
    bits = draw(SITE_OUT, out.shape, r0, 0, 0, specs.out.rand_bits) \
        if specs.out.stochastic else None
    return common.apply_spec_block(specs.out, out, bits)


def _bwd_p_ds(spec_qk: RoundingSpec, scale, q_blk, k_blk, v_blk, do_blk,
              m_col, l_col, d_col, valid, r0, c0, draw):
    """Recompute the forward's rounded logits (same qk words, stream 0,
    global coordinates => bit-identical s) and form the normalized
    probabilities and the softmax-backward ``ds``; both fully masked so
    undefined tail reads can't leak NaN into the grad dots."""
    s = jnp.dot(q_blk, k_blk.T, preferred_element_type=jnp.float32) \
        * jnp.float32(scale)
    bits = draw(SITE_QK, s.shape, r0, c0, 0, spec_qk.rand_bits) \
        if spec_qk.stochastic else None
    s = common.apply_spec_block(spec_qk, s, bits)
    m_safe = jnp.where(jnp.isfinite(m_col), m_col, jnp.float32(0.0))
    linv = jnp.where(l_col > 0, 1.0 / l_col, jnp.float32(0.0))
    p = jnp.where(valid, jnp.exp(s - m_safe) * linv, jnp.float32(0.0))
    dp = jnp.dot(do_blk, v_blk.T, preferred_element_type=jnp.float32)
    ds = jnp.where(valid, p * (dp - d_col) * jnp.float32(scale),
                   jnp.float32(0.0))
    return p, ds


def _blocks(size, block):
    b = min(block, size)
    return b, -(-size // b)


def _res_rows(*res):
    """(B·H, Sq) softmax residuals -> the kernels' (B·H, 1, Sq) layout: a
    (1, q_block) block of it spans the array's unit middle dim, which
    Mosaic's (8, 128) block rule accepts where a (1, q_block) block of the
    (B·H, Sq) array is refused."""
    return tuple(r.reshape(r.shape[0], 1, r.shape[1]) for r in res)


def _kernel_draw(seed_ref, row, block_id, interpret: bool):
    """The kernels' ``draw`` for one grid block, on row ``row`` of the
    seeds operand.  On the chip every draw reseeds the hardware PRNG from
    its own site's words and ``block_id``, so no two sites share bits and
    a recompute with the same words and block id redraws the same bits.
    Under interpret the counter derivation keys the element coordinates
    and ``stream`` instead (the reference's draw)."""
    def draw(site, shape, row0, col0, stream, rb):
        w0, w1 = seed_ref[row, 2 * site], seed_ref[row, 2 * site + 1]
        common.seed_kernel_prng_words(w0, w1, block_id, interpret=interpret)
        return common.kernel_bits_words(
            w0, w1, shape, row0=row0, col0=col0, stream=stream,
            rand_bits=rb, interpret=interpret)
    return draw


def _check_seeds(seeds, n, cols):
    seeds = jnp.asarray(seeds, jnp.uint32)
    if seeds.shape != (n, cols):
        raise ValueError(f"seeds must be ({n}, {cols}) uint32 site words, "
                         f"got {seeds.shape}")
    return seeds


def _ref_draw(words):
    """Reference-side draw: the counter derivation the interpret-mode
    kernel uses, on one row of the seeds operand."""
    def draw(site, shape, row0, col0, stream, rb):
        return common.counter_bits_reduced(
            words[2 * site], words[2 * site + 1], shape, rb,
            row0=row0, col0=col0, stream=stream)
    return draw


def _pad_rows(x, n):
    if x.shape[1] == n:
        return x.astype(jnp.float32)
    pad = [(0, 0)] * x.ndim
    pad[1] = (0, n - x.shape[1])
    return jnp.pad(x.astype(jnp.float32), pad)


# ---------------------------------------------------------------------------
# Forward.
# ---------------------------------------------------------------------------
def flash_fwd_p(q, k, v, seeds, specs, *, scale, n_heads: int, n_kv: int,
                causal: bool = True, window: int = 0,
                q_block: int = _DEF_BLOCK, kv_block: int = _DEF_BLOCK,
                q_offset: int = 0, interpret=None):
    """Rounded flash-attention forward.

    q: (B·H, Sq, dk); k/v: (B·KV, Skv, dk/dv) float32; seeds: (B·H, 6)
    uint32 — the [qk | av | out] site word pairs.  Returns
    ``(out (B·H, Sq, dv), m (B·H, Sq), l (B·H, Sq))`` — m/l are the
    backward's softmax residuals.  ``q_offset`` shifts the global query
    positions (a prefill chunk starting mid-sequence).
    """
    if interpret is None:
        interpret = common.default_interpret()
    specs = AttnSpecs(*specs)
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    BH, Sq, dk = q.shape
    BKV, Skv, _ = k.shape
    dv = v.shape[-1]
    if n_heads % n_kv or BH % n_heads or BH // n_heads * n_kv != BKV:
        raise ValueError(f"bad GQA shapes: BH={BH} BKV={BKV} "
                         f"H={n_heads} KV={n_kv}")
    seeds = _check_seeds(seeds, BH, 6)
    qb, n_q = _blocks(Sq, q_block)
    kb, n_k = _blocks(Skv, kv_block)
    q_len = q_offset + Sq

    def idx_q(bh, i, j, *s):
        return (bh, i, 0)

    def idx_kv(bh, i, j, *s):
        return (_kv_of(bh, n_heads, n_kv), j, 0)

    def idx_ml(bh, i, j, *s):
        return (bh, 0, i)

    def kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
               acc_scr, m_scr, l_scr):
        bh, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

        @pl.when(j == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        draw = _kernel_draw(seed_ref, bh, (bh * n_q + i) * n_k + j, interpret)

        q0, k0 = q_offset + i * qb, j * kb
        valid = _position_mask((qb, kb), q0, k0, q_len=q_len, kv_len=Skv,
                               causal=causal, window=window)
        m_new, l_new, acc_new = _fwd_block(
            specs, scale, q_ref[0], k_ref[0], v_ref[0], valid, q0, k0,
            Skv, j, draw, m_scr[...], l_scr[...], acc_scr[...])
        m_scr[...] = m_new
        l_scr[...] = l_new
        acc_scr[...] = acc_new

        @pl.when(j == n_k - 1)
        def _emit():
            o_ref[0] = _fwd_finish(specs, acc_scr[...], l_scr[...], q0, draw)
            m_ref[...] = m_scr[...].reshape(1, qb)
            l_ref[...] = l_scr[...].reshape(1, qb)

    cost = pl.CostEstimate(
        flops=2 * BH * Sq * Skv * (dk + dv) + 6 * BH * Sq * Skv,
        transcendentals=2 * BH * Sq * Skv,
        bytes_accessed=4 * (BH * Sq * (dk + 2 * dv + 2)
                            + BKV * Skv * (dk + dv)))
    out, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(BH, n_q, n_k),
            in_specs=[pl.BlockSpec((1, qb, dk), idx_q),
                      pl.BlockSpec((1, kb, dk), idx_kv),
                      pl.BlockSpec((1, kb, dv), idx_kv)],
            out_specs=[pl.BlockSpec((1, qb, dv), idx_q),
                       pl.BlockSpec((None, 1, qb), idx_ml),
                       pl.BlockSpec((None, 1, qb), idx_ml)],
            scratch_shapes=[pltpu.VMEM((qb, dv), jnp.float32),
                            pltpu.VMEM((qb, 1), jnp.float32),
                            pltpu.VMEM((qb, 1), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((BH, Sq, dv), jnp.float32),
                   jax.ShapeDtypeStruct((BH, 1, Sq), jnp.float32),
                   jax.ShapeDtypeStruct((BH, 1, Sq), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS, vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=cost,
    )(seeds, q, k, v)
    return out, m.reshape(BH, Sq), l.reshape(BH, Sq)


def flash_fwd_reference(q, k, v, seeds, specs, *, scale, n_heads: int,
                        n_kv: int, causal: bool = True, window: int = 0,
                        q_block: int = _DEF_BLOCK,
                        kv_block: int = _DEF_BLOCK, q_offset: int = 0):
    """Pure-jnp replay of flash_fwd_p's blocked math on zero-padded
    operands — bit-identical to the interpret-mode kernel."""
    specs = AttnSpecs(*specs)
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    BH, Sq, dk = q.shape
    Skv, dv = k.shape[1], v.shape[-1]
    seeds = _check_seeds(seeds, BH, 6)
    qb, n_q = _blocks(Sq, q_block)
    kb, n_k = _blocks(Skv, kv_block)
    q_len = q_offset + Sq
    qp = _pad_rows(q, n_q * qb)
    kp, vp = _pad_rows(k, n_k * kb), _pad_rows(v, n_k * kb)
    outs, ms, ls = [], [], []
    for bh in range(BH):
        draw = _ref_draw(seeds[bh])
        kv = _kv_of(bh, n_heads, n_kv)
        o_r, m_r, l_r = [], [], []
        for i in range(n_q):
            m = jnp.full((qb, 1), -jnp.inf, jnp.float32)
            l = jnp.zeros((qb, 1), jnp.float32)
            acc = jnp.zeros((qb, dv), jnp.float32)
            q0 = q_offset + i * qb
            for j in range(n_k):
                k0 = j * kb
                valid = _position_mask((qb, kb), q0, k0, q_len=q_len,
                                       kv_len=Skv, causal=causal,
                                       window=window)
                m, l, acc = _fwd_block(
                    specs, scale, qp[bh, i * qb:(i + 1) * qb],
                    kp[kv, k0:k0 + kb], vp[kv, k0:k0 + kb], valid,
                    q0, k0, Skv, j, draw, m, l, acc)
            o_r.append(_fwd_finish(specs, acc, l, q0, draw))
            m_r.append(m[:, 0])
            l_r.append(l[:, 0])
        outs.append(jnp.concatenate(o_r)[:Sq])
        ms.append(jnp.concatenate(m_r)[:Sq])
        ls.append(jnp.concatenate(l_r)[:Sq])
    return jnp.stack(outs), jnp.stack(ms), jnp.stack(ls)


# ---------------------------------------------------------------------------
# Backward.
# ---------------------------------------------------------------------------
def flash_bwd_dq_p(q, k, v, do, m, l, d, seeds, spec_qk: RoundingSpec,
                   spec_dq: RoundingSpec, *, scale, n_heads: int,
                   n_kv: int, causal: bool = True, window: int = 0,
                   q_block: int = _DEF_BLOCK, kv_block: int = _DEF_BLOCK,
                   q_offset: int = 0, interpret=None):
    """dq backward kernel: grid (B·H, n_q, n_kv-blocks sequential).

    seeds: (B·H, 4) uint32 — [qk | dq] word pairs; the qk pair MUST be the
    forward's (the rounded-logit recompute), the dq pair rounds each
    kv-block's dq contribution on ``spec_dq`` (stream = kv-block index).
    ``d`` is the rowwise ``sum(do * out)`` residual, (B·H, Sq).
    """
    if interpret is None:
        interpret = common.default_interpret()
    q, k, v, do = (x.astype(jnp.float32) for x in (q, k, v, do))
    m, l, d = (x.astype(jnp.float32) for x in (m, l, d))
    BH, Sq, dk = q.shape
    Skv, dv = k.shape[1], v.shape[-1]
    seeds = _check_seeds(seeds, BH, 4)
    qb, n_q = _blocks(Sq, q_block)
    kb, n_k = _blocks(Skv, kv_block)
    q_len = q_offset + Sq

    def idx_q(bh, i, j, *s):
        return (bh, i, 0)

    def idx_kv(bh, i, j, *s):
        return (_kv_of(bh, n_heads, n_kv), j, 0)

    def idx_ml(bh, i, j, *s):
        return (bh, 0, i)

    def kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, d_ref,
               dq_ref, acc_scr):
        bh, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

        @pl.when(j == 0)
        def _init():
            acc_scr[...] = jnp.zeros_like(acc_scr)

        draw = _kernel_draw(seed_ref, bh, (bh * n_q + i) * n_k + j, interpret)

        q0, k0 = q_offset + i * qb, j * kb
        valid = _position_mask((qb, kb), q0, k0, q_len=q_len, kv_len=Skv,
                               causal=causal, window=window)
        k_blk = _zero_tail_rows(k_ref[0], k0, Skv)
        _, ds = _bwd_p_ds(spec_qk, scale, q_ref[0], k_blk, v_ref[0],
                          do_ref[0], m_ref[...].reshape(qb, 1),
                          l_ref[...].reshape(qb, 1),
                          d_ref[...].reshape(qb, 1), valid, q0, k0, draw)
        dq_c = jnp.dot(ds, k_blk, preferred_element_type=jnp.float32)
        bits = draw(SITE_BWD_A, dq_c.shape, q0, 0, j, spec_dq.rand_bits) \
            if spec_dq.stochastic else None
        acc_scr[...] += common.apply_spec_block(spec_dq, dq_c, bits)

        @pl.when(j == n_k - 1)
        def _emit():
            dq_ref[0] = acc_scr[...]

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(BH, n_q, n_k),
            in_specs=[pl.BlockSpec((1, qb, dk), idx_q),
                      pl.BlockSpec((1, kb, dk), idx_kv),
                      pl.BlockSpec((1, kb, dv), idx_kv),
                      pl.BlockSpec((1, qb, dv), idx_q),
                      pl.BlockSpec((None, 1, qb), idx_ml),
                      pl.BlockSpec((None, 1, qb), idx_ml),
                      pl.BlockSpec((None, 1, qb), idx_ml)],
            out_specs=pl.BlockSpec((1, qb, dk), idx_q),
            scratch_shapes=[pltpu.VMEM((qb, dk), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, dk), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS, vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=4 * BH * Sq * Skv * (dk + dv),
            transcendentals=BH * Sq * Skv,
            bytes_accessed=4 * (2 * BH * Sq * (dk + dv)
                                + BH * Skv * (dk + dv))),
    )(seeds, q, k, v, do, *_res_rows(m, l, d))


def flash_bwd_dkv_p(q, k, v, do, m, l, d, seeds, spec_qk: RoundingSpec,
                    spec_dk: RoundingSpec, spec_dv: RoundingSpec, *,
                    scale, n_heads: int, n_kv: int, causal: bool = True,
                    window: int = 0, q_block: int = _DEF_BLOCK,
                    kv_block: int = _DEF_BLOCK, q_offset: int = 0,
                    interpret=None):
    """dk/dv backward kernel: grid (B·H, n_kv-blocks, n_q sequential).

    seeds: (B·H, 6) uint32 — [qk | dk | dv] word pairs.  Outputs are *per
    query head*, (B·H, Skv, dk) and (B·H, Skv, dv); the GQA group-sum to
    kv heads happens outside (full precision, like every accumulate).
    """
    if interpret is None:
        interpret = common.default_interpret()
    q, k, v, do = (x.astype(jnp.float32) for x in (q, k, v, do))
    m, l, d = (x.astype(jnp.float32) for x in (m, l, d))
    BH, Sq, dk = q.shape
    Skv, dv = k.shape[1], v.shape[-1]
    seeds = _check_seeds(seeds, BH, 6)
    qb, n_q = _blocks(Sq, q_block)
    kb, n_k = _blocks(Skv, kv_block)
    q_len = q_offset + Sq

    def idx_q(bh, j, i, *s):
        return (bh, i, 0)

    def idx_kv(bh, j, i, *s):
        return (_kv_of(bh, n_heads, n_kv), j, 0)

    def idx_ml(bh, j, i, *s):
        return (bh, 0, i)

    def idx_out(bh, j, i, *s):
        return (bh, j, 0)

    def kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, d_ref,
               dk_ref, dv_ref, dk_scr, dv_scr):
        bh, j, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)

        @pl.when(i == 0)
        def _init():
            dk_scr[...] = jnp.zeros_like(dk_scr)
            dv_scr[...] = jnp.zeros_like(dv_scr)

        # the forward's block id for this (q block, kv block): the qk
        # recompute then redraws the forward's logit bits on the chip
        draw = _kernel_draw(seed_ref, bh, (bh * n_q + i) * n_k + j, interpret)

        q0, k0 = q_offset + i * qb, j * kb
        valid = _position_mask((qb, kb), q0, k0, q_len=q_len, kv_len=Skv,
                               causal=causal, window=window)
        q_blk = _zero_tail_rows(q_ref[0], q0, q_len)
        do_blk = _zero_tail_rows(do_ref[0], q0, q_len)
        p, ds = _bwd_p_ds(spec_qk, scale, q_blk, k_ref[0], v_ref[0],
                          do_blk, m_ref[...].reshape(qb, 1),
                          l_ref[...].reshape(qb, 1),
                          d_ref[...].reshape(qb, 1), valid, q0, k0, draw)
        dv_c = jnp.dot(p.T, do_blk, preferred_element_type=jnp.float32)
        bits = draw(SITE_BWD_B, dv_c.shape, k0, 0, i, spec_dv.rand_bits) \
            if spec_dv.stochastic else None
        dv_scr[...] += common.apply_spec_block(spec_dv, dv_c, bits)
        dk_c = jnp.dot(ds.T, q_blk, preferred_element_type=jnp.float32)
        bits = draw(SITE_BWD_A, dk_c.shape, k0, 0, i, spec_dk.rand_bits) \
            if spec_dk.stochastic else None
        dk_scr[...] += common.apply_spec_block(spec_dk, dk_c, bits)

        @pl.when(i == n_q - 1)
        def _emit():
            dk_ref[0] = dk_scr[...]
            dv_ref[0] = dv_scr[...]

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(BH, n_k, n_q),
            in_specs=[pl.BlockSpec((1, qb, dk), idx_q),
                      pl.BlockSpec((1, kb, dk), idx_kv),
                      pl.BlockSpec((1, kb, dv), idx_kv),
                      pl.BlockSpec((1, qb, dv), idx_q),
                      pl.BlockSpec((None, 1, qb), idx_ml),
                      pl.BlockSpec((None, 1, qb), idx_ml),
                      pl.BlockSpec((None, 1, qb), idx_ml)],
            out_specs=[pl.BlockSpec((1, kb, dk), idx_out),
                       pl.BlockSpec((1, kb, dv), idx_out)],
            scratch_shapes=[pltpu.VMEM((kb, dk), jnp.float32),
                            pltpu.VMEM((kb, dv), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((BH, Skv, dk), jnp.float32),
                   jax.ShapeDtypeStruct((BH, Skv, dv), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS, vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=6 * BH * Sq * Skv * (dk + dv),
            transcendentals=BH * Sq * Skv,
            bytes_accessed=4 * (2 * BH * Sq * (dk + dv)
                                + 2 * BH * Skv * (dk + dv))),
    )(seeds, q, k, v, do, *_res_rows(m, l, d))


def flash_bwd_dq_reference(q, k, v, do, m, l, d, seeds, spec_qk, spec_dq,
                           *, scale, n_heads: int, n_kv: int,
                           causal: bool = True, window: int = 0,
                           q_block: int = _DEF_BLOCK,
                           kv_block: int = _DEF_BLOCK, q_offset: int = 0):
    q, k, v, do = (x.astype(jnp.float32) for x in (q, k, v, do))
    m, l, d = (x.astype(jnp.float32) for x in (m, l, d))
    BH, Sq, dk = q.shape
    Skv = k.shape[1]
    seeds = _check_seeds(seeds, BH, 4)
    qb, n_q = _blocks(Sq, q_block)
    kb, n_k = _blocks(Skv, kv_block)
    q_len = q_offset + Sq
    qp, dop = _pad_rows(q, n_q * qb), _pad_rows(do, n_q * qb)
    kp, vp = _pad_rows(k, n_k * kb), _pad_rows(v, n_k * kb)
    mp, lp, dp_ = (_pad_rows(x[..., None], n_q * qb)[..., 0]
                   for x in (m, l, d))
    out = []
    for bh in range(BH):
        draw = _ref_draw(seeds[bh])
        kv = _kv_of(bh, n_heads, n_kv)
        rows = []
        for i in range(n_q):
            q0 = q_offset + i * qb
            sl = slice(i * qb, (i + 1) * qb)
            acc = jnp.zeros((qb, dk), jnp.float32)
            for j in range(n_k):
                k0 = j * kb
                valid = _position_mask((qb, kb), q0, k0, q_len=q_len,
                                       kv_len=Skv, causal=causal,
                                       window=window)
                k_blk = _zero_tail_rows(kp[kv, k0:k0 + kb], k0, Skv)
                _, ds = _bwd_p_ds(spec_qk, scale, qp[bh, sl], k_blk,
                                  vp[kv, k0:k0 + kb], dop[bh, sl],
                                  mp[bh, sl, None], lp[bh, sl, None],
                                  dp_[bh, sl, None], valid, q0, k0, draw)
                dq_c = jnp.dot(ds, k_blk, preferred_element_type=jnp.float32)
                bits = draw(SITE_BWD_A, dq_c.shape, q0, 0, j,
                            spec_dq.rand_bits) if spec_dq.stochastic else None
                acc = acc + common.apply_spec_block(spec_dq, dq_c, bits)
            rows.append(acc)
        out.append(jnp.concatenate(rows)[:Sq])
    return jnp.stack(out)


def flash_bwd_dkv_reference(q, k, v, do, m, l, d, seeds, spec_qk, spec_dk,
                            spec_dv, *, scale, n_heads: int, n_kv: int,
                            causal: bool = True, window: int = 0,
                            q_block: int = _DEF_BLOCK,
                            kv_block: int = _DEF_BLOCK, q_offset: int = 0):
    q, k, v, do = (x.astype(jnp.float32) for x in (q, k, v, do))
    m, l, d = (x.astype(jnp.float32) for x in (m, l, d))
    BH, Sq, dk = q.shape
    Skv, dv = k.shape[1], v.shape[-1]
    seeds = _check_seeds(seeds, BH, 6)
    qb, n_q = _blocks(Sq, q_block)
    kb, n_k = _blocks(Skv, kv_block)
    q_len = q_offset + Sq
    qp, dop = _pad_rows(q, n_q * qb), _pad_rows(do, n_q * qb)
    kp, vp = _pad_rows(k, n_k * kb), _pad_rows(v, n_k * kb)
    mp, lp, dp_ = (_pad_rows(x[..., None], n_q * qb)[..., 0]
                   for x in (m, l, d))
    dks, dvs = [], []
    for bh in range(BH):
        draw = _ref_draw(seeds[bh])
        kv = _kv_of(bh, n_heads, n_kv)
        k_rows, v_rows = [], []
        for j in range(n_k):
            k0 = j * kb
            acc_dk = jnp.zeros((kb, dk), jnp.float32)
            acc_dv = jnp.zeros((kb, dv), jnp.float32)
            for i in range(n_q):
                q0 = q_offset + i * qb
                sl = slice(i * qb, (i + 1) * qb)
                valid = _position_mask((qb, kb), q0, k0, q_len=q_len,
                                       kv_len=Skv, causal=causal,
                                       window=window)
                q_blk = _zero_tail_rows(qp[bh, sl], q0, q_len)
                do_blk = _zero_tail_rows(dop[bh, sl], q0, q_len)
                p, ds = _bwd_p_ds(spec_qk, scale, q_blk, kp[kv, k0:k0 + kb],
                                  vp[kv, k0:k0 + kb], do_blk,
                                  mp[bh, sl, None], lp[bh, sl, None],
                                  dp_[bh, sl, None], valid, q0, k0, draw)
                dv_c = jnp.dot(p.T, do_blk,
                               preferred_element_type=jnp.float32)
                bits = draw(SITE_BWD_B, dv_c.shape, k0, 0, i,
                            spec_dv.rand_bits) if spec_dv.stochastic else None
                acc_dv = acc_dv + common.apply_spec_block(spec_dv, dv_c, bits)
                dk_c = jnp.dot(ds.T, q_blk,
                               preferred_element_type=jnp.float32)
                bits = draw(SITE_BWD_A, dk_c.shape, k0, 0, i,
                            spec_dk.rand_bits) if spec_dk.stochastic else None
                acc_dk = acc_dk + common.apply_spec_block(spec_dk, dk_c, bits)
            k_rows.append(acc_dk)
            v_rows.append(acc_dv)
        dks.append(jnp.concatenate(k_rows)[:Skv])
        dvs.append(jnp.concatenate(v_rows)[:Skv])
    return jnp.stack(dks), jnp.stack(dvs)


# ---------------------------------------------------------------------------
# Single-token decode (packed or float KV cache).
# ---------------------------------------------------------------------------
def flash_decode_p(q, k, v, seeds, length, specs, *, scale,
                   window: int = 0, kv_block: int = _DEF_BLOCK,
                   kv_fmt=None, interpret=None):
    """Rounded decode step over the whole KV cache of one new token.

    q: (B·KV, G, dk) — the G query heads of each kv group side by side;
    k/v: (B·KV, S_max, dk/dv), float values or, with ``kv_fmt``, packed
    code words of that grid (decoded on load in-kernel).  ``length`` is
    the number of valid cache entries *including* the token being decoded
    (traced OK — it rides scalar prefetch).  Returns (B·KV, G, dv) f32.
    """
    if interpret is None:
        interpret = common.default_interpret()
    specs = AttnSpecs(*specs)
    q = q.astype(jnp.float32)
    if kv_fmt is None:
        k, v = k.astype(jnp.float32), v.astype(jnp.float32)
    BKV, G, dk = q.shape
    Smax = k.shape[1]
    dv = v.shape[-1]
    seeds = _check_seeds(seeds, BKV, 6)
    lens = jnp.asarray(length, jnp.int32).reshape(-1)[:1]
    kb, n_k = _blocks(Smax, kv_block)

    def idx_q(b, j, *s):
        return (b, 0, 0)

    def idx_kv(b, j, *s):
        return (b, j, 0)

    def kernel(seed_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
               acc_scr, m_scr, l_scr):
        b, j = pl.program_id(0), pl.program_id(1)
        length = len_ref[0]

        @pl.when(j == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        draw = _kernel_draw(seed_ref, b, b * n_k + j, interpret)

        k_blk, v_blk = k_ref[0], v_ref[0]
        if kv_fmt is not None:
            k_blk = common.unpack_block(k_blk, kv_fmt)
            v_blk = common.unpack_block(v_blk, kv_fmt)
        k0 = j * kb
        valid = _decode_mask((G, kb), k0, length, window)
        m_new, l_new, acc_new = _fwd_block(
            specs, scale, q_ref[0], k_blk, v_blk, valid, 0, k0,
            length, j, draw, m_scr[...], l_scr[...], acc_scr[...])
        m_scr[...] = m_new
        l_scr[...] = l_new
        acc_scr[...] = acc_new

        @pl.when(j == n_k - 1)
        def _emit():
            o_ref[0] = _fwd_finish(specs, acc_scr[...], l_scr[...], 0, draw)

    kv_bytes = common.pack_bytes(kv_fmt) if kv_fmt is not None else 4
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(BKV, n_k),
            in_specs=[pl.BlockSpec((1, G, dk), idx_q),
                      pl.BlockSpec((1, kb, dk), idx_kv),
                      pl.BlockSpec((1, kb, dv), idx_kv)],
            out_specs=pl.BlockSpec((1, G, dv), idx_q),
            scratch_shapes=[pltpu.VMEM((G, dv), jnp.float32),
                            pltpu.VMEM((G, 1), jnp.float32),
                            pltpu.VMEM((G, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((BKV, G, dv), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * BKV * G * Smax * (dk + dv),
            transcendentals=BKV * G * Smax,
            bytes_accessed=(4 * BKV * G * (dk + dv)
                            + kv_bytes * BKV * Smax * (dk + dv))),
    )(seeds, lens, q, k, v)


def flash_decode_paged_p(q, k_pages, v_pages, seeds, lengths, tables, specs,
                         *, scale, n_kv: int, window: int = 0, kv_fmt=None,
                         interpret=None):
    """Rounded decode step over a *paged* (possibly packed) KV cache.

    q: (B·KV, G, dk) — the G query heads of each kv group side by side;
    k_pages/v_pages: (P·KV, page, dk/dv) physical pages — page ``p`` of kv
    head ``h`` lives at row ``p·KV + h`` (the serving layer's
    ``(P, KV, page, d)`` pool reshaped), float values or, with ``kv_fmt``,
    packed code words decoded on load in-kernel; lengths: (B,) int32 valid
    rows per request *including* the token being decoded; tables:
    (B, n_max) int32 logical→physical page ids (both ride scalar prefetch,
    so the index map DMAs exactly the request's pages — the vLLM paged-
    attention pattern).  Table entries past a request's allocation must
    point at *some* valid page (the allocator's scratch page 0): their
    logical positions are ≥ length, so they are fully masked and — because
    a fully-masked block contributes exactly 0 to the online softmax and
    ``corr == 1`` — bit-neutral.  Hence with ``page == kv_block`` the
    result is bit-identical to :func:`flash_decode_p` on the contiguously
    gathered cache, regardless of the physical page placement.

    Randomness discipline: draws are keyed by the *logical* kv-block index
    (stream = logical page, col0 = logical position), never the physical
    page id, so a request's rounding stream is placement-invariant.
    Returns (B·KV, G, dv) float32.
    """
    if interpret is None:
        interpret = common.default_interpret()
    specs = AttnSpecs(*specs)
    q = q.astype(jnp.float32)
    if kv_fmt is None:
        k_pages = k_pages.astype(jnp.float32)
        v_pages = v_pages.astype(jnp.float32)
    BKV, G, dk = q.shape
    page = k_pages.shape[1]
    dv = v_pages.shape[-1]
    if BKV % n_kv or k_pages.shape[0] % n_kv:
        raise ValueError(f"BKV={BKV} / P·KV={k_pages.shape[0]} not "
                         f"multiples of n_kv={n_kv}")
    seeds = _check_seeds(seeds, BKV, 6)
    B = BKV // n_kv
    lens = jnp.asarray(lengths, jnp.int32).reshape(-1)
    if lens.shape != (B,):
        raise ValueError(f"lengths must be ({B},), got {lens.shape}")
    tables = jnp.asarray(tables, jnp.int32)
    if tables.ndim != 2 or tables.shape[0] != B:
        raise ValueError(f"tables must be ({B}, n_max), got {tables.shape}")
    n_max = tables.shape[1]

    def idx_q(b, j, *s):
        return (b, 0, 0)

    def idx_kv(b, j, seed_ref, len_ref, tbl_ref):
        return (tbl_ref[b // n_kv, j] * n_kv + b % n_kv, 0, 0)

    def kernel(seed_ref, len_ref, tbl_ref, q_ref, k_ref, v_ref, o_ref,
               acc_scr, m_scr, l_scr):
        b, j = pl.program_id(0), pl.program_id(1)
        length = len_ref[b // n_kv]

        @pl.when(j == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        # logical coordinates only (logical page, kv head): the row's words
        # already key (request, position, kv head), and the batch slot and
        # table width never enter — the engine's determinism contract
        draw = _kernel_draw(seed_ref, b, j * n_kv + b % n_kv, interpret)

        k_blk, v_blk = k_ref[0], v_ref[0]
        if kv_fmt is not None:
            k_blk = common.unpack_block(k_blk, kv_fmt)
            v_blk = common.unpack_block(v_blk, kv_fmt)
        k0 = j * page                       # logical position of the block
        valid = _decode_mask((G, page), k0, length, window)
        m_new, l_new, acc_new = _fwd_block(
            specs, scale, q_ref[0], k_blk, v_blk, valid, 0, k0,
            length, j, draw, m_scr[...], l_scr[...], acc_scr[...])
        m_scr[...] = m_new
        l_scr[...] = l_new
        acc_scr[...] = acc_new

        @pl.when(j == n_max - 1)
        def _emit():
            o_ref[0] = _fwd_finish(specs, acc_scr[...], l_scr[...], 0, draw)

    kv_bytes = common.pack_bytes(kv_fmt) if kv_fmt is not None else 4
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(BKV, n_max),
            in_specs=[pl.BlockSpec((1, G, dk), idx_q),
                      pl.BlockSpec((1, page, dk), idx_kv),
                      pl.BlockSpec((1, page, dv), idx_kv)],
            out_specs=pl.BlockSpec((1, G, dv), idx_q),
            scratch_shapes=[pltpu.VMEM((G, dv), jnp.float32),
                            pltpu.VMEM((G, 1), jnp.float32),
                            pltpu.VMEM((G, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((BKV, G, dv), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * BKV * G * n_max * page * (dk + dv),
            transcendentals=BKV * G * n_max * page,
            bytes_accessed=(4 * BKV * G * (dk + dv)
                            + kv_bytes * BKV * n_max * page * (dk + dv))),
    )(seeds, lens, tables, q, k_pages, v_pages)


def flash_decode_paged_reference(q, k_pages, v_pages, seeds, lengths,
                                 tables, specs, *, scale, n_kv: int,
                                 window: int = 0, kv_fmt=None):
    """Pure-jnp replay of flash_decode_paged_p (bit-identical under
    interpret): gathers each request's logical block sequence from the
    page pool and replays the identical blocked online-softmax math."""
    specs = AttnSpecs(*specs)
    q = q.astype(jnp.float32)
    if kv_fmt is not None:
        k_pages = common.unpack_block(k_pages, kv_fmt)
        v_pages = common.unpack_block(v_pages, kv_fmt)
    k_pages = k_pages.astype(jnp.float32)
    v_pages = v_pages.astype(jnp.float32)
    BKV, G, dk = q.shape
    page = k_pages.shape[1]
    dv = v_pages.shape[-1]
    B = BKV // n_kv
    seeds = _check_seeds(seeds, BKV, 6)
    lens = jnp.asarray(lengths, jnp.int32).reshape(B)
    tables = jnp.asarray(tables, jnp.int32)
    n_max = tables.shape[1]
    outs = []
    for b in range(BKV):
        draw = _ref_draw(seeds[b])
        length = lens[b // n_kv]
        m = jnp.full((G, 1), -jnp.inf, jnp.float32)
        l = jnp.zeros((G, 1), jnp.float32)
        acc = jnp.zeros((G, dv), jnp.float32)
        for j in range(n_max):
            row = tables[b // n_kv, j] * n_kv + b % n_kv
            k0 = j * page
            valid = _decode_mask((G, page), k0, length, window)
            m, l, acc = _fwd_block(
                specs, scale, q[b], k_pages[row], v_pages[row],
                valid, 0, k0, length, j, draw, m, l, acc)
        outs.append(_fwd_finish(specs, acc, l, 0, draw))
    return jnp.stack(outs)


def flash_decode_reference(q, k, v, seeds, length, specs, *, scale,
                           window: int = 0, kv_block: int = _DEF_BLOCK,
                           kv_fmt=None):
    """Pure-jnp replay of flash_decode_p (bit-identical under interpret)."""
    specs = AttnSpecs(*specs)
    q = q.astype(jnp.float32)
    if kv_fmt is not None:
        k = common.unpack_block(k, kv_fmt)
        v = common.unpack_block(v, kv_fmt)
    k, v = k.astype(jnp.float32), v.astype(jnp.float32)
    BKV, G, dk = q.shape
    Smax, dv = k.shape[1], v.shape[-1]
    seeds = _check_seeds(seeds, BKV, 6)
    length = jnp.asarray(length, jnp.int32).reshape(-1)[0]
    kb, n_k = _blocks(Smax, kv_block)
    kp, vp = _pad_rows(k, n_k * kb), _pad_rows(v, n_k * kb)
    outs = []
    for b in range(BKV):
        draw = _ref_draw(seeds[b])
        m = jnp.full((G, 1), -jnp.inf, jnp.float32)
        l = jnp.zeros((G, 1), jnp.float32)
        acc = jnp.zeros((G, dv), jnp.float32)
        for j in range(n_k):
            k0 = j * kb
            valid = _decode_mask((G, kb), k0, length, window)
            m, l, acc = _fwd_block(
                specs, scale, q[b], kp[b, k0:k0 + kb], vp[b, k0:k0 + kb],
                valid, 0, k0, length, j, draw, m, l, acc)
        outs.append(_fwd_finish(specs, acc, l, 0, draw))
    return jnp.stack(outs)
