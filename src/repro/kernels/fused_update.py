"""Pallas TPU kernel: the fused three-step rounded GD update (paper eq. 8).

Computes, in a single HBM pass over the parameters:

    ĝ   = Q₁(g)            (8a residual rounding of the computed gradient)
    upd = Q₂(t · ĝ)        (8b)
    x⁺  = Q₃(x − upd)      (8c, signed-SRε biased by sign(ĝ))

Unfused, this chain is ≥ 5 elementwise XLA ops → ≥ 7 HBM streams over the
parameter size; fused it is x, g, (3×) bits in + x⁺ out (24 B/elt); with
the in-kernel PRNG (``fused_qupdate_prng_p``) the bits streams vanish and
it is x, g in + x⁺ out — 12 B/elt, the roofline bound.  This is the hot op
of the paper's method at framework scale: it touches every parameter on
every optimizer step and is purely memory-bound, so the fusion ratio is the
roofline lever (see EXPERIMENTS.md §Perf).

The stepsize arrives via scalar prefetch (SMEM); rounding configs are static.

Numerical note: when a step's RoundingSpec is the *identity* (fp32
baseline), XLA may contract the ``t·g`` multiply into an FMA with the
subtraction, giving a result that can differ from the two-op eager
evaluation by one fp32 ulp (the FMA is the more accurate of the two).  Any
*quantized* step is immune: the rounding bit-ops materialize the
intermediate exactly, so kernel == oracle bit-for-bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.gd import GDRounding
from repro.kernels import common
from repro.kernels.sr_cast import LANES, _pad_2d, pick_block_rows


def _resolve_v_static(source: str, g_hat, x):
    if source == "grad":
        return g_hat
    if source == "neg_grad":
        return -g_hat
    if source == "self":
        return None
    raise ValueError(f"unknown v_source {source!r}")


def _update_chain(cfg: GDRounding, x, g, t, b1, b2, b3):
    """The eq.-8 three-step rounded chain on one block — shared by the
    explicit-bits and PRNG kernel bodies so the two paths cannot diverge."""
    g_hat = common.apply_spec_block(
        cfg.grad, g, b1, v=_resolve_v_static(cfg.grad_v, g, x))
    upd = common.apply_spec_block(
        cfg.mul, t * g_hat, b2, v=_resolve_v_static(cfg.mul_v, g_hat, x))
    z = x - upd
    return common.apply_spec_block(
        cfg.sub, z, b3, v=_resolve_v_static(cfg.sub_v, g_hat, x))


def _fused_update_kernel(t_ref, x_ref, g_ref, b1_ref, b2_ref, b3_ref, o_ref,
                         *, cfg: GDRounding):
    o_ref[...] = _update_chain(cfg, x_ref[...], g_ref[...], t_ref[0],
                               b1_ref[...], b2_ref[...], b3_ref[...])


def fused_qupdate_p(x, g, t, bits3, cfg: GDRounding,
                    *, block_rows=None, interpret=None):
    """Fused rounded GD update.

    Args:
      x: parameters, float32 (any shape).
      g: gradient, same shape.
      t: scalar stepsize.
      bits3: uint32 (3, *x.shape) random bits for the three rounding steps
        (rows unused by deterministic/identity steps are simply ignored).
      cfg: the three-step rounding policy.

    Returns float32 array of updated parameters (on the cfg.sub grid).
    """
    if interpret is None:
        interpret = common.default_interpret()
    block_rows = pick_block_rows(x.size, interpret, block_rows)
    shape = x.shape
    xf, rows = _pad_2d(x.reshape(-1), block_rows)
    gf, _ = _pad_2d(g.reshape(-1), block_rows)
    b1, _ = _pad_2d(bits3[0].reshape(-1), block_rows)
    b2, _ = _pad_2d(bits3[1].reshape(-1), block_rows)
    b3, _ = _pad_2d(bits3[2].reshape(-1), block_rows)
    grid = (rows // block_rows,)
    bspec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))

    t_arr = jnp.asarray([t], jnp.float32)
    kern = functools.partial(_fused_update_kernel, cfg=cfg)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  bspec, bspec, bspec, bspec, bspec],
        out_specs=bspec,
        out_shape=jax.ShapeDtypeStruct(xf.shape, jnp.float32),
        name="fused_qupdate",
        interpret=interpret,
    )(t_arr, xf, gf, b1, b2, b3)
    return out.reshape(-1)[: x.size].reshape(shape)


# ---------------------------------------------------------------------------
# In-kernel PRNG variant: x, g in + x⁺ out — 12 B/elt, the roofline bound.
# ---------------------------------------------------------------------------
def _fused_update_prng_kernel(seed_ref, t_ref, x_ref, g_ref, o_ref,
                              *, cfg: GDRounding, block_rows, interpret):
    i = pl.program_id(0)
    common.seed_kernel_prng(seed_ref, i, interpret=interpret)
    b1, b2, b3 = common.kernel_bits3(
        seed_ref, x_ref.shape, i * block_rows,
        (cfg.grad.stochastic, cfg.mul.stochastic, cfg.sub.stochastic),
        interpret=interpret)
    o_ref[...] = _update_chain(cfg, x_ref[...], g_ref[...], t_ref[0],
                               b1, b2, b3)


def fused_qupdate_prng_p(x, g, t, seed, cfg: GDRounding,
                         *, block_rows=None, interpret=None):
    """Fused rounded GD update with in-kernel randomness.

    Same math as ``fused_qupdate_p`` but the three bits streams are
    generated inside the kernel (hardware PRNG on TPU, counter-hash under
    interpret), so HBM traffic drops from 24 to 12 B/elt.  ``seed``: (2,)
    uint32 words (common.derive_seed), delivered via SMEM scalar prefetch;
    the per-block seed is (words, block index).
    """
    if interpret is None:
        interpret = common.default_interpret()
    block_rows = pick_block_rows(x.size, interpret, block_rows)
    shape = x.shape
    xf, rows = _pad_2d(x.reshape(-1), block_rows)
    gf, _ = _pad_2d(g.reshape(-1), block_rows)
    grid = (rows // block_rows,)
    bspec = pl.BlockSpec((block_rows, LANES), lambda i, s: (i, 0))
    seed = jnp.asarray(seed, jnp.uint32).reshape(2)

    t_arr = jnp.asarray([t], jnp.float32)
    kern = functools.partial(_fused_update_prng_kernel, cfg=cfg,
                             block_rows=block_rows, interpret=interpret)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      bspec, bspec],
            out_specs=bspec,
        ),
        out_shape=jax.ShapeDtypeStruct(xf.shape, jnp.float32),
        name="fused_update",
        interpret=interpret,
    )(seed, t_arr, xf, gf)
    return out.reshape(-1)[: x.size].reshape(shape)


# ---------------------------------------------------------------------------
# Fully-fused QAdam step: rounded m/v moment EMAs (optionally packed to
# uint8/uint16 grid codes, optionally Kahan-compensated), bias-corrected
# direction and the eq.-8 chain in ONE HBM pass.  Traffic with bf16-packed
# moments: x,g (8) + m,v codes in (4) + x⁺ (4) + m,v codes out (4) =
# 20 B/elt, vs 28 for fp32 moments in the same kernel and ~48 for the
# legacy jnp-moments + fused-chain step (see benchmarks/kernel_bench.py).
# ---------------------------------------------------------------------------
# Interpret-mode PRF streams for the moment draws.  The eq.-8 chain's
# kernel_bits3 consumes pair streams 0/1; the moment sites draw from
# distinct stream offsets so their words never collide with the chain's.
STREAM_MOMENT_M = 8
STREAM_MOMENT_V = 9


def _moment_ema(spec, m, a, beta: float, bits, comp):
    """One rounded EMA carry: ``m' = Q(beta·m + (1-beta)·a)`` on ``spec``'s
    grid.  With ``comp`` (Kahan) the update is accumulated as
    ``m + ((1-beta)(a-m) - comp)`` and the new carry ``(m'-m) - y`` is
    returned — same compensation algebra as optim/accumulate.py, so the
    carry tracks the fp32 EMA to ulps even on bf16-rn."""
    if comp is None:
        return common.apply_spec_block(spec, beta * m + (1.0 - beta) * a,
                                       bits), None
    y = (1.0 - beta) * (a - m) - comp
    s = common.apply_spec_block(spec, m + y, bits)
    return s, (s - m) - y


def _fused_adam_prng_kernel(seed_ref, s_ref, x_ref, g_ref, m_ref, v_ref,
                            *refs, cfg: GDRounding, m_spec, v_spec,
                            b1, b2, packed, kahan, block_rows, interpret):
    if kahan:
        cm_ref, cv_ref, ox_ref, om_ref, ov_ref, ocm_ref, ocv_ref = refs
    else:
        ox_ref, om_ref, ov_ref = refs
    i = pl.program_id(0)
    common.seed_kernel_prng(seed_ref, i, interpret=interpret)
    row0 = i * block_rows
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    m = common.unpack_block(m_ref[...], m_spec.fmt) if packed else m_ref[...]
    v = common.unpack_block(v_ref[...], v_spec.fmt) if packed else v_ref[...]
    t, c1, c2, eps, wd = (s_ref[0], s_ref[1], s_ref[2], s_ref[3], s_ref[4])

    bm = (common.kernel_bits(seed_ref, x.shape, row0=row0,
                             stream=STREAM_MOMENT_M,
                             rand_bits=m_spec.rand_bits, interpret=interpret)
          if m_spec.stochastic else None)
    bv = (common.kernel_bits(seed_ref, x.shape, row0=row0,
                             stream=STREAM_MOMENT_V,
                             rand_bits=v_spec.rand_bits, interpret=interpret)
          if v_spec.stochastic else None)
    m_new, cm_new = _moment_ema(m_spec, m, g, b1, bm,
                                cm_ref[...] if kahan else None)
    v_new, cv_new = _moment_ema(v_spec, v, g * g, b2, bv,
                                cv_ref[...] if kahan else None)

    # bias-corrected Adam direction (same op order as optim/adam.py's jnp
    # path) + decoupled weight decay, then the eq.-8 rounded chain on it
    d = (m_new / c1) / (jnp.sqrt(v_new / c2) + eps) + wd * x
    bc1, bc2, bc3 = common.kernel_bits3(
        seed_ref, x.shape, row0,
        (cfg.grad.stochastic, cfg.mul.stochastic, cfg.sub.stochastic),
        interpret=interpret)
    ox_ref[...] = _update_chain(cfg, x, d, t, bc1, bc2, bc3)
    om_ref[...] = common.pack_block(m_new, m_spec.fmt) if packed else m_new
    ov_ref[...] = common.pack_block(v_new, v_spec.fmt) if packed else v_new
    if kahan:
        ocm_ref[...] = cm_new
        ocv_ref[...] = cv_new


def fused_qadam_prng_p(x, g, m, v, scal, seed, cfg: GDRounding,
                       *, m_spec, v_spec, b1: float, b2: float,
                       packed: bool, cm=None, cv=None,
                       block_rows=None, interpret=None):
    """Fully-fused QAdam step with in-kernel randomness.

    Args:
      x, g: flat float32 parameter / gradient vectors (same size).
      m, v: flat moment carries — float32, or packed grid codes
        (uint8/uint16 per ``common.pack_dtype``) when ``packed``.
      scal: (5,) float32 ``[t, c1, c2, eps, weight_decay]`` — the traced
        stepsize and bias corrections ride in SMEM so step-dependent
        values never retrace the kernel.
      seed: (2,) uint32 words (common.derive_seed).
      cfg: the eq.-8 three-step policy applied to the Adam direction.
      m_spec/v_spec: RoundingSpec for each moment carry (identity = fp32).
      cm/cv: float32 Kahan compensation carries (enables the compensated
        EMA when given — both or neither).

    Returns ``(x⁺, m', v')`` or ``(x⁺, m', v', cm', cv')``, flat, with
    moments in the same representation they arrived in.
    """
    if interpret is None:
        interpret = common.default_interpret()
    kahan = cm is not None
    if kahan != (cv is not None):
        raise ValueError("Kahan compensation needs both cm and cv")
    if packed and (m_spec.is_identity or v_spec.is_identity):
        raise ValueError("packed moments require non-identity m/v specs")
    block_rows = pick_block_rows(x.size, interpret, block_rows)
    n = x.size
    xf, rows = _pad_2d(x.reshape(-1), block_rows)
    gf, _ = _pad_2d(g.reshape(-1), block_rows)
    mf, _ = _pad_2d(m.reshape(-1), block_rows)
    vf, _ = _pad_2d(v.reshape(-1), block_rows)
    grid = (rows // block_rows,)
    bspec = pl.BlockSpec((block_rows, LANES), lambda i, s: (i, 0))
    seed = jnp.asarray(seed, jnp.uint32).reshape(2)
    scal = jnp.asarray(scal, jnp.float32).reshape(5)

    operands = [xf, gf, mf, vf]
    out_shape = [jax.ShapeDtypeStruct(xf.shape, jnp.float32),
                 jax.ShapeDtypeStruct(xf.shape, mf.dtype),
                 jax.ShapeDtypeStruct(xf.shape, vf.dtype)]
    if kahan:
        cmf, _ = _pad_2d(cm.reshape(-1), block_rows)
        cvf, _ = _pad_2d(cv.reshape(-1), block_rows)
        operands += [cmf, cvf]
        out_shape += [jax.ShapeDtypeStruct(xf.shape, jnp.float32),
                      jax.ShapeDtypeStruct(xf.shape, jnp.float32)]
    kern = functools.partial(
        _fused_adam_prng_kernel, cfg=cfg, m_spec=m_spec, v_spec=v_spec,
        b1=float(b1), b2=float(b2), packed=packed, kahan=kahan,
        block_rows=block_rows, interpret=interpret)
    outs = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
            + [bspec] * len(operands),
            out_specs=[bspec] * len(out_shape),
        ),
        out_shape=out_shape,
        name="fused_qadam",
        interpret=interpret,
    )(seed, scal, *operands)
    return tuple(o.reshape(-1)[:n] for o in outs)
