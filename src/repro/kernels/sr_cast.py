"""Pallas TPU kernel: stochastic-rounding cast (the paper's fl(·) operator).

Elementwise, memory-bound.  The wrapper flattens/pads the operand onto a
(rows, 128)-lane layout and tiles rows into VMEM blocks; each grid step
reads one block of values + one block of random bits and writes one rounded
block.  Roofline: 3 HBM streams (x, bits, out) = 12 bytes/element, vs 8 for
a plain cast — the bits stream is the price of *explicit* randomness.
``sr_cast_prng_p`` deletes that stream by generating bits *in-kernel*
(hardware PRNG on TPU, counter-hash under interpret; kernels/common.py),
hitting the 8 bytes/element plain-cast bound (EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.grids import get_grid
from repro.core.rounding import get_scheme
from repro.kernels import common

LANES = 128
DEFAULT_BLOCK_ROWS = 512    # 512x128 f32 = 256 KiB/operand block in VMEM
MAX_INTERPRET_ROWS = 32768  # interpret has no VMEM: fewer, bigger blocks


def pick_block_rows(n_elements: int, interpret: bool,
                    block_rows=None) -> int:
    """Resolve the block height.  On real TPU the default keeps the working
    set in VMEM; under interpret (no VMEM, per-grid-step emulator overhead
    dominates) we cover the array in as few blocks as possible.

    Partition-invariance caveat: explicit-bits results never depend on the
    block partition (bits are operands), and interpret-mode PRNG bits are
    keyed by *global* coordinates, so there this is purely a wall-clock
    knob.  On real TPU, however, the hardware PRNG is seeded per block
    index — PRNG-mode results are deterministic in (seed, block_rows,
    backend), NOT across different block_rows choices.
    """
    if block_rows is not None:
        return block_rows
    if not interpret:
        return DEFAULT_BLOCK_ROWS
    rows = -(-max(n_elements, 1) // LANES)
    rows = -(-rows // 8) * 8
    return max(8, min(rows, MAX_INTERPRET_ROWS))


def _sr_cast_kernel(x_ref, bits_ref, o_ref, *, fmt, mode, eps, rand_bits,
                    overflow):
    o_ref[...] = common.round_block(x_ref[...], bits_ref[...], fmt, mode, eps,
                                    rand_bits=rand_bits, overflow=overflow)


def _signed_sr_cast_kernel(x_ref, bits_ref, v_ref, o_ref, *, fmt, mode, eps,
                           overflow):
    o_ref[...] = common.round_block(
        x_ref[...], bits_ref[...], fmt, mode, eps, v=v_ref[...],
        overflow=overflow)


def _pad_2d(flat, block_rows):
    n = flat.shape[0]
    rows = -(-n // LANES)
    rows_padded = -(-rows // block_rows) * block_rows
    padded = jnp.zeros((rows_padded * LANES,), flat.dtype).at[:n].set(flat)
    return padded.reshape(rows_padded, LANES), rows_padded


def sr_cast_p(x, bits, fmt, mode: str, eps: float = 0.0, v=None,
              *, block_rows=None, rand_bits: int = 32,
              overflow: str = "saturate", interpret=None):
    """Stochastic-round ``x`` onto ``fmt`` with a Pallas kernel.

    x: float32 array (any shape); bits: uint32, same shape (with
    ``rand_bits < 32`` only the low bits are consumed); v: bias
    direction (same shape) — required iff the scheme ``needs_v``
    (signed-SRε).  ``fmt`` may be any registered grid (fp or fxp);
    ``mode`` any registered scheme (sr2's comparison draw included).
    """
    fmt = get_grid(fmt)
    if interpret is None:
        interpret = common.default_interpret()
    block_rows = pick_block_rows(x.size, interpret, block_rows)
    shape = x.shape
    xf, rows = _pad_2d(x.reshape(-1), block_rows)
    bitsf, _ = _pad_2d(bits.reshape(-1), block_rows)
    grid = (rows // block_rows,)
    bspec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))

    if get_scheme(mode).needs_v:
        if v is None:
            raise ValueError(f"{mode} requires v")
        vf, _ = _pad_2d(jnp.broadcast_to(v, shape).reshape(-1), block_rows)
        kern = functools.partial(_signed_sr_cast_kernel, fmt=fmt, mode=mode,
                                 eps=eps, overflow=overflow)
        out = pl.pallas_call(
            kern,
            grid=grid,
            in_specs=[bspec, bspec, bspec],
            out_specs=bspec,
            out_shape=jax.ShapeDtypeStruct(xf.shape, jnp.float32),
            name="sr_cast",
            interpret=interpret,
        )(xf, bitsf, vf)
    else:
        kern = functools.partial(_sr_cast_kernel, fmt=fmt, mode=mode, eps=eps,
                                 rand_bits=rand_bits, overflow=overflow)
        out = pl.pallas_call(
            kern,
            grid=grid,
            in_specs=[bspec, bspec],
            out_specs=bspec,
            out_shape=jax.ShapeDtypeStruct(xf.shape, jnp.float32),
            name="sr_cast",
            interpret=interpret,
        )(xf, bitsf)
    return out.reshape(-1)[: x.size].reshape(shape)


# ---------------------------------------------------------------------------
# In-kernel PRNG variant: no bits operand (8 B/elt instead of 12).
# ---------------------------------------------------------------------------
def _sr_cast_prng_kernel(seed_ref, x_ref, o_ref,
                         *, fmt, mode, eps, block_rows, rand_bits,
                         overflow, interpret):
    i = pl.program_id(0)
    common.seed_kernel_prng(seed_ref, i, interpret=interpret)
    bits = common.kernel_bits(seed_ref, x_ref.shape,
                              row0=i * block_rows, rand_bits=rand_bits,
                              interpret=interpret)
    o_ref[...] = common.round_block(x_ref[...], bits, fmt, mode, eps,
                                    rand_bits=rand_bits, overflow=overflow)


def _signed_sr_cast_prng_kernel(seed_ref, x_ref, v_ref, o_ref,
                                *, fmt, mode, eps, block_rows, overflow,
                                interpret):
    i = pl.program_id(0)
    common.seed_kernel_prng(seed_ref, i, interpret=interpret)
    bits = common.kernel_bits(seed_ref, x_ref.shape,
                              row0=i * block_rows, interpret=interpret)
    o_ref[...] = common.round_block(
        x_ref[...], bits, fmt, mode, eps, v=v_ref[...], overflow=overflow)


def sr_cast_prng_p(x, seed, fmt, mode: str, eps: float = 0.0, v=None,
                   *, block_rows=None, rand_bits: int = 32,
                   overflow: str = "saturate", interpret=None):
    """Stochastic-round ``x`` onto ``fmt`` with in-kernel randomness.

    ``seed``: (2,) uint32 words (see common.derive_seed); the per-block
    seed is (words, block index), delivered via SMEM scalar prefetch.
    Deterministic modes should use ``sr_cast_p`` (the bits are unused).
    """
    fmt = get_grid(fmt)
    if interpret is None:
        interpret = common.default_interpret()
    block_rows = pick_block_rows(x.size, interpret, block_rows)
    shape = x.shape
    xf, rows = _pad_2d(x.reshape(-1), block_rows)
    grid = (rows // block_rows,)
    # with scalar prefetch the index_map also receives the scalar ref
    bspec = pl.BlockSpec((block_rows, LANES), lambda i, s: (i, 0))
    seed = jnp.asarray(seed, jnp.uint32).reshape(2)

    if get_scheme(mode).needs_v:
        if v is None:
            raise ValueError(f"{mode} requires v")
        vf, _ = _pad_2d(jnp.broadcast_to(v, shape).reshape(-1), block_rows)
        kern = functools.partial(_signed_sr_cast_prng_kernel, fmt=fmt,
                                 mode=mode, eps=eps, block_rows=block_rows,
                                 overflow=overflow, interpret=interpret)
        operands, in_specs = (xf, vf), [bspec, bspec]
    else:
        kern = functools.partial(_sr_cast_prng_kernel, fmt=fmt, mode=mode,
                                 eps=eps, block_rows=block_rows,
                                 rand_bits=rand_bits, overflow=overflow,
                                 interpret=interpret)
        operands, in_specs = (xf,), [bspec]

    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=bspec,
        ),
        out_shape=jax.ShapeDtypeStruct(xf.shape, jnp.float32),
        name="sr_cast_prng",
        interpret=interpret,
    )(seed, *operands)
    return out.reshape(-1)[: x.size].reshape(shape)
