"""Pallas TPU kernels: blocked matmul with low-precision rounded output.

Models the paper's (8a): a gradient/activation GEMM whose *result* is stored
in the low-precision format (rounded by RN or SR).  MXU-shaped tiling:
(bm, bk) x (bk, bn) blocks accumulate into a float32 VMEM scratch across the
K grid dimension; on the last K step the accumulator runs the **fused
epilogue** — optional bias add, optional activation with its own rounding
site, optional packing to low-precision code words — and is written out
exactly once.  Two flavours share all scaffolding and differ only in where
the random bits for the stochastic modes come from: ``qmatmul_p`` reads
explicit uint32 HBM operands (bit-exact oracle mode), ``qmatmul_prng_p``
generates them in-kernel at emit time.

v2 geometry is **pad-free**: the grid is the ceiling division of (M, N, K)
by the block sizes and edge blocks are handled in-kernel — the K-tail
columns/rows are masked to zero inside ``pl.when``-guarded edge steps
(out-of-bounds reads are undefined — NaN under interpret — so *both*
operands are masked), and out-of-bounds output rows/cols are dropped by the
masked block writes Pallas performs natively.  No host-side ``jnp.pad``
copies, no output slicing.

Storage: with ``out_packed=True`` the epilogue emits the rounded result as
packed code words (uint8 for binary8/e4m3, uint16 for binary16/bfloat16 —
``kernels.common.pack_block``), cutting output HBM traffic 4x; a consuming
kernel accepts packed operands via ``a_fmt=...`` and decodes on load
(``unpack_block`` is pure bit math on the loaded block).

Block sizes default to the shape-keyed autotuner (`kernels.autotune`):
whole-array blocks under interpret (per-grid-step emulation overhead
dominates), MXU-saturating VMEM-budgeted tiles on real TPU.  All variants
carry Mosaic scheduling hints (``dimension_semantics``: the K dimension is
the only sequential one) and a ``pl.CostEstimate``.

Batched variants (``qmatmul_batched_p`` / ``qmatmul_batched_prng_p``) add a
leading batch grid dimension over (E, M, K) x (E, K, N) operand stacks —
the lowering target for ``precision.qeinsum`` (MoE expert stacks, per-head
MLA contractions).  The PRNG flavour takes *per-slice* seed words (E, 2)
via scalar prefetch so every batch slice draws an independent bit stream
even under the interpret-mode counter hash; under interpret the batch-block
size ``be`` may exceed 1 (several slices per grid step, vectorized
per-slice draws — results are invariant to ``be``), on real TPU it is
pinned to 1 (the hardware PRNG seeds per grid step).

``qmatmul_swiglu_p`` / ``qmatmul_swiglu_prng_p`` fuse the GLU-FFN prefix —
two GEMMs sharing the x operand, both result-rounded, the gate activation,
the elementwise product and the activation-site rounding — into ONE kernel
(one x read, no elementwise HBM round trips), optionally emitting the
rounded branch values as packed residuals for the backward pass.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.grids import get_grid
from repro.core.rounding import RoundingSpec, get_scheme
from repro.kernels import common

ACT_FNS = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "relu": jax.nn.relu,
    "relu_sq": lambda x: jnp.square(jax.nn.relu(x)),
}
_TRANSCENDENTAL_ACTS = ("silu", "gelu")

# epilogue PRNG stream ids (per seed-word pair): the GEMM-result rounding
# and the activation-site rounding must not share bits
STREAM_FWD, STREAM_ACT = 0, 1


def _check_mode(mode: str) -> None:
    if get_scheme(mode).needs_v:
        raise ValueError(f"{mode} is not supported for GEMM result "
                         "rounding (no bias-direction operand); use "
                         "'sr'/'sr2'/'sr_eps' or a deterministic mode")


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _resolve_epilogue(fmt, act, act_spec, out_packed):
    """Normalize the epilogue config; returns (act_spec|None, pack_fmt|None).

    ``out_packed`` requires the *last* epilogue stage to be a rounding, so
    the emitted values are guaranteed on a packable grid: either no
    activation (pack the GEMM-result format) or an activation followed by a
    non-identity ``act_spec``.
    """
    if act is not None and act not in ACT_FNS:
        raise ValueError(f"unknown epilogue activation {act!r}; "
                         f"known: {sorted(ACT_FNS)}")
    if act_spec is not None and act_spec.is_identity:
        act_spec = None
    if act_spec is not None and act_spec.scheme.needs_v:
        raise ValueError(f"{act_spec.mode} is not supported for the "
                         "activation rounding site (no bias-direction "
                         "operand)")
    if not out_packed:
        return act_spec, None
    if act_spec is not None:
        return act_spec, get_grid(act_spec.fmt)
    if act is not None:
        raise ValueError("out_packed with an activation requires a "
                         "non-identity act_spec (the packed values must "
                         "land on a rounding grid)")
    return None, get_grid(fmt)


def _resolve_blocks(M, N, K, bm, bn, bk, *, mode, interpret):
    """Fill None block sizes from the autotuner, clamp to the problem."""
    if bm is None or bn is None or bk is None:
        from repro.kernels import autotune
        tbm, tbn, tbk = autotune.get_blocks(M, N, K, mode=mode,
                                            interpret=interpret)
        bm = tbm if bm is None else bm
        bn = tbn if bn is None else bn
        bk = tbk if bk is None else bk
    return min(bm, M), min(bn, N), min(bk, K)


def _emit_value(acc, fwd_bits, act_bits, *, fmt, mode, eps, rand_bits,
                act, act_spec, pack_fmt):
    """The shared fused epilogue: round -> activate -> round -> pack."""
    y = common.round_block(acc, fwd_bits, fmt, mode, eps,
                           rand_bits=rand_bits)
    if act is not None:
        y = ACT_FNS[act](y)
    if act_spec is not None:
        y = common.apply_spec_block(act_spec, y, act_bits)
    if pack_fmt is not None:
        y = common.pack_block(y, pack_fmt)
    return y


def _masked_dot(a_blk, b_blk, k_rem):
    """(bm, bk) x (bk, bn) MXU step with the K-tail zeroed on both sides
    (edge-block reads beyond K are undefined: NaN under interpret)."""
    if k_rem:
        kc = jax.lax.broadcasted_iota(jnp.int32, a_blk.shape, 1)
        a_blk = jnp.where(kc < k_rem, a_blk, 0.0)
        kr = jax.lax.broadcasted_iota(jnp.int32, b_blk.shape, 0)
        b_blk = jnp.where(kr < k_rem, b_blk, 0.0)
    return jnp.dot(a_blk, b_blk, preferred_element_type=jnp.float32)


def _cost(M, N, K, *, E=1, act=None, in_bytes, out_bytes):
    return pl.CostEstimate(
        flops=2 * E * M * N * K,
        bytes_accessed=in_bytes + out_bytes,
        transcendentals=E * M * N if act in _TRANSCENDENTAL_ACTS else 0)


_SEMANTICS_2D = ("parallel", "parallel", "arbitrary")
_SEMANTICS_BATCHED = ("parallel", "parallel", "parallel", "arbitrary")


# ---------------------------------------------------------------------------
# 2-D variants.
# ---------------------------------------------------------------------------
def _qmm2d(a, b, rand, fmt, mode, eps, *, rand_bits, bm, bn, bk, bias, act,
           act_spec, act_bits, out_packed, a_fmt, interpret):
    _check_mode(mode)
    fmt = get_grid(fmt)
    if interpret is None:
        interpret = common.default_interpret()
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    bm_, bn_, bk_ = _resolve_blocks(M, N, K, bm, bn, bk, mode=mode,
                                    interpret=interpret)
    grid = (_cdiv(M, bm_), _cdiv(N, bn_), _cdiv(K, bk_))
    k_steps = grid[2]
    k_rem = K % bk_
    act_spec, pack_fmt = _resolve_epilogue(fmt, act, act_spec, out_packed)
    prng = rand[0] == "seed"
    stoch = get_scheme(mode).stochastic
    act_stoch = act_spec is not None and act_spec.stochastic

    def idx_a(i, j, k, *s):
        return (i, k)

    def idx_b(i, j, k, *s):
        return (k, j)

    def idx_out(i, j, k, *s):
        return (i, j)

    def idx_bias(i, j, k, *s):
        return (0, j)

    operands, in_specs = [a, b], [
        pl.BlockSpec((bm_, bk_), idx_a),
        pl.BlockSpec((bk_, bn_), idx_b),
    ]
    has_bias = bias is not None
    if has_bias:
        operands.append(jnp.asarray(bias, jnp.float32).reshape(1, N))
        in_specs.append(pl.BlockSpec((1, bn_), idx_bias))
    if not prng:
        operands.append(rand[1])                  # bits: uniform signature
        in_specs.append(pl.BlockSpec((bm_, bn_), idx_out))
        if act_stoch:
            if act_bits is None:
                raise ValueError("stochastic act_spec in explicit-bits mode "
                                 "requires act_bits")
            operands.append(act_bits)
            in_specs.append(pl.BlockSpec((bm_, bn_), idx_out))
    elif act_bits is not None:
        raise ValueError("act_bits is an explicit-bits-mode operand; the "
                         "PRNG flavour draws the activation stream in-kernel")

    out_dtype = common.pack_dtype(pack_fmt) if pack_fmt is not None \
        else jnp.float32

    # single-K-step fast path (what the autotuner picks under interpret):
    # no accumulator scratch, no pl.when conds — the dot feeds the fused
    # epilogue directly.  Bit-compatible with the blocked path (the first
    # accumulate into a zeroed scratch folds to the dot itself).
    single_k = k_steps == 1

    def kernel(*refs):
        if prng:
            seed_ref, refs = refs[0], refs[1:]
        a_ref, b_ref = refs[0], refs[1]
        idx = 2
        if has_bias:
            bias_ref = refs[idx]
            idx += 1
        if not prng:
            # the bits operand is always present (uniform signature) but
            # only consumed by stochastic modes
            if stoch:
                bits_ref = refs[idx]
            idx += 1
            if act_stoch:
                act_bits_ref = refs[idx]
                idx += 1
        if single_k:
            o_ref, acc_ref = refs[idx], None
        else:
            o_ref, acc_ref = refs[idx], refs[idx + 1]

        i, j = pl.program_id(0), pl.program_id(1)
        n_j = pl.num_programs(1)

        def _dot_block(rem):
            a_blk = a_ref[...]
            if a_fmt is not None:
                a_blk = common.unpack_block(a_blk, a_fmt)
            return _masked_dot(a_blk, b_ref[...], rem)

        def _emit_from(acc):
            if has_bias:
                acc = acc + bias_ref[...]
            if prng and (stoch or act_stoch):
                common.seed_kernel_prng(seed_ref, i * n_j + j,
                                        interpret=interpret)
            fwd_bits = None
            if stoch:
                fwd_bits = bits_ref[...] if not prng else common.kernel_bits(
                    seed_ref, acc.shape, row0=i * bm_, col0=j * bn_,
                    stream=STREAM_FWD, rand_bits=rand_bits,
                    interpret=interpret)
            ab = None
            if act_stoch:
                ab = act_bits_ref[...] if not prng else common.kernel_bits(
                    seed_ref, acc.shape, row0=i * bm_, col0=j * bn_,
                    stream=STREAM_ACT, rand_bits=act_spec.rand_bits,
                    interpret=interpret)
            o_ref[...] = _emit_value(acc, fwd_bits, ab, fmt=fmt, mode=mode,
                                     eps=eps, rand_bits=rand_bits, act=act,
                                     act_spec=act_spec, pack_fmt=pack_fmt)

        if single_k:
            _emit_from(_dot_block(0))
            return

        @pl.when(pl.program_id(2) == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        if k_rem:
            @pl.when(pl.program_id(2) == k_steps - 1)
            def _edge():
                acc_ref[...] += _dot_block(k_rem)

            @pl.when(pl.program_id(2) < k_steps - 1)
            def _full():
                acc_ref[...] += _dot_block(0)
        else:
            acc_ref[...] += _dot_block(0)

        @pl.when(pl.program_id(2) == k_steps - 1)
        def _emit():
            _emit_from(acc_ref[...])

    in_bytes = (M * K * (common.pack_bytes(a_fmt) if a_fmt is not None else 4)
                + K * N * 4 + (N * 4 if has_bias else 0)
                + (0 if prng else M * N * 4 * (int(stoch) + int(act_stoch))))
    out_bytes = M * N * (common.pack_bytes(pack_fmt) if pack_fmt is not None
                         else 4)
    call_kwargs = dict(
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        name="qmatmul",
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS_2D),
        cost_estimate=_cost(M, N, K, act=act, in_bytes=in_bytes,
                            out_bytes=out_bytes),
    )
    scratch = [] if single_k else [pltpu.VMEM((bm_, bn_), jnp.float32)]
    if prng:
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
                out_specs=pl.BlockSpec((bm_, bn_), idx_out),
                scratch_shapes=scratch),
            **call_kwargs)(rand[1], *operands)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm_, bn_), idx_out),
        scratch_shapes=scratch,
        **call_kwargs)(*operands)


def qmatmul_p(a, b, bits, fmt, mode: str = "sr", eps: float = 0.0,
              *, bm=None, bn=None, bk=None, bias=None, act=None,
              act_spec: RoundingSpec | None = None, act_bits=None,
              out_packed: bool = False, a_fmt=None, rand_bits: int = 32,
              interpret=None):
    """Rounded ``a @ b`` (result-rounding fidelity) as a Pallas kernel.

    a: (M, K) float32 — or packed code words of ``a_fmt`` (decoded on
    load); b: (K, N) float32; bits: (M, N) uint32 (ignored for
    deterministic modes but must be supplied for a uniform signature; with
    ``rand_bits < 32`` only the low bits of each word are consumed).
    Block sizes default to the shape-keyed autotuner.  ``signed_sr_eps``
    is rejected: result-rounding a GEMM has no bias-direction operand.

    Fused epilogue (all optional, applied inside the last K step):
    ``bias`` (N,) added to the accumulator before rounding; ``act``
    activation applied *after* the GEMM-result rounding; ``act_spec`` a
    second rounding onto the activation grid (stochastic act_spec needs
    the ``act_bits`` (M, N) operand here); ``out_packed`` emits packed
    code words instead of float32.
    """
    a_fmt = None if a_fmt is None else get_grid(a_fmt)
    return _qmm2d(a, b, ("bits", bits), fmt, mode, eps, rand_bits=rand_bits,
                  bm=bm, bn=bn, bk=bk, bias=bias, act=act, act_spec=act_spec,
                  act_bits=act_bits, out_packed=out_packed, a_fmt=a_fmt,
                  interpret=interpret)


def qmatmul_prng_p(a, b, seed, fmt, mode: str = "sr", eps: float = 0.0,
                   *, bm=None, bn=None, bk=None, bias=None, act=None,
                   act_spec: RoundingSpec | None = None,
                   out_packed: bool = False, a_fmt=None, rand_bits: int = 32,
                   interpret=None):
    """Rounded ``a @ b`` with in-kernel randomness (no bits operands).

    ``seed``: (2,) uint32 words (common.derive_seed) via SMEM scalar
    prefetch; the per-tile seed is (words, linearized (i, j) tile index).
    The GEMM-result rounding draws stream 0, a stochastic ``act_spec``
    stream 1.  Epilogue/packing/blocks as in :func:`qmatmul_p`.
    """
    seed = jnp.asarray(seed, jnp.uint32).reshape(2)
    a_fmt = None if a_fmt is None else get_grid(a_fmt)
    return _qmm2d(a, b, ("seed", seed), fmt, mode, eps, rand_bits=rand_bits,
                  bm=bm, bn=bn, bk=bk, bias=bias, act=act, act_spec=act_spec,
                  act_bits=None, out_packed=out_packed, a_fmt=a_fmt,
                  interpret=interpret)


# ---------------------------------------------------------------------------
# Batched (stacked) variants: grid (e, i, j, k) over (E, M, K) x (E, K, N).
# ---------------------------------------------------------------------------
def _resolve_batch_blocks(E, M, N, K, be, bm, bn, bk, *, mode, interpret):
    if bm is None or bn is None or bk is None or be is None:
        from repro.kernels import autotune
        tbe, tbm, tbn, tbk = autotune.get_batch_blocks(
            E, M, N, K, mode=mode, interpret=interpret)
        # explicit (bm, bn, bk) with be unset keeps the legacy one-slice-
        # per-step grid (hardware-PRNG compatible and partition-pinned)
        if be is None:
            be = tbe if (bm is None and bn is None and bk is None) else 1
        bm = tbm if bm is None else bm
        bn = tbn if bn is None else bn
        bk = tbk if bk is None else bk
    if be > 1 and not interpret:
        raise ValueError("batch-block be > 1 is interpret-only (the TPU "
                         "hardware PRNG seeds one batch slice per grid "
                         "step)")
    return min(be, E), min(bm, M), min(bn, N), min(bk, K)


def _qmmb(a, b, rand, fmt, mode, eps, *, rand_bits, be, bm, bn, bk, act,
          act_spec, act_bits, out_packed, a_fmt, interpret):
    _check_mode(mode)
    fmt = get_grid(fmt)
    if interpret is None:
        interpret = common.default_interpret()
    E, M, K = a.shape
    E2, K2, N = b.shape
    assert E == E2 and K == K2, (a.shape, b.shape)
    be_, bm_, bn_, bk_ = _resolve_batch_blocks(
        E, M, N, K, be, bm, bn, bk, mode=mode, interpret=interpret)
    grid = (_cdiv(E, be_), _cdiv(M, bm_), _cdiv(N, bn_), _cdiv(K, bk_))
    k_steps = grid[3]
    k_rem = K % bk_
    act_spec, pack_fmt = _resolve_epilogue(fmt, act, act_spec, out_packed)
    prng = rand[0] == "seed"
    stoch = get_scheme(mode).stochastic
    act_stoch = act_spec is not None and act_spec.stochastic

    def idx_a(e, i, j, k, *s):
        return (e, i, k)

    def idx_b(e, i, j, k, *s):
        return (e, k, j)

    def idx_out(e, i, j, k, *s):
        return (e, i, j)

    operands, in_specs = [a, b], [
        pl.BlockSpec((be_, bm_, bk_), idx_a),
        pl.BlockSpec((be_, bk_, bn_), idx_b),
    ]
    if not prng:
        operands.append(rand[1])
        in_specs.append(pl.BlockSpec((be_, bm_, bn_), idx_out))
        if act_stoch:
            if act_bits is None:
                raise ValueError("stochastic act_spec in explicit-bits mode "
                                 "requires act_bits")
            operands.append(act_bits)
            in_specs.append(pl.BlockSpec((be_, bm_, bn_), idx_out))
    seeds = None
    if prng:
        seeds = rand[1]
        Ep = grid[0] * be_
        if Ep != E:                       # tiny (E, 2) host-side pad only
            seeds = jnp.concatenate(
                [seeds, jnp.zeros((Ep - E, 2), jnp.uint32)])

    out_dtype = common.pack_dtype(pack_fmt) if pack_fmt is not None \
        else jnp.float32

    single_k = k_steps == 1

    def kernel(*refs):
        if prng:
            seed_ref, refs = refs[0], refs[1:]
        a_ref, b_ref = refs[0], refs[1]
        idx = 2
        if not prng:
            if stoch:
                bits_ref = refs[idx]
            idx += 1
            if act_stoch:
                act_bits_ref = refs[idx]
                idx += 1
        if single_k:
            o_ref, acc_ref = refs[idx], None
        else:
            o_ref, acc_ref = refs[idx], refs[idx + 1]

        e, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        n_i, n_j = pl.num_programs(1), pl.num_programs(2)

        def _dot_block(rem):
            a_blk = a_ref[...]
            if a_fmt is not None:
                a_blk = common.unpack_block(a_blk, a_fmt)
            b_blk = b_ref[...]
            if rem:
                kc = jax.lax.broadcasted_iota(jnp.int32, a_blk.shape, 2)
                a_blk = jnp.where(kc < rem, a_blk, 0.0)
                kr = jax.lax.broadcasted_iota(jnp.int32, b_blk.shape, 1)
                b_blk = jnp.where(kr < rem, b_blk, 0.0)
            return jax.lax.dot_general(
                a_blk, b_blk, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)

        def _emit_from(acc):
            if prng and (stoch or act_stoch) and not interpret:
                # hardware path (be_ == 1): seed ONCE per block from the
                # slice's words + block id; successive draws advance the
                # stream (fwd first, then the activation site)
                common.seed_kernel_prng_words(
                    seed_ref[e, 0], seed_ref[e, 1],
                    (e * n_i + i) * n_j + j, interpret=interpret)

            def draw(stream, rb):
                if interpret:
                    words = jax.lax.dynamic_slice(
                        seed_ref[...], (e * be_, 0), (be_, 2))
                    return common.counter_bits_batch(
                        words, acc.shape, rb, row0=i * bm_, col0=j * bn_,
                        stream=stream)
                return common.kernel_bits_words(
                    seed_ref[e, 0], seed_ref[e, 1], acc.shape[1:],
                    row0=i * bm_, col0=j * bn_, stream=stream, rand_bits=rb,
                    interpret=interpret)[None]

            fwd_bits = None
            if stoch:
                fwd_bits = bits_ref[...] if not prng \
                    else draw(STREAM_FWD, rand_bits)
            ab = None
            if act_stoch:
                ab = act_bits_ref[...] if not prng \
                    else draw(STREAM_ACT, act_spec.rand_bits)
            o_ref[...] = _emit_value(acc, fwd_bits, ab, fmt=fmt, mode=mode,
                                     eps=eps, rand_bits=rand_bits, act=act,
                                     act_spec=act_spec, pack_fmt=pack_fmt)

        if single_k:
            _emit_from(_dot_block(0))
            return

        @pl.when(pl.program_id(3) == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        if k_rem:
            @pl.when(pl.program_id(3) == k_steps - 1)
            def _edge():
                acc_ref[...] += _dot_block(k_rem)

            @pl.when(pl.program_id(3) < k_steps - 1)
            def _full():
                acc_ref[...] += _dot_block(0)
        else:
            acc_ref[...] += _dot_block(0)

        @pl.when(pl.program_id(3) == k_steps - 1)
        def _emit():
            _emit_from(acc_ref[...])

    in_bytes = (E * M * K * (common.pack_bytes(a_fmt) if a_fmt is not None
                             else 4) + E * K * N * 4
                + (0 if prng
                   else E * M * N * 4 * (int(stoch) + int(act_stoch))))
    out_bytes = E * M * N * (common.pack_bytes(pack_fmt)
                             if pack_fmt is not None else 4)
    call_kwargs = dict(
        out_shape=jax.ShapeDtypeStruct((E, M, N), out_dtype),
        name="qmatmul_batched",
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS_BATCHED),
        cost_estimate=_cost(M, N, K, E=E, act=act, in_bytes=in_bytes,
                            out_bytes=out_bytes),
    )
    scratch = [] if single_k else [pltpu.VMEM((be_, bm_, bn_), jnp.float32)]
    if prng:
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
                out_specs=pl.BlockSpec((be_, bm_, bn_), idx_out),
                scratch_shapes=scratch),
            **call_kwargs)(seeds, *operands)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((be_, bm_, bn_), idx_out),
        scratch_shapes=scratch,
        **call_kwargs)(*operands)


def qmatmul_batched_p(a, b, bits, fmt, mode: str = "sr", eps: float = 0.0,
                      *, be=None, bm=None, bn=None, bk=None, act=None,
                      act_spec: RoundingSpec | None = None, act_bits=None,
                      out_packed: bool = False, a_fmt=None,
                      rand_bits: int = 32, interpret=None):
    """Rounded batched matmul ``a[e] @ b[e]`` with explicit bits (oracle).

    a: (E, M, K) float32 (or packed ``a_fmt`` codes); b: (E, K, N)
    float32; bits: (E, M, N) uint32 — one bit-plane per batch slice
    (deterministic modes ignore it but the signature stays uniform with
    the 2-D kernel).  Epilogue/packing/blocks as in :func:`qmatmul_p`;
    ``be`` batch slices are processed per grid step (autotuned, results
    invariant to the choice).
    """
    a_fmt = None if a_fmt is None else get_grid(a_fmt)
    return _qmmb(a, b, ("bits", bits), fmt, mode, eps, rand_bits=rand_bits,
                 be=be, bm=bm, bn=bn, bk=bk, act=act, act_spec=act_spec,
                 act_bits=act_bits, out_packed=out_packed, a_fmt=a_fmt,
                 interpret=interpret)


def qmatmul_batched_prng_p(a, b, seeds, fmt, mode: str = "sr",
                           eps: float = 0.0, *, be=None, bm=None, bn=None,
                           bk=None, act=None,
                           act_spec: RoundingSpec | None = None,
                           out_packed: bool = False, a_fmt=None,
                           rand_bits: int = 32, interpret=None):
    """Rounded batched matmul with in-kernel randomness.

    ``seeds``: (E, 2) uint32 — *per-batch-slice* seed words (the caller
    folds the slice index into the call-site words, precision.policy), via
    SMEM scalar prefetch.  Slices therefore own independent bit streams on
    both the hardware-PRNG and interpret paths, and interpret-mode results
    are invariant to the batch-block size ``be``.
    """
    E = a.shape[0]
    seeds = jnp.asarray(seeds, jnp.uint32).reshape(E, 2)
    a_fmt = None if a_fmt is None else get_grid(a_fmt)
    return _qmmb(a, b, ("seed", seeds), fmt, mode, eps, rand_bits=rand_bits,
                 be=be, bm=bm, bn=bn, bk=bk, act=act, act_spec=act_spec,
                 act_bits=None, out_packed=out_packed, a_fmt=a_fmt,
                 interpret=interpret)


# ---------------------------------------------------------------------------
# Fused GLU-FFN prefix: h = round_act(act(round(x@wg)) * round(x@wu)).
# ---------------------------------------------------------------------------
def _qmm_swiglu(x, wg, wu, rand, fmt, mode, eps, *, rand_bits, act, act_spec,
                act_bits, bm, bn, bk, out_packed, residuals,
                residuals_packed, interpret):
    _check_mode(mode)
    fmt = get_grid(fmt)
    if interpret is None:
        interpret = common.default_interpret()
    M, K = x.shape
    K2, N = wg.shape
    assert K == K2 and wu.shape == wg.shape, (x.shape, wg.shape, wu.shape)
    bm_, bn_, bk_ = _resolve_blocks(M, N, K, bm, bn, bk, mode=mode,
                                    interpret=interpret)
    grid = (_cdiv(M, bm_), _cdiv(N, bn_), _cdiv(K, bk_))
    k_steps = grid[2]
    k_rem = K % bk_
    act_spec, pack_fmt = _resolve_epilogue(fmt, act, act_spec, out_packed)
    if act is None:
        raise ValueError("the fused GLU kernel needs an activation")
    prng = rand[0] == "seed"
    stoch = get_scheme(mode).stochastic
    act_stoch = act_spec is not None and act_spec.stochastic
    res_fmt = fmt if residuals_packed else None
    res_dtype = common.pack_dtype(fmt) if res_fmt is not None else jnp.float32

    def idx_x(i, j, k, *s):
        return (i, k)

    def idx_w(i, j, k, *s):
        return (k, j)

    def idx_out(i, j, k, *s):
        return (i, j)

    operands, in_specs = [x, wg, wu], [
        pl.BlockSpec((bm_, bk_), idx_x),
        pl.BlockSpec((bk_, bn_), idx_w),
        pl.BlockSpec((bk_, bn_), idx_w),
    ]
    if not prng:
        bits_g, bits_u = rand[1]
        operands += [bits_g, bits_u]
        in_specs += [pl.BlockSpec((bm_, bn_), idx_out)] * 2
        if act_stoch:
            if act_bits is None:
                raise ValueError("stochastic act_spec in explicit-bits mode "
                                 "requires act_bits")
            operands.append(act_bits)
            in_specs.append(pl.BlockSpec((bm_, bn_), idx_out))

    h_dtype = common.pack_dtype(pack_fmt) if pack_fmt is not None \
        else jnp.float32
    out_shapes = [jax.ShapeDtypeStruct((M, N), h_dtype)]
    out_specs = [pl.BlockSpec((bm_, bn_), idx_out)]
    if residuals:
        out_shapes += [jax.ShapeDtypeStruct((M, N), res_dtype)] * 2
        out_specs += [pl.BlockSpec((bm_, bn_), idx_out)] * 2

    single_k = k_steps == 1

    def kernel(*refs):
        if prng:
            seed_ref, refs = refs[0], refs[1:]
        x_ref, wg_ref, wu_ref = refs[0], refs[1], refs[2]
        idx = 3
        if not prng and stoch:
            bits_g_ref, bits_u_ref = refs[idx], refs[idx + 1]
            idx += 2
        elif not prng:
            idx += 2                       # deterministic: operands unused
        if not prng and act_stoch:
            act_bits_ref = refs[idx]
            idx += 1
        if residuals:
            h_ref, g_ref, u_ref = refs[idx], refs[idx + 1], refs[idx + 2]
            idx += 3
        else:
            h_ref = refs[idx]
            idx += 1
        accg_ref, accu_ref = (None, None) if single_k \
            else (refs[idx], refs[idx + 1])

        i, j = pl.program_id(0), pl.program_id(1)
        n_j = pl.num_programs(1)

        def _dots(rem):
            x_blk = x_ref[...]
            return (_masked_dot(x_blk, wg_ref[...], rem),
                    _masked_dot(x_blk, wu_ref[...], rem))

        def _emit_from(accg, accu):
            if prng and (stoch or act_stoch) and not interpret:
                # hardware path: one seed per block; the three streams are
                # successive draws.  interpret: stateless per-words counters.
                common.seed_kernel_prng_words(
                    seed_ref[0, 0], seed_ref[0, 1], i * n_j + j,
                    interpret=interpret)

            def draw(row, stream, rb):
                return common.kernel_bits_words(
                    seed_ref[row, 0], seed_ref[row, 1], (bm_, bn_),
                    row0=i * bm_, col0=j * bn_, stream=stream, rand_bits=rb,
                    interpret=interpret)

            bg = bu = None
            if stoch:
                if prng:
                    bg = draw(0, STREAM_FWD, rand_bits)
                    bu = draw(1, STREAM_FWD, rand_bits)
                else:
                    bg, bu = bits_g_ref[...], bits_u_ref[...]
            g_r = common.round_block(accg, bg, fmt, mode, eps,
                                     rand_bits=rand_bits)
            u_r = common.round_block(accu, bu, fmt, mode, eps,
                                     rand_bits=rand_bits)
            h = ACT_FNS[act](g_r) * u_r
            ab = None
            if act_stoch:
                ab = act_bits_ref[...] if not prng \
                    else draw(2, STREAM_ACT, act_spec.rand_bits)
            if act_spec is not None:
                h = common.apply_spec_block(act_spec, h, ab)
            if pack_fmt is not None:
                h = common.pack_block(h, pack_fmt)
            h_ref[...] = h
            if residuals:
                if res_fmt is not None:
                    g_ref[...] = common.pack_block(g_r, res_fmt)
                    u_ref[...] = common.pack_block(u_r, res_fmt)
                else:
                    g_ref[...] = g_r
                    u_ref[...] = u_r

        if single_k:
            _emit_from(*_dots(0))
            return

        @pl.when(pl.program_id(2) == 0)
        def _init():
            accg_ref[...] = jnp.zeros_like(accg_ref)
            accu_ref[...] = jnp.zeros_like(accu_ref)

        if k_rem:
            @pl.when(pl.program_id(2) == k_steps - 1)
            def _edge():
                dg, du = _dots(k_rem)
                accg_ref[...] += dg
                accu_ref[...] += du

            @pl.when(pl.program_id(2) < k_steps - 1)
            def _full():
                dg, du = _dots(0)
                accg_ref[...] += dg
                accu_ref[...] += du
        else:
            dg, du = _dots(0)
            accg_ref[...] += dg
            accu_ref[...] += du

        @pl.when(pl.program_id(2) == k_steps - 1)
        def _emit():
            _emit_from(accg_ref[...], accu_ref[...])

    in_bytes = M * K * 4 + 2 * K * N * 4 \
        + (0 if prng else M * N * 4 * (2 * int(stoch) + int(act_stoch)))
    out_bytes = M * N * (common.pack_bytes(pack_fmt) if pack_fmt is not None
                         else 4)
    if residuals:
        out_bytes += 2 * M * N * (common.pack_bytes(res_fmt)
                                  if res_fmt is not None else 4)
    call_kwargs = dict(
        out_shape=out_shapes,
        name="qmatmul_glu",
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS_2D),
        cost_estimate=pl.CostEstimate(
            flops=4 * M * N * K, bytes_accessed=in_bytes + out_bytes,
            transcendentals=M * N),
    )
    scratch = [] if single_k else [pltpu.VMEM((bm_, bn_), jnp.float32)] * 2
    if prng:
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
                out_specs=out_specs, scratch_shapes=scratch),
            **call_kwargs)(rand[1], *operands)
    else:
        out = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
            **call_kwargs)(*operands)
    return tuple(out) if residuals else (out[0],)


def qmatmul_swiglu_p(x, wg, wu, bits_g, bits_u, fmt, mode: str = "sr",
                     eps: float = 0.0, *, act: str = "silu",
                     act_spec: RoundingSpec | None = None, act_bits=None,
                     bm=None, bn=None, bk=None, out_packed: bool = False,
                     residuals: bool = False, residuals_packed: bool = False,
                     rand_bits: int = 32, interpret=None):
    """Fused GLU-FFN prefix, explicit-bits (oracle) flavour.

    Computes ``h = round_act(act(round(x@wg)) * round(x@wu))`` in one
    kernel: x (M, K), wg/wu (K, N), bits_g/bits_u (M, N) uint32 (the two
    GEMM-result rounding planes; ignored for deterministic modes),
    ``act_bits`` the activation-site plane (required iff ``act_spec`` is
    stochastic).  Returns ``(h,)``, or ``(h, g_r, u_r)`` with
    ``residuals=True`` — the rounded branch values the backward pass
    needs, packed to ``fmt`` code words when ``residuals_packed``.
    """
    return _qmm_swiglu(x, wg, wu, ("bits", (bits_g, bits_u)), fmt, mode,
                       eps, rand_bits=rand_bits, act=act, act_spec=act_spec,
                       act_bits=act_bits, bm=bm, bn=bn, bk=bk,
                       out_packed=out_packed, residuals=residuals,
                       residuals_packed=residuals_packed,
                       interpret=interpret)


def qmatmul_swiglu_prng_p(x, wg, wu, seeds, fmt, mode: str = "sr",
                          eps: float = 0.0, *, act: str = "silu",
                          act_spec: RoundingSpec | None = None,
                          bm=None, bn=None, bk=None,
                          out_packed: bool = False, residuals: bool = False,
                          residuals_packed: bool = False,
                          rand_bits: int = 32, interpret=None):
    """Fused GLU-FFN prefix with in-kernel randomness.

    ``seeds``: (3, 2) uint32 — the gate-GEMM, up-GEMM and activation-site
    word pairs (the caller derives them with the same tag/site folds the
    unfused qdense/qact chain uses, so under interpret the gate and up
    rounding decisions are bit-identical to the unfused kernels').
    """
    seeds = jnp.asarray(seeds, jnp.uint32).reshape(3, 2)
    return _qmm_swiglu(x, wg, wu, ("seed", seeds), fmt, mode, eps,
                       rand_bits=rand_bits, act=act, act_spec=act_spec,
                       act_bits=None, bm=bm, bn=bn, bk=bk,
                       out_packed=out_packed, residuals=residuals,
                       residuals_packed=residuals_packed,
                       interpret=interpret)
