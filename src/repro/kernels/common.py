"""Shared rounding math + in-kernel randomness for the Pallas kernels.

The kernel bodies reuse the *identical* jnp bit-manipulation code as the
pure-JAX engine (`repro.core.rounding`) — every op involved (integer shifts,
bitcast, floor, where) lowers both to XLA and to Mosaic/TPU, and runs under
``interpret=True`` on CPU.  This guarantees kernel == oracle bit-for-bit when
fed the same random bits.

Randomness comes in two flavours:

* **explicit-bits mode** — random bits are a uint32 HBM operand generated
  with ``jax.random.bits`` outside the kernel.  Bit-exact against the jnp
  oracle, used as the reference/checkpoint-exact mode, but costs one extra
  HBM stream per rounding step (the roofline killer; EXPERIMENTS.md §Perf).
* **in-kernel PRNG mode** — bits are generated *inside* the kernel, so the
  bits streams vanish from HBM.  On real TPU this is the hardware per-core
  PRNG (``pltpu.prng_seed`` / ``pltpu.prng_random_bits``), seeded per block
  from ``(seed words, block index)`` delivered via SMEM scalar prefetch.
  Under ``interpret=True`` (CPU CI) the same kernel body calls a
  counter-based Threefry-2x32 hash in plain jnp keyed by the same seed and
  the element's *global* (row, lane) coordinates — so CPU runs exercise the
  identical code path and the bits are independent of the block partition.
  The two backends draw different bits; PRNG-mode correctness is therefore
  statistical (mean/variance of the roundoff error vs the paper's eqs. 3-5,
  tests/test_kernel_prng.py), not bit-exact.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import FPFormat, get_format
from repro.core.grids import Grid, get_grid
from repro.core.rounding import (RoundingSpec, _ceil_from_decompose,
                                 _exact_scale, _float_exponent,
                                 _uniform_from_bits, get_scheme,
                                 magnitude_decompose, uint_to_f32)


def round_block(x, bits, fmt, mode, eps: float, v=None,
                rand_bits: int = 32, overflow: str = "saturate"):
    """Round one block of float32 values; identical math to round_to_format.

    ``fmt`` is a Grid, FPFormat or grid name; ``mode`` a scheme name (or
    RoundingScheme) — the kernel body emits the scheme's ``p_up`` rule on
    the grid decomposition, so any registered scheme × grid pair (SR 2.0,
    fixed-point, shifted grids) works in-kernel.  ``bits`` may be None for
    deterministic schemes.  ``v`` is the bias direction for signed-SRε.
    With ``rand_bits < 32`` the low ``rand_bits`` bits of each word are
    consumed (few-random-bits SR / SR 2.0's single comparison draw; see
    rounding._uniform_from_bits).
    """
    grid = get_grid(fmt)
    scheme = get_scheme(mode)
    fmt = grid.fmt
    x = x.astype(jnp.float32)
    z = grid.to_grid(x)
    z = jnp.where(jnp.abs(z) < jnp.float32(2.0 ** -126), z * 0.0, z)

    if (scheme.randomness == "bittrick" and bits is not None
            and not grid.transformed and fmt.name == "bfloat16"
            and rand_bits == 16):
        # PRF-free bf16-SR int fast path (`copy_stochastic_`): add 16
        # random bits to the float32 word, truncate to the top 16.  The
        # carry out of the low half is exactly the oracle's round-up
        # event u < frac with the complemented draw (rounding.
        # _uniform_from_bits "bittrick"), so this is bit-identical to
        # the generic path below given the same words.  Finite inputs
        # can only overflow to ±inf (the carry stops at the exponent
        # field), never to a NaN pattern, and ±0 / −0 are preserved by
        # the integer arithmetic itself.
        zb = jax.lax.bitcast_convert_type(z, jnp.uint32)
        r = (zb + (bits & jnp.uint32(0xFFFF))) & jnp.uint32(0xFFFF0000)
        out = jax.lax.bitcast_convert_type(r, jnp.float32)
        if overflow != "inf":
            out = jnp.where(jnp.isfinite(out), out,
                            jnp.sign(z) * jnp.float32(fmt.xmax))
        return jnp.where(jnp.isfinite(x), out, x)

    floor_mag, quantum, frac, fy = magnitude_decompose(z, fmt)
    sign_x = jnp.sign(z)

    if bits is None:
        u = jnp.full(x.shape, 0.5, jnp.float32)
    else:
        u = _uniform_from_bits(bits, rand_bits, scheme.randomness)

    if scheme.p_up_is_frac and fmt.quantum_min_exp >= -126:
        # pure-SR fast path (the GEMM-epilogue hot case), valid for every
        # scheme with p_up == frac (SR and SR 2.0 — the draws differ, the
        # comparison is the same): the ceil neighbour is floor_mag +
        # quantum — exact, because both are multiples of the same power of
        # two and fy+1 <= 2^precision — and p_up == frac makes the
        # frac == 0 fix-up a no-op (u >= 0 never rounds up).
        # Bit-identical to the generic path below; restricted to grids
        # whose quantum stays f32-normal (bfloat16's subnormal-range
        # quantum would flush to zero; fxp grids always qualify).
        mag = jnp.where(u < frac, floor_mag + quantum, floor_mag)
    else:
        ceil_mag = _ceil_from_decompose(z, fy, fmt)
        sign_v = jnp.sign(v.astype(jnp.float32)) if v is not None \
            else jnp.zeros_like(z)
        p_up = scheme.p_up(frac, fy, sign_x, jnp.float32(eps), sign_v)
        mag = jnp.where(u < p_up, ceil_mag, floor_mag)
        mag = jnp.where(frac == 0.0, jnp.abs(z), mag)
    xmax = jnp.float32(fmt.xmax)
    if overflow == "inf":
        mag = jnp.where(mag > xmax, jnp.float32(jnp.inf), mag)
    else:
        mag = jnp.minimum(mag, xmax)
    out = jnp.where(sign_x < 0, -mag, mag)
    # negative-zero fix-up (matches round_to_format): sign(-0.0) == 0, so
    # the branch above would emit +0.0 where the oracle preserves -0.0
    out = jnp.where(jnp.signbit(z) & (z == 0), -jnp.float32(0.0), out)
    out = grid.from_grid(out)
    return jnp.where(jnp.isfinite(x), out, x)


def apply_spec_block(spec: RoundingSpec, x, bits, v=None):
    """RoundingSpec-dispatched block rounding (identity-aware)."""
    if spec.is_identity:
        return x.astype(jnp.float32)
    return round_block(x, bits if spec.stochastic else None,
                       spec.fmt, spec.mode, spec.eps, v=v,
                       rand_bits=spec.rand_bits, overflow=spec.overflow)


# ---------------------------------------------------------------------------
# Packed low-precision storage: format grid values <-> integer code words.
# ---------------------------------------------------------------------------
def pack_spec(fmt):
    """(ebits, mbits, width_bytes, has_nonfinite_field) for a packable fmt.

    The code word is the generic (sign | biased-exponent | mantissa) layout
    with ``mbits = precision - 1`` mantissa bits and the smallest exponent
    field that covers ``emin..emax`` plus the subnormal field 0 — for
    binary8/E5M2, binary16 and bfloat16 this reproduces the IEEE bit layout
    exactly; e4m3 uses all 16 exponent fields for finite values (the OCP
    finite-max flavour), so non-finite inputs saturate to ±xmax on encode.
    Raises for formats wider than 16 bits (nothing to pack).

    Accepts any (untransformed) grid: a ``fxpW.F`` grid's degenerate
    descriptor (single binade + subnormals, uniform quantum) packs to
    exactly ``W`` code bits with no spare non-finite field — saturating,
    like e4m3.
    """
    fmt = get_grid(fmt).fmt
    mbits = fmt.precision - 1
    n_fields = fmt.emax - fmt.emin + 2          # subnormal field 0 included
    ebits = max(1, (n_fields - 1).bit_length())
    total = 1 + ebits + mbits
    if total > 16:
        raise ValueError(f"format {fmt.name!r} does not fit a packed "
                         f"16-bit code word ({total} bits)")
    width = 1 if total <= 8 else 2
    has_nf = (1 << ebits) - 1 >= n_fields       # a spare all-ones field
    return ebits, mbits, width, has_nf


def pack_bytes(fmt) -> int:
    """Bytes per element of the packed representation of ``fmt``."""
    return pack_spec(fmt)[2]


def pack_dtype(fmt):
    return jnp.uint8 if pack_spec(fmt)[2] == 1 else jnp.uint16


def pack_block(x, fmt):
    """Encode float32 values *already on the fmt grid* as packed codes.

    Inverse of :func:`unpack_block` on grid values.  Out-of-grid inputs are
    undefined (the epilogues only ever feed it round_block outputs).
    Non-finite values use the spare all-ones exponent field where the
    format has one (binary8/bfloat16/binary16, matching IEEE), and
    saturate to ±xmax for e4m3 and fxp grids.
    """
    fmt = get_grid(fmt).fmt
    ebits, mbits, width, has_nf = pack_spec(fmt)
    x = x.astype(jnp.float32)
    sign = jnp.signbit(x).astype(jnp.uint32)
    mag = jnp.abs(x)
    finite = jnp.isfinite(x)
    mag_f = jnp.where(finite, mag, jnp.float32(fmt.xmax))
    is_sub = mag_f < jnp.float32(fmt.xmin)
    e = jnp.where(is_sub, jnp.int32(fmt.emin), _float_exponent(mag_f))
    q = _exact_scale(mag_f, mbits - e)          # integer significand, exact
    m = q.astype(jnp.uint32) & jnp.uint32((1 << mbits) - 1)
    field = jnp.where(is_sub, jnp.uint32(0),
                      (e - fmt.emin + 1).astype(jnp.uint32))
    code = (sign << jnp.uint32(ebits + mbits)) | (field << jnp.uint32(mbits)) | m
    if has_nf:
        nf_field = jnp.uint32((1 << ebits) - 1)
        m_nf = jnp.where(jnp.isnan(x), jnp.uint32((1 << mbits) - 1),
                         jnp.uint32(0))
        code_nf = (sign << jnp.uint32(ebits + mbits)) \
            | (nf_field << jnp.uint32(mbits)) | m_nf
        code = jnp.where(finite, code, code_nf)
    return code.astype(jnp.uint8 if width == 1 else jnp.uint16)


def unpack_block(codes, fmt):
    """Decode packed code words back to exact float32 grid values."""
    fmt = get_grid(fmt).fmt
    ebits, mbits, _, has_nf = pack_spec(fmt)
    c = codes.astype(jnp.uint32)
    sign = (c >> jnp.uint32(ebits + mbits)) & jnp.uint32(1)
    field = (c >> jnp.uint32(mbits)) & jnp.uint32((1 << ebits) - 1)
    m = c & jnp.uint32((1 << mbits) - 1)
    is_sub = field == 0
    e = jnp.where(is_sub, jnp.int32(fmt.emin),
                  field.astype(jnp.int32) - 1 + fmt.emin)
    sig = uint_to_f32(jnp.where(is_sub, m, m + jnp.uint32(1 << mbits)))
    mag = _exact_scale(sig, e - mbits)
    out = jnp.where(sign == 1, -mag, mag)
    if has_nf:
        nf = field == (1 << ebits) - 1
        inf = jnp.where(sign == 1, -jnp.inf, jnp.inf).astype(jnp.float32)
        out = jnp.where(nf, jnp.where(m == 0, inf, jnp.float32(jnp.nan)), out)
    return out


def default_interpret() -> bool:
    """Pallas interpret mode: on for CPU (this container), off on real TPU."""
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# In-kernel randomness (no bits operands in HBM).
# ---------------------------------------------------------------------------
_GOLDEN = 0x9E3779B9          # stream offsets fold into the Threefry key


def _rotl32(x, r: int):
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def threefry2x32(k0, k1, c0, c1):
    """Threefry-2x32, 20 rounds — the PRF behind jax.random, in plain jnp.

    Only 32-bit adds/xors/rotates, so it lowers to XLA-CPU, Mosaic, and the
    Pallas interpreter alike.  Inputs broadcast; returns the two output
    words (uint32).
    """
    k0, k1 = jnp.uint32(k0), jnp.uint32(k1)
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(0x1BD11BDA))
    x0 = jnp.uint32(c0) + ks[0]
    x1 = jnp.uint32(c1) + ks[1]
    rots = ((13, 15, 26, 6), (17, 29, 16, 24))
    for g in range(5):
        for r in rots[g % 2]:
            x0 = x0 + x1
            x1 = _rotl32(x1, r) ^ x0
        x0 = x0 + ks[(g + 1) % 3]
        x1 = x1 + ks[(g + 2) % 3] + jnp.uint32(g + 1)
    return x0, x1


def counter_bits_pair(k0, k1, shape, row0=0, col0=0, stream: int = 0):
    """TWO independent uint32 bit-planes for one 2-D block, pure jnp.

    Key = (k0, k1 + GOLDEN·stream); counter = the element's *global*
    (row, col) coordinates — so the bits are a deterministic function of
    (seed, coordinates, stream) and independent of how the array was cut
    into blocks.  This is the interpret-mode stand-in for the TPU hardware
    PRNG: same call sites, same independence structure.  Threefry emits two
    output words per counter; callers needing several streams should
    consume both (halves the PRF cost of the fused three-round kernel).
    """
    r = (jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
         + jnp.uint32(row0))
    c = (jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
         + jnp.uint32(col0))
    return threefry2x32(
        k0, jnp.uint32(k1) + jnp.uint32(_GOLDEN) * jnp.uint32(stream), r, c)


def _interleaved_words(k0, k1, shape, row0, col0, stream: int):
    """One uint32 word per element of ``shape`` (last two dims = rows,
    cols; an optional leading batch dim broadcasts through the keys).  The
    Threefry counter is the column *pair* ``(row, col // 2)`` keyed by
    global coordinates, and column ``col`` takes output word ``col % 2``
    of its pair.  Like every counter derivation here it is
    partition-invariant and recomputable outside the kernel; ``col0`` may
    be a traced block offset (dynamic lane alignment).

    Two bit-identical formulations, chosen by backend: XLA:CPU hashes each
    pair once and interleaves its two words through a ``(..., pairs, 2)``
    array; on the TPU that minor dimension of 2 is padded to 128 lanes (a
    64x blow-up for the gradient-sized draws of the wire codec and the
    accumulator), so there each pair is hashed at both of its columns and
    the word picked by parity."""
    key1 = jnp.uint32(k1) + jnp.uint32(_GOLDEN) * jnp.uint32(stream)
    *lead, rows, cols = shape
    if jax.default_backend() == "tpu":
        nd = len(shape)
        r = (jax.lax.broadcasted_iota(jnp.uint32, shape, nd - 2)
             + jnp.asarray(row0).astype(jnp.uint32))
        c = (jax.lax.broadcasted_iota(jnp.uint32, shape, nd - 1)
             + jnp.asarray(col0).astype(jnp.uint32))
        x0, x1 = threefry2x32(k0, key1, r, c >> jnp.uint32(1))
        return jnp.where((c & jnp.uint32(1)) == 0, x0, x1)
    static_col = isinstance(col0, int)
    if static_col:
        off = col0 % 2
        n_pairs = (off + cols + 1) // 2
        cp0 = col0 // 2
    else:
        off = jnp.asarray(col0, jnp.int32) % 2
        n_pairs = cols // 2 + 1                    # static upper bound
        cp0 = jnp.asarray(col0, jnp.int32) // 2
    wshape = tuple(lead) + (rows, n_pairs)
    r = (jax.lax.broadcasted_iota(jnp.uint32, wshape, len(lead))
         + jnp.uint32(row0))
    c = (jax.lax.broadcasted_iota(jnp.uint32, wshape, len(lead) + 1)
         + jnp.uint32(cp0))
    x0, x1 = threefry2x32(k0, key1, r, c)
    inter = jnp.stack([x0, x1], axis=-1).reshape(
        tuple(lead) + (rows, 2 * n_pairs))
    if static_col:
        return inter[..., off:off + cols]
    return jax.lax.dynamic_slice_in_dim(inter, off, cols, axis=-1)


def counter_bits(k0, k1, shape, row0=0, col0=0, stream: int = 0):
    """A uint32 bit-plane for one 2-D block, pure jnp — the canonical
    interpret-mode/oracle bit derivation (see _interleaved_words)."""
    return _interleaved_words(k0, k1, shape, row0, col0, stream)


def _expand_reduced(words, shape, off: int, rand_bits: int):
    """Spread packed ``rand_bits``-bit lanes of uint32 ``words`` over a
    block whose *last* axis is columns: element (..., c) takes field
    ``(off + c) % ratio`` of word ``(..., (off + c) // ratio)``.  The result
    holds the r-bit value in the low bits of each uint32 (the round_block
    contract)."""
    ratio = 32 // rand_bits
    rep = jnp.repeat(words, ratio, axis=-1)[..., off:off + shape[-1]]
    sub = (jax.lax.broadcasted_iota(jnp.uint32, shape, len(shape) - 1)
           + jnp.uint32(off)) % jnp.uint32(ratio)
    return (rep >> (sub * jnp.uint32(rand_bits))) \
        & jnp.uint32((1 << rand_bits) - 1)


def counter_bits_reduced(k0, k1, shape, rand_bits: int, row0=0, col0=0,
                         stream: int = 0):
    """``rand_bits``-bit random fields for a 2-D block at 32/rand_bits of
    the PRF cost (few-random-bits SR).

    One Threefry word serves ``32/rand_bits`` consecutive columns: the word
    grid is keyed by *global* (row, col // ratio) coordinates, so — like
    ``counter_bits`` — the fields are independent of the block partition
    and recomputable outside the kernel (the oracle derivation).  For
    ``rand_bits == 32`` this is exactly ``counter_bits``.  ``col0`` may be
    a traced value (a kernel block offset): the word count is then the
    static upper bound and the lane alignment is a dynamic slice.
    """
    if rand_bits == 32:
        return counter_bits(k0, k1, shape, row0=row0, col0=col0,
                            stream=stream)
    ratio = 32 // rand_bits
    rows, cols = shape
    if isinstance(col0, int):
        off = col0 % ratio
        n_words = (off + cols + ratio - 1) // ratio
        words = counter_bits(k0, k1, (rows, n_words), row0=row0,
                             col0=col0 // ratio, stream=stream)
        return _expand_reduced(words, shape, off, rand_bits)
    off = jnp.asarray(col0, jnp.int32) % ratio
    # static upper bound covering any off <= ratio-1: ceil((cols +
    # ratio-1) / ratio) words are enough for off + cols lanes
    n_words = (cols + 2 * (ratio - 1)) // ratio
    words = counter_bits(k0, k1, (rows, n_words), row0=row0,
                         col0=jnp.asarray(col0, jnp.int32) // ratio,
                         stream=stream)
    rep = jax.lax.dynamic_slice_in_dim(
        jnp.repeat(words, ratio, axis=-1), off, cols, axis=-1)
    sub = (jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
           + off.astype(jnp.uint32)) % jnp.uint32(ratio)
    return (rep >> (sub * jnp.uint32(rand_bits))) \
        & jnp.uint32((1 << rand_bits) - 1)


def counter_bits_batch(words, shape, rand_bits: int = 32, row0=0, col0=0,
                       stream: int = 0):
    """Per-slice counter bits for a (be, rows, cols) batch block, pure jnp.

    ``words``: (be, 2) uint32 — one seed pair per batch slice (the
    ``precision.policy.slice_words`` derivation).  Slice ``e`` draws exactly
    the bits :func:`counter_bits_reduced` would produce from ``words[e]`` at
    the same within-slice global coordinates, so batched results are
    independent of the batch-block partition and recomputable slice-by-slice
    outside the kernel (the oracle derivation).
    """
    be, rows, cols = shape
    k0 = words[:, 0][:, None, None]
    k1 = words[:, 1][:, None, None]
    if rand_bits == 32:
        return _interleaved_words(k0, k1, shape, row0, col0, stream)
    ratio = 32 // rand_bits
    static_col = isinstance(col0, int)
    if static_col:
        off = col0 % ratio
        n_words = (off + cols + ratio - 1) // ratio
        w = _interleaved_words(k0, k1, (be, rows, n_words), row0,
                               col0 // ratio, stream)
        return _expand_reduced(w, shape, off, rand_bits)
    off = jnp.asarray(col0, jnp.int32) % ratio
    n_words = (cols + 2 * (ratio - 1)) // ratio    # static upper bound
    w = _interleaved_words(k0, k1, (be, rows, n_words), row0,
                           jnp.asarray(col0, jnp.int32) // ratio, stream)
    rep = jax.lax.dynamic_slice_in_dim(
        jnp.repeat(w, ratio, axis=-1), off, cols, axis=-1)
    sub = (jax.lax.broadcasted_iota(jnp.uint32, shape, 2)
           + off.astype(jnp.uint32)) % jnp.uint32(ratio)
    return (rep >> (sub * jnp.uint32(rand_bits))) \
        & jnp.uint32((1 << rand_bits) - 1)


def seed_kernel_prng_words(w0, w1, block_id, *, interpret: bool) -> None:
    """Seed the TPU per-core PRNG from two already-loaded uint32 words
    and the block index (no-op under interpret, where kernel_bits_words
    re-derives everything from coordinates instead).  The words flavour
    exists for kernels whose seed operand holds *several* word pairs
    (batched qmatmul: one pair per batch slice) and must pick one
    dynamically.

    Mosaic's ``prng_seed`` takes at most two words, so the block index is
    folded into them in uint32 arithmetic: ``w0 ^ block·golden`` and
    ``w1 + block``.  For a fixed pair the map is injective in the block
    index (the golden constant is odd), so distinct blocks never share a
    hardware stream."""
    if not interpret:
        b = jnp.asarray(block_id).astype(jnp.uint32)
        w0 = jnp.asarray(w0, jnp.uint32) ^ (b * jnp.uint32(_GOLDEN))
        pltpu.prng_seed(w0, jnp.asarray(w1, jnp.uint32) + b)


def _hw_bits(shape):
    """Hardware PRNG draw as uint32 (``prng_random_bits`` returns int32,
    whose arithmetic shifts would corrupt the uint32 mask-and-shift
    derivations downstream)."""
    return jax.lax.bitcast_convert_type(pltpu.prng_random_bits(shape),
                                        jnp.uint32)


def kernel_bits_words(w0, w1, shape, row0=0, col0=0, stream: int = 0,
                      rand_bits: int = 32, *, interpret: bool):
    """kernel_bits on explicit seed words (see seed_kernel_prng_words).

    ``rand_bits < 32`` draws ``rand_bits/32`` as many PRF/hardware words
    per block and spreads their packed lanes over the block
    (few-random-bits SR; the r-bit value lands in the low bits of each
    uint32, matching ``round_block(..., rand_bits=r)``)."""
    if interpret:
        return counter_bits_reduced(w0, w1, shape, rand_bits, row0=row0,
                                    col0=col0, stream=stream)
    if rand_bits == 32:
        return _hw_bits(shape)
    ratio = 32 // rand_bits
    n_words = (shape[1] + ratio - 1) // ratio
    words = _hw_bits((shape[0], n_words))
    return _expand_reduced(words, shape, 0, rand_bits)


def seed_kernel_prng(seed_ref, block_id, *, interpret: bool) -> None:
    """Seed the TPU per-core PRNG for this block (no-op under interpret,
    where kernel_bits re-derives everything from coordinates instead)."""
    if not interpret:
        seed_kernel_prng_words(seed_ref[0], seed_ref[1], block_id,
                               interpret=interpret)


def kernel_bits(seed_ref, shape, row0=0, col0=0, stream: int = 0,
                rand_bits: int = 32, *, interpret: bool):
    """Draw a block of uint32 random bits inside a kernel body.

    ``interpret=True``: counter-based Threefry in plain jnp (CPU CI path).
    ``interpret=False`` (real TPU): the in-core hardware PRNG — the caller
    must have run seed_kernel_prng for this block first; successive draws
    advance the hardware stream, so ``stream`` is only used by the
    interpret path (where draws are stateless).
    """
    return kernel_bits_words(seed_ref[0], seed_ref[1], shape, row0=row0,
                             col0=col0, stream=stream, rand_bits=rand_bits,
                             interpret=interpret)


def kernel_bits3(seed_ref, shape, row0, need, *, interpret: bool):
    """Up to three bit-planes for the fused eq.-8 kernel, ``None`` where the
    corresponding rounding step is deterministic (``need`` is a static bool
    triple).  The interpret path consumes both Threefry output words per
    call, so three stochastic steps cost two PRF evaluations, not three."""
    if not interpret:
        return [_hw_bits(shape) if n else None for n in need]
    out = [None, None, None]
    pair, drawn = None, 0
    for i, n in enumerate(need):
        if not n:
            continue
        if pair is None:
            pair = counter_bits_pair(seed_ref[0], seed_ref[1], shape,
                                     row0=row0, stream=drawn)
            drawn += 1
            out[i] = pair[0]
        else:
            out[i] = pair[1]
            pair = None
    return out


def derive_seed(key, step=None, site=None):
    """(base_key[, step[, site]]) -> (2,) uint32 seed words for the kernel PRNG.

    The per-block seed inside the kernel is (words, block_index); folding
    ``step`` here keeps the whole optimizer step a deterministic function
    of the checkpointed (key, step) — restart stays bit-exact.  ``site`` is
    a static int distinguishing rounding sites that share a (key, step)
    pair (e.g. the fwd/dgrad/wgrad GEMMs of one qdot call; repro.precision).
    """
    if step is not None:
        key = jax.random.fold_in(key, step)
    if site is not None:
        key = jax.random.fold_in(key, site)
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    return key.reshape(-1)[:2].astype(jnp.uint32)
