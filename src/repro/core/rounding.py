"""Bit-exact software emulation of low-precision rounding, in pure JAX.

Implements every rounding scheme studied in the paper:

* deterministic: RN (round-to-nearest, ties-to-even), RZ, RA, RD (floor),
  RU (ceil);
* stochastic (paper sec. 2.2): SR (Definition 1, unbiased), SRε
  (Definition 2, bias ``sign(x)·ε·ulp``), signed-SRε (Definition 3, bias
  ``-sign(v)·ε·ulp`` — a *descent direction* when ``v`` is the gradient).

Design notes (TPU-native, reused verbatim inside the Pallas kernels):

* Values are carried in float32.  A target-format value is decomposed onto
  its rounding grid with **integer bit manipulation** (not ``frexp``, which
  mishandles float32 subnormals) and **exact two-step power-of-two scaling**
  (each factor is constructed by exponent-field bit assembly, so no
  transcendental is involved and every step is exact).
* The fractional position ``frac = (|x| - ⌊|x|⌋_grid)/ulp ∈ [0, 1)`` is exact
  in float32, because the scaled value ``y = |x|·2^-qe`` lies in ``[0, 2^p)``
  with ``p ≤ 24``.
* All schemes reduce to one unified magnitude rule: *round the magnitude away
  from zero with probability* ``p_up``:

  ======================  =====================================
  scheme                  ``p_up``
  ======================  =====================================
  SR                      ``frac``
  SRε                     ``min(frac + ε, 1)``
  signed-SRε              ``clip(frac − sign(x)·sign(v)·ε, 0, 1)``
  RN (ties-even)          ``1{frac>½} + 1{frac=½}·(fy odd)``
  ======================  =====================================

  (Equivalence to Definitions 1–3 is proven in tests against eqs. (3)/(4).)
* Randomness enters as an explicit uint32 operand, so kernels are
  deterministic given the key (checkpoint-exact restart) and identical code
  runs inside Pallas (which has no CPU-interpretable PRNG primitive).

Emulation domain (TPU flush-to-zero semantics): XLA on TPU — and the XLA CPU
backend used here — flush float32 *subnormals* to zero, so carrier values
below ``2**-126`` are not reliable.  The engine therefore flushes inputs with
``|x| < 2**-126`` to (signed) zero.  This only affects formats whose
subnormal range dips below float32's normal range (bfloat16: true subnormals
span ``2**-133..2**-127``); it exactly matches real TPU bfloat16 behaviour.
binary8/E4M3/binary16 (the paper's formats) are emulated bit-exactly,
subnormals included.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import schemes as _schemes
from repro.core.formats import FPFormat, get_format
from repro.core.grids import Grid, get_grid
from repro.core.schemes import (ALL_MODES, DETERMINISTIC_MODES,
                                RAND_BITS_CHOICES, STOCHASTIC_MODES,
                                RoundingScheme, get_scheme)

_F32_MANT_BITS = 23
_F32_EXP_BIAS = 127


def _pow2(n):
    """Exact float32 2**n for integer array n with -126 <= n <= 127.

    Built by assembling the exponent field directly; never inexact.
    """
    n = n.astype(jnp.int32)
    bits = (n + _F32_EXP_BIAS) << _F32_MANT_BITS
    return lax.bitcast_convert_type(bits, jnp.float32)


def _exact_scale(x, n):
    """x * 2**n, exact, for integer array n with |n| <= 252.

    Split into two in-range factors so intermediate powers of two stay normal.
    """
    n = n.astype(jnp.int32)
    n1 = n // 2
    n2 = n - n1
    return x * _pow2(n1) * _pow2(n2)


def uint_to_f32(x):
    """Exact float32 of unsigned words below 2**24, through ``int32``.

    Mosaic has no unsigned-to-float conversion, so kernels never cast
    ``uint32`` to float directly; below 2**24 (every caller's range) the
    detour is bit-identical to the direct cast."""
    return lax.bitcast_convert_type(x.astype(jnp.uint32),
                                    jnp.int32).astype(jnp.float32)


def _float_exponent(x):
    """Floor(log2(|x|)) for normal float32; any value < -126 for subnormals.

    We only need the exact exponent for float32-*normal* inputs: for
    float32-subnormal inputs the result is clamped below by the target
    format's ``emin`` anyway (all supported targets have emin >= -126, and
    for emin == -126 the subnormal grid coincides with float32's own grid).
    """
    bits = lax.bitcast_convert_type(x, jnp.uint32).astype(jnp.int32)
    raw_exp = (bits >> _F32_MANT_BITS) & 0xFF
    return jnp.where(raw_exp > 0, raw_exp - _F32_EXP_BIAS, -_F32_EXP_BIAS)


def _narrow_grid(fmt: FPFormat) -> bool:
    """Whether every grid-spacing exponent of ``fmt`` keeps 2^±qe a
    *normal* float32, so a single exact `_pow2` multiply replaces the
    two-step `_exact_scale` (binary8/e4m3/binary16: yes; bfloat16: its
    subnormal-range quantum 2^-133 would flush to zero)."""
    return fmt.quantum_min_exp >= -126 and fmt.emax - fmt.precision < 126


def magnitude_decompose(x, fmt: FPFormat):
    """Decompose |x| on the target rounding grid.

    Returns:
      floor_mag: largest grid magnitude <= |x| (float32, exact).
      quantum:   grid spacing (ulp) at x (float32, exact power of two).
      frac:      (|x| - floor_mag)/quantum in [0, 1) (float32, exact).
      fy:        floor_mag / quantum as float32 integer (< 2**precision).

    For narrow-exponent formats (``_narrow_grid``) the power-of-two
    scalings collapse to one exact multiply each — the products are a
    ≤24-bit integer significand times a normal power of two, so no step
    ever rounds; bit-identical to the generic two-step path.
    """
    x = x.astype(jnp.float32)
    mag = jnp.abs(x)
    qe = _quantum_exponent(x, fmt)
    if _narrow_grid(fmt):
        quantum = _pow2(qe)
        y = mag * _pow2(-qe)
        fy = jnp.floor(y)
        frac = y - fy
        floor_mag = fy * quantum
        return floor_mag, quantum, frac, fy
    y = _exact_scale(mag, -qe)
    fy = jnp.floor(y)
    frac = y - fy
    floor_mag = _exact_scale(fy, qe)
    quantum = _pow2(qe // 2) * _pow2(qe - qe // 2)
    return floor_mag, quantum, frac, fy


def _quantum_exponent(x, fmt: FPFormat):
    """Exponent of the grid spacing at |x| (int32).

    The exponent is clamped to [emin, emax]: the grid has no binades
    beyond emax, so spacing queries above xmax report the top-binade
    quantum (and fixed-point grids, emin == emax, get their uniform
    quantum everywhere).  Rounding outputs are unaffected — beyond xmax
    both neighbours land past the range and the overflow policy decides.
    """
    e = _float_exponent(jnp.abs(x))
    qe = jnp.clip(e, fmt.emin, fmt.emax) - (fmt.precision - 1)
    if not fmt.subnormals:
        qe = jnp.where(e < fmt.emin, jnp.int32(fmt.emin), qe)
    return qe


def _ceil_from_decompose(x, fy, fmt: FPFormat):
    """(fy + 1) * 2**qe, exact, avoiding subnormal intermediates."""
    qe = _quantum_exponent(x, fmt)
    if _narrow_grid(fmt):
        return (fy + 1.0) * _pow2(qe)
    return _exact_scale(fy + 1.0, qe)


def _p_round_up(mode, frac, fy, sign_x, eps, sign_v):
    """Probability of rounding the magnitude away from zero (unified rule).

    Delegates to the :mod:`repro.core.schemes` registry — each scheme
    declares its own ``p_up``; this wrapper is the engine/kernel entry
    point (and the back-compat name).
    """
    return get_scheme(mode).p_up(frac, fy, sign_x, eps, sign_v)


def _uniform_from_bits(bits, rand_bits: int = 32,
                       randomness: str = "uniform"):
    """Random bits -> uniform float32 in [0, 1).

    ``randomness="uniform"`` (SR/SRε/signed-SRε):

    * ``rand_bits=32`` (default): ``bits`` is a full uint32 word; the top
      24 bits give a uniform with float32-exact resolution — the
      legacy/oracle derivation, bit-compatible with every pre-existing
      stream.
    * ``rand_bits∈{8, 16}`` (few-random-bits SR, Fitzgibbon & Felix 2025;
      Xia et al. 2020): ``bits`` holds an ``rand_bits``-bit value in its
      low bits and the uniform is ``(b + ½)·2^-r`` — the half-ulp offset
      centres each probability cell, so the SR round-up probability
      becomes the *nearest* r-bit quantization of ``frac`` and the
      residual bias is bounded by ``2^-(r+1)`` ulp (vs ``2^-r`` for
      truncation).

    ``randomness="comparison"`` (SR 2.0, arXiv 2410.10517): the single
    comparison draw ``u = b·2^-r`` with **no** half-ulp centering —
    ``P(round up) = ceil(frac·2^r)/2^r``, a one-sided away-from-zero
    bias in ``[0, 2^-r)`` ulp.  For ``rand_bits=32`` this coincides with
    the uniform top-24-bit derivation (which is already uncentered).

    ``randomness="bittrick"`` (the `copy_stochastic_` int-add idiom): the
    *complemented* uncentered draw ``u = (b XOR (2^r-1))·2^-r``.  With
    r=16 on the bfloat16 grid the event ``u < frac`` is *exactly* the
    carry out of the low 16 mantissa bits in ``(bits32(x) + b) & mask``
    — the oracle here and the kernels' integer fast path are
    bit-identical given the same random words.  Same one-sided
    ``[0, 2^-r)``-ulp bound as the comparison draw on other grids.
    """
    if randomness == "bittrick":
        mask = jnp.uint32((1 << rand_bits) - 1)
        comp = uint_to_f32((bits & mask) ^ mask)
        return comp * jnp.float32(2.0 ** -rand_bits)
    if rand_bits == 32:
        return uint_to_f32(bits >> jnp.uint32(8)) * jnp.float32(2.0 ** -24)
    if rand_bits not in RAND_BITS_CHOICES:
        raise ValueError(f"rand_bits must be one of {RAND_BITS_CHOICES}, "
                         f"got {rand_bits}")
    mask = jnp.uint32((1 << rand_bits) - 1)
    low = uint_to_f32(bits & mask)
    if randomness == "comparison":
        return low * jnp.float32(2.0 ** -rand_bits)
    return (low + jnp.float32(0.5)) * jnp.float32(2.0 ** -rand_bits)


def round_to_format(
    x,
    fmt,
    mode: str = "rn",
    *,
    key: Optional[jax.Array] = None,
    bits: Optional[jax.Array] = None,
    eps: float = 0.0,
    v: Optional[jax.Array] = None,
    overflow: str = "saturate",
    rand_bits: int = 32,
):
    """Round float32 array ``x`` onto the grid of ``fmt`` using ``mode``.

    Args:
      x: input array (cast to float32).
      fmt: Grid, FPFormat, or any grid name (``"binary8"``, ``"fxp16.8"``).
      mode: a registered scheme name (``schemes.ALL_MODES``).
      key: PRNG key for stochastic modes (ignored if ``bits`` given).
      bits: uint32 array, same shape as x, of random bits (stochastic modes).
        With ``rand_bits < 32`` only the low ``rand_bits`` bits are consumed.
      eps: the ε of SRε / signed-SRε (paper Definitions 2/3), in (0, 1).
      v: bias-direction array for signed-SRε (paper's ``v``; e.g. the gradient
        component matching each x element).  ``sign(v)==0`` degrades to SR.
      overflow: "saturate" (clamp to ±xmax; default) or "inf".
      rand_bits: random bits consumed per element (32, 16 or 8); see
        ``_uniform_from_bits`` for the few-random-bits SR / SR 2.0
        comparison-draw semantics.

    Returns:
      float32 array of values exactly representable on the grid.
    """
    grid = get_grid(fmt)
    scheme = get_scheme(mode)
    fmt = grid.fmt
    x = jnp.asarray(x, jnp.float32)

    if scheme.stochastic:
        if bits is None:
            if key is None:
                raise ValueError(f"mode {mode!r} needs `key` or `bits`")
            bits = jax.random.bits(key, x.shape, jnp.uint32)
        u = _uniform_from_bits(bits, rand_bits, scheme.randomness)
    else:
        u = jnp.full(x.shape, 0.5, jnp.float32)

    if scheme.needs_v:
        if v is None:
            raise ValueError(f"{scheme.name} requires the bias-direction `v`")
        sign_v = jnp.sign(jnp.broadcast_to(jnp.asarray(v, jnp.float32), x.shape))
    else:
        sign_v = jnp.zeros_like(x)

    # shifted grids: round (x − μ)/scale on the inner grid, map back below
    z = grid.to_grid(x)
    # TPU/XLA-CPU FTZ: flush float32-subnormal inputs to signed zero.
    z = jnp.where(jnp.abs(z) < jnp.float32(2.0 ** -126), z * 0.0, z)

    floor_mag, _, frac, fy = magnitude_decompose(z, fmt)
    # ceil neighbour computed by exact scaling so it stays float32-normal
    # even where the grid spacing itself would be float32-subnormal.
    ceil_mag = _ceil_from_decompose(z, fy, fmt)
    sign_x = jnp.sign(z)
    p_up = scheme.p_up(frac, fy, sign_x, jnp.float32(eps), sign_v)

    go_up = u < p_up
    mag = jnp.where(go_up, ceil_mag, floor_mag)
    # Exactly-representable input: both neighbours coincide with x.
    mag = jnp.where(frac == 0.0, jnp.abs(z), mag)

    xmax = jnp.float32(fmt.xmax)
    if overflow == "saturate":
        mag = jnp.minimum(mag, xmax)
    elif overflow == "inf":
        mag = jnp.where(mag > xmax, jnp.float32(jnp.inf), mag)
    else:
        raise ValueError(f"unknown overflow policy {overflow!r}")

    out = jnp.where(sign_x < 0, -mag, mag)  # preserves +0 for x == +0
    out = jnp.where(jnp.signbit(z) & (z == 0), -jnp.float32(0.0), out)
    out = grid.from_grid(out)
    # NaN / inf pass through.
    finite = jnp.isfinite(x)
    return jnp.where(finite, out, x)


def floor_ceil(x, fmt) -> Tuple[jax.Array, jax.Array]:
    """True directed floor/ceil (⌊x⌋, ⌈x⌉) on the grid (paper §2.2)."""
    down = round_to_format(x, fmt, "rd")
    up = round_to_format(x, fmt, "ru")
    return down, up


def ulp(x, fmt):
    """Grid spacing ⌈x⌉-⌊x⌋ at x in carrier units (0 only for non-finite
    x).  ``fmt`` may be any Grid/format/grid name — shifted grids scale
    the inner quantum (the monitor's deadband predicate asks the grid)."""
    return get_grid(fmt).ulp(x)


def is_representable(x, fmt):
    """Whether each element of x is exactly representable on the grid."""
    grid = get_grid(fmt)
    x = jnp.asarray(x, jnp.float32)
    z = grid.to_grid(x)
    _, _, frac, _ = magnitude_decompose(z, grid.fmt)
    in_range = jnp.abs(z) <= grid.fmt.xmax
    return ((frac == 0.0) & in_range) | ~jnp.isfinite(x)


def _successor_fmt(x, fmt: FPFormat):
    """su(x) on an *untransformed* format grid (the engine primitive).

    For grid points the step up is: the local quantum when x >= 0 (the
    decomposition at ``|x| = 2**E`` already yields the *upper*-side spacing),
    and the *lower*-side spacing when x < 0 (half the quantum at binade
    boundaries above the subnormal range).
    """
    x = jnp.asarray(x, jnp.float32)
    _, q, frac, fy = magnitude_decompose(x, fmt)
    e = _float_exponent(jnp.abs(x))
    boundary = (fy == 2.0 ** (fmt.precision - 1)) & (e > fmt.emin)
    q_below = jnp.where(boundary, q * 0.5, q)
    succ_exact = jnp.where(x >= 0, x + q, x + q_below)
    out = jnp.where(frac == 0.0, succ_exact, round_to_format(x, fmt, "ru"))
    return jnp.where(jnp.isfinite(x), out, x)


def successor(x, fmt):
    """su(x): smallest grid value strictly greater than x (paper eq. 10)."""
    grid = get_grid(fmt)
    if not grid.transformed:
        return _successor_fmt(x, grid.fmt)
    return grid.from_grid(_successor_fmt(grid.to_grid(x), grid.fmt))


def predecessor(x, fmt):
    """pr(x): largest grid value strictly smaller than x (paper eq. 10)."""
    grid = get_grid(fmt)
    x = jnp.asarray(x, jnp.float32)
    if not grid.transformed:
        return -_successor_fmt(-x, grid.fmt)
    return grid.from_grid(-_successor_fmt(-grid.to_grid(x), grid.fmt))


# ---------------------------------------------------------------------------
# RoundingSpec: the (grid, scheme, params) bundle — the framework's config
# unit.  One canonical string form (core/schemes.py grammar) serves every
# registry: precision/policy, dist/codecs, optim/accumulate, health/watchdog
# and the launch CLI.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class RoundingSpec:
    """A rounding policy: grid + scheme + ε + randomness budget + overflow.

    ``fmt`` holds the *grid name* (any `core.grids` name: an FP format,
    an ``fxpW.F`` fixed-point grid, or a registered custom grid) — None
    means "keep full precision" (identity), which is how the fp32
    baseline is expressed uniformly in the optimizer/trainer.  ``mode``
    holds the *scheme name* (`core.schemes` registry).  The resolved
    objects are available as ``.grid`` / ``.scheme``.

    ``rand_bits`` is the number of random bits a *stochastic* scheme
    consumes per rounded element (32 = the legacy full-word streams; 16/8
    = the few-random-bits SR regime — the PRNG kernels then draw 2×/4×
    fewer PRF words per output tile, at a residual bias ≤
    ``2^-(rand_bits+1)`` ulp centered, ``< 2^-rand_bits`` one-sided for
    SR 2.0's comparison draw).  Deterministic schemes ignore it.

    ``overflow``: "saturate" (clamp to ±xmax, the default) or "inf"
    (overflow to ±inf — the IEEE-style diagnosing variant).
    """

    fmt: Optional[str] = None
    mode: str = "rn"
    eps: float = 0.0
    rand_bits: int = 32
    overflow: str = "saturate"

    def __post_init__(self):
        if self.rand_bits not in RAND_BITS_CHOICES:
            raise ValueError(f"rand_bits must be one of {RAND_BITS_CHOICES}, "
                             f"got {self.rand_bits}")
        if self.overflow not in ("saturate", "inf"):
            raise ValueError(f"overflow must be 'saturate' or 'inf', "
                             f"got {self.overflow!r}")
        get_scheme(self.mode)    # raise early on unknown scheme names

    @property
    def is_identity(self) -> bool:
        return self.fmt is None

    @property
    def stochastic(self) -> bool:
        return (not self.is_identity) and get_scheme(self.mode).stochastic

    @property
    def grid(self) -> Optional[Grid]:
        return None if self.fmt is None else get_grid(self.fmt)

    @property
    def scheme(self) -> RoundingScheme:
        return get_scheme(self.mode)

    def format(self) -> Optional[FPFormat]:
        """The grid's engine descriptor (an FPFormat, degenerate for fxp)."""
        return None if self.fmt is None else get_grid(self.fmt).fmt

    def __str__(self) -> str:
        return _schemes.format_spec_name(
            None if self.fmt is None else get_grid(self.fmt).name,
            self.scheme.name, self.eps, self.rand_bits, self.overflow)

    def __call__(self, x, *, key=None, bits=None, v=None):
        if self.is_identity:
            return jnp.asarray(x, jnp.float32)
        return round_to_format(
            x, self.fmt, self.mode, key=key, bits=bits, eps=self.eps, v=v,
            rand_bits=self.rand_bits, overflow=self.overflow)


IDENTITY = RoundingSpec(None)


def spec(fmt=None, mode="rn", eps=0.0, rand_bits: int = 32,
         overflow: str = "saturate") -> RoundingSpec:
    """Convenience constructor (grid/scheme names canonicalized)."""
    return RoundingSpec(None if fmt is None else get_grid(fmt).name,
                        get_scheme(mode).name, eps, rand_bits, overflow)


def parse_spec(name: str) -> RoundingSpec:
    """Canonical name -> RoundingSpec (``parse_spec(str(s)) == s``).

    The single string grammar every registry consumes — see
    `core/schemes.py`: ``"binary8-sr"``, ``"fxp16.8-sr2"``,
    ``"bf16-ssr-e0.4"``, ``"e4m3-sr-r8"``, ``"binary8-rn-inf"``,
    ``"fp32"``.
    """
    p = _schemes.parse_spec_name(name)
    return RoundingSpec(p.grid, p.scheme, p.eps, p.rand_bits, p.overflow)
