"""Statistical validation of the in-kernel PRNG path.

The PRNG kernels draw different bits than the jnp oracle (hardware /
counter-hash vs jax.random), so correctness is statistical, not bitwise:

* the raw bit-planes are uniform (chi-square over byte bins, bit balance,
  cross-stream independence);
* E[fl(x) - x] matches the paper's closed-form bias formulas — 0 for SR
  (Definition 1), sign(x)·ε·ulp for SRε (eq. 3), −sign(v)·ε·ulp for
  signed-SRε (eq. 4) — within CLT bounds;
* Var[fl(x) - x] matches frac·(1−frac)·ulp² for SR (eq. 5 regime);
* structural invariants: determinism in (key, step), block-partition
  invariance, bracketing, and the whole-tree step's bit-mode equivalence
  with the explicit-bits oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gd, rounding
from repro.kernels import common, ops, ref
from repro.kernels.fused_update import fused_qupdate_prng_p
from repro.kernels.qmatmul import qmatmul_prng_p
from repro.kernels.sr_cast import sr_cast_prng_p
from repro.kernels.tree_update import fused_tree_update, tree_ravel

KEY = jax.random.PRNGKey(42)
SEED = common.derive_seed(KEY, 0)


# ------------------------------------------------------------- uniformity --
def _chi_square_uniform(samples, n_bins):
    """Pearson chi-square statistic against the uniform distribution."""
    counts = np.bincount(samples, minlength=n_bins).astype(np.float64)
    expected = samples.size / n_bins
    return float(((counts - expected) ** 2 / expected).sum())


def test_counter_bits_chi_square_bytes():
    """Each byte lane of the counter-hash bits is uniform over 256 bins.

    For k=256 bins the chi-square statistic has ~255 dof; 330 is the
    ~0.1% upper tail — a seed-independent deterministic check (the bits
    are a pure function of (seed, coords)).
    """
    bits = np.asarray(common.counter_bits(
        jnp.uint32(0xDEADBEEF), jnp.uint32(0x12345678), (2048, 128)))
    flat = bits.ravel()
    for shift in (0, 8, 16, 24):
        byte = ((flat >> shift) & 0xFF).astype(np.int64)
        chi2 = _chi_square_uniform(byte, 256)
        assert chi2 < 330.0, (shift, chi2)


def test_counter_bits_bit_balance_and_stream_independence():
    shape = (1024, 128)
    b0 = np.asarray(common.counter_bits(
        jnp.uint32(1), jnp.uint32(2), shape, stream=0)).ravel()
    b1 = np.asarray(common.counter_bits(
        jnp.uint32(1), jnp.uint32(2), shape, stream=1)).ravel()
    n = b0.size
    for bit in range(32):
        p = ((b0 >> bit) & 1).mean()
        assert abs(p - 0.5) < 5.0 / np.sqrt(n), (bit, p)
    u0 = b0.astype(np.float64) / 2 ** 32
    u1 = b1.astype(np.float64) / 2 ** 32
    assert abs(np.corrcoef(u0, u1)[0, 1]) < 5.0 / np.sqrt(n)
    # the pair words of one Threefry call are also independent streams
    w0, w1 = common.counter_bits_pair(jnp.uint32(1), jnp.uint32(2), shape)
    uw0 = np.asarray(w0).ravel().astype(np.float64) / 2 ** 32
    uw1 = np.asarray(w1).ravel().astype(np.float64) / 2 ** 32
    assert abs(np.corrcoef(uw0, uw1)[0, 1]) < 5.0 / np.sqrt(n)


@pytest.mark.parametrize("shape,row0,col0", [
    ((7, 13), 3, 5), ((8, 128), 0, 0), ((3, 5, 9), 2, 7), ((4, 33), 1, None)])
def test_interleaved_words_tpu_formulation_matches(monkeypatch, shape, row0,
                                                   col0):
    """The TPU formulation of the counter words (hash both columns of a
    pair, pick by parity) is bit-identical to the CPU one (hash each pair
    once, interleave); ``col0=None`` runs a traced offset."""
    if len(shape) == 3:
        w = jnp.asarray([[1, 2], [3, 4], [5, 6]], jnp.uint32)
        k0, k1 = w[:, 0][:, None, None], w[:, 1][:, None, None]
    else:
        k0, k1 = jnp.uint32(9), jnp.uint32(8)

    def words():
        if col0 is None:
            return jax.jit(lambda c: common._interleaved_words(
                k0, k1, shape, row0, c, 2))(jnp.int32(11))
        return common._interleaved_words(k0, k1, shape, row0, col0, 2)

    cpu = np.asarray(words())
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    np.testing.assert_array_equal(np.asarray(words()), cpu)


def test_threefry_matches_jax_prf():
    """Our in-kernel Threefry-2x32 is bit-identical to jax.random's PRF."""
    from jax._src.prng import threefry_2x32
    k = jnp.array([123, 456], jnp.uint32)
    c = jnp.arange(64, dtype=jnp.uint32)
    ours0, ours1 = common.threefry2x32(jnp.uint32(123), jnp.uint32(456),
                                       c[:32], c[32:])
    want = np.asarray(threefry_2x32(k, c))
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(ours0), np.asarray(ours1)]), want)


# ---------------------------------------------------- bias/variance (3-5) --
N_MC = 1 << 19          # Monte-Carlo sample count per check
X0 = 1.1                # interior point: binary8 ulp(1.1) = 0.25, frac = 0.4


def _mc_bias(fmt, mode, eps=0.0, v_sign=None, x0=X0):
    """Empirical E[fl(x)-x] on a constant array via the PRNG cast kernel."""
    x = jnp.full((N_MC,), x0, jnp.float32)
    v = None if v_sign is None else jnp.full_like(x, v_sign)
    y = sr_cast_prng_p(x, SEED, fmt, mode, eps=eps, v=v, interpret=True)
    err = np.asarray(y, np.float64) - x0
    return err.mean(), err.var(), float(rounding.ulp(jnp.float32(x0), fmt))


def _clt_tol(var, sigmas=4.0):
    return sigmas * np.sqrt(max(var, 1e-30) / N_MC)


def test_prng_sr_bias_zero():
    """Definition 1: E[SR(x)] = x."""
    mean, var, _ = _mc_bias("binary8", "sr")
    assert abs(mean) < _clt_tol(var)


def test_prng_sr_variance_eq5():
    """Var[SR(x) - x] = frac(1-frac)·ulp² at an interior point."""
    mean, var, q = _mc_bias("binary8", "sr")
    _, _, frac_a, _ = rounding.magnitude_decompose(
        jnp.float32(X0), rounding.get_format("binary8"))
    frac = float(frac_a)
    want = frac * (1.0 - frac) * q * q
    assert abs(var - want) < 0.02 * want


@pytest.mark.parametrize("eps", [0.1, 0.3])
def test_prng_sr_eps_bias_eq3(eps):
    """eq. (3): E[σ^{SRε}(x)] = sign(x)·ε·ulp in the unclipped regime."""
    for x0 in (X0, -X0):
        mean, var, q = _mc_bias("binary8", "sr_eps", eps=eps, x0=x0)
        want = np.sign(x0) * eps * q
        assert abs(mean - want) < _clt_tol(var), (x0, mean, want)


@pytest.mark.parametrize("v_sign", [-1.0, 1.0])
def test_prng_signed_sr_eps_bias_eq4(v_sign):
    """eq. (4): E[σ^{signed-SRε}(x)] = −sign(v)·ε·ulp (descent direction)."""
    eps = 0.2
    mean, var, q = _mc_bias("binary8", "signed_sr_eps", eps=eps,
                            v_sign=v_sign)
    want = -v_sign * eps * q
    assert abs(mean - want) < _clt_tol(var)


def test_prng_bracketing_and_grid():
    """PRNG-mode outputs still land on the format grid, on a neighbour."""
    x = jax.random.normal(KEY, (4096,), jnp.float32)
    y = sr_cast_prng_p(x, SEED, "binary8", "sr", interpret=True)
    assert bool(jnp.all(rounding.is_representable(y, "binary8")))
    lo, hi = rounding.floor_ceil(x, "binary8")
    on_neighbour = (y == lo) | (y == hi)
    assert bool(jnp.all(on_neighbour))


# ------------------------------------------- few-random-bits SR (rand_bits) --
def _mc_bias_rb(fmt, mode, rand_bits, eps=0.0, x0=X0):
    x = jnp.full((N_MC,), x0, jnp.float32)
    y = sr_cast_prng_p(x, SEED, fmt, mode, eps=eps, rand_bits=rand_bits,
                       interpret=True)
    err = np.asarray(y, np.float64) - x0
    return err.mean(), err.var(), float(rounding.ulp(jnp.float32(x0), fmt))


@pytest.mark.parametrize("rand_bits", [8, 16, 32])
def test_prng_sr_bias_zero_at_every_rand_bits(rand_bits):
    """Definition 1 under few-random-bits SR: the residual bias of the
    r-bit uniform (half-offset) is bounded by 2^-(r+1) ulp — E[SR(x)-x]
    stays within CLT noise + that quantization bound at every setting."""
    mean, var, q = _mc_bias_rb("binary8", "sr", rand_bits)
    assert abs(mean) < _clt_tol(var) + q * 2.0 ** -(rand_bits + 1)


@pytest.mark.parametrize("rand_bits", [8, 16, 32])
def test_prng_sr_variance_eq5_at_every_rand_bits(rand_bits):
    mean, var, q = _mc_bias_rb("binary8", "sr", rand_bits)
    _, _, frac_a, _ = rounding.magnitude_decompose(
        jnp.float32(X0), rounding.get_format("binary8"))
    frac = float(frac_a)
    want = frac * (1.0 - frac) * q * q
    assert abs(var - want) < 0.05 * want, (rand_bits, var, want)


@pytest.mark.parametrize("rand_bits", [8, 16])
def test_prng_sr_eps_bias_eq3_at_reduced_rand_bits(rand_bits):
    eps = 0.2
    for x0 in (X0, -X0):
        mean, var, q = _mc_bias_rb("binary8", "sr_eps", rand_bits, eps=eps,
                                   x0=x0)
        want = np.sign(x0) * eps * q
        tol = _clt_tol(var) + q * 2.0 ** -(rand_bits + 1)
        assert abs(mean - want) < tol, (rand_bits, x0, mean, want)


def test_reduced_counter_fields_uniform():
    """chi-square uniformity of the 8-bit reduced fields over 256 bins
    (same 0.1%-tail bound as the full-word byte-lane test)."""
    fields = np.asarray(common.counter_bits_reduced(
        jnp.uint32(0xABCD1234), jnp.uint32(0x9E3779B9), (2048, 128), 8))
    assert fields.max() < 256
    chi2 = _chi_square_uniform(fields.ravel().astype(np.int64), 256)
    assert chi2 < 330.0, chi2


def test_reduced_bits_partition_invariance():
    """Reduced draws are keyed by global word coordinates: results are
    independent of the block partition, like the full-word path."""
    x = jax.random.normal(KEY, (5000,), jnp.float32)
    outs = [np.asarray(sr_cast_prng_p(x, SEED, "binary8", "sr",
                                      block_rows=br, rand_bits=8,
                                      interpret=True))
            for br in (8, 64, 512)]
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])


def test_reduced_bits_word_sharing_structure():
    """One PRF word serves 32/r consecutive columns — the reduced plane is
    exactly the byte/halfword expansion of the packed word grid."""
    w0, w1 = jnp.uint32(11), jnp.uint32(22)
    full = np.asarray(common.counter_bits(w0, w1, (4, 8)))
    red = np.asarray(common.counter_bits_reduced(w0, w1, (4, 32), 8))
    for c in range(32):
        want = (full[:, c // 4] >> (8 * (c % 4))) & 0xFF
        np.testing.assert_array_equal(red[:, c], want)


# -------------------------------------------------- structural invariants --
def test_prng_deterministic_in_key_step():
    x = jax.random.normal(KEY, (3000,), jnp.float32)
    y1 = sr_cast_prng_p(x, common.derive_seed(KEY, 5), "binary8", "sr",
                        interpret=True)
    y2 = sr_cast_prng_p(x, common.derive_seed(KEY, 5), "binary8", "sr",
                        interpret=True)
    y3 = sr_cast_prng_p(x, common.derive_seed(KEY, 6), "binary8", "sr",
                        interpret=True)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    assert np.any(np.asarray(y1) != np.asarray(y3))


def test_prng_block_partition_invariance():
    """Counter bits are keyed by global coordinates, so results don't
    depend on how the array is cut into blocks."""
    x = jax.random.normal(KEY, (5000,), jnp.float32)
    outs = [np.asarray(sr_cast_prng_p(x, SEED, "binary8", "sr",
                                      block_rows=br, interpret=True))
            for br in (8, 64, 512)]
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])


def test_fused_prng_update_statistics():
    """The fused eq.-8 PRNG kernel preserves the signed-SRε descent bias:
    on the 8c step the mean update bias has sign −sign(ĝ)."""
    cfg = gd.GDRounding(sub=rounding.spec("binary8", "signed_sr_eps", 0.25),
                        sub_v="grad")
    n = 1 << 18
    x = jnp.full((n,), X0, jnp.float32)
    g = jnp.full((n,), 1e-12, jnp.float32)    # tiny positive gradient
    out = fused_qupdate_prng_p(x, g, 1.0, SEED, cfg, interpret=True)
    # z = x - t·g ≈ x (exactly representable neighbourhood unchanged);
    # signed-SRε with v = ĝ > 0 biases DOWN by ε·ulp
    err = np.asarray(out, np.float64) - np.asarray(x, np.float64)
    q = float(rounding.ulp(jnp.float32(X0), "binary8"))
    want = -0.25 * q
    assert abs(err.mean() - want) < 6 * q / np.sqrt(n)


def test_fused_prng_streams_differ_across_rounds():
    """The three rounding steps must not share bits: with all three steps
    SR on the same grid, per-element round-up decisions across steps are
    uncorrelated."""
    cfg = gd.make_config("binary8", "sr", "sr", "sr")
    n = 1 << 16
    x = jnp.full((n,), X0, jnp.float32)
    g = jnp.zeros((n,), jnp.float32)
    # with g = 0: ĝ = SR(0) = 0, upd = SR(0) = 0, out = SR(x) — only the
    # third stream is visible; compare against the first stream via a cast
    out = fused_qupdate_prng_p(x, g, 1.0, SEED, cfg, interpret=True)
    cast = sr_cast_prng_p(x, SEED, "binary8", "sr", interpret=True)
    up_fused = (np.asarray(out) > X0).astype(np.float64)
    up_cast = (np.asarray(cast) > X0).astype(np.float64)
    corr = np.corrcoef(up_fused, up_cast)[0, 1]
    assert abs(corr) < 5.0 / np.sqrt(n)


def test_qmatmul_prng_statistics():
    """PRNG-mode rounded GEMM: output on grid, mean error ~ 0 over many
    entries (SR unbiasedness at the matmul emit)."""
    a = jax.random.normal(KEY, (128, 64), jnp.float32) * 0.1
    b = jax.random.normal(jax.random.fold_in(KEY, 1), (64, 128),
                          jnp.float32) * 0.1
    got = qmatmul_prng_p(a, b, SEED, "binary8", "sr", bm=64, bn=64, bk=64,
                         interpret=True)
    assert bool(jnp.all(rounding.is_representable(got, "binary8")))
    prod = np.asarray(a @ b, np.float64)
    err = np.asarray(got, np.float64) - prod
    q = np.asarray(rounding.ulp(jnp.asarray(prod, jnp.float32), "binary8"),
                   np.float64)
    assert np.all(np.abs(err) <= q * (1 + 1e-6))
    assert abs((err / q).mean()) < 0.02


# ----------------------------------------------------- whole-tree step ----
def _tree_problem(n_leaves=7, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), n_leaves)
    shapes = [(257,), (16, 16), (3,), (129, 5), (1,), (64,), (10, 2, 3)]
    params = {f"p{i}": jax.random.normal(k, s)
              for i, (k, s) in enumerate(zip(ks, shapes))}
    grads = jax.tree.map(lambda x: 0.1 * x + 0.01, params)
    return params, grads


def test_tree_update_bits_mode_matches_oracle():
    """Explicit-bits whole-tree step == jnp oracle on the concatenation."""
    cfg = gd.make_config("binary8", "sr", "sr", "sr")
    params, grads = _tree_problem()
    out = fused_tree_update(params, grads, 0.05, cfg, KEY, 9, mode="bits",
                            interpret=True)
    xf, spec = tree_ravel(params)
    gf, _ = tree_ravel(grads)
    bits3 = jax.random.bits(jax.random.fold_in(KEY, 9), (3, xf.size),
                            jnp.uint32)
    want = ref.fused_qupdate_ref(xf, gf, 0.05, bits3, cfg)
    got, _ = tree_ravel(out)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_tree_update_prng_mode_shapes_grid_determinism():
    cfg = gd.make_config("binary8", "rn", "sr", "signed_sr_eps",
                         eps_8c=0.1)
    params, grads = _tree_problem(seed=3)
    out1 = fused_tree_update(params, grads, 0.05, cfg, KEY, 2, mode="prng",
                             interpret=True)
    out2 = fused_tree_update(params, grads, 0.05, cfg, KEY, 2, mode="prng",
                             interpret=True)
    assert jax.tree.map(lambda x: x.shape, out1) == \
        jax.tree.map(lambda x: x.shape, params)
    f1, _ = tree_ravel(out1)
    f2, _ = tree_ravel(out2)
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
    assert bool(jnp.all(rounding.is_representable(f1, "binary8")))


def test_tree_update_issues_exactly_one_pallas_call():
    """The whole point of the whole-tree step: ONE kernel launch for any
    pytree (and none of the explicit-bits streams in PRNG mode)."""
    cfg = gd.make_config("binary8", "sr", "sr", "sr")
    params, grads = _tree_problem(seed=1)
    closed = jax.make_jaxpr(
        lambda p, g: fused_tree_update(p, g, 0.05, cfg, KEY, 0,
                                       mode="prng", interpret=True)
    )(params, grads)
    names = [e.primitive.name for e in closed.jaxpr.eqns]
    assert names.count("pallas_call") == 1, names


def test_optimizer_fused_path_converges():
    """QSGD on the fused whole-tree path solves the quadratic, like the
    jnp path does (statistical equivalence at the optimizer level)."""
    from repro.optim import qsgd
    rng = np.random.default_rng(0)
    xstar = rng.normal(size=32).astype(np.float32)
    params = {"w": jnp.asarray(xstar + 3 * rng.normal(size=32)
                               .astype(np.float32)),
              "b": jnp.asarray(rng.normal(size=4).astype(np.float32))}

    def loss(p):
        return (0.5 * jnp.sum((p["w"] - xstar) ** 2)
                + 0.5 * jnp.sum(p["b"] ** 2))

    cfg = gd.make_config("binary8", "rn", "sr", "sr")
    opt = qsgd(lr=0.5, cfg=cfg, update_path="fused")
    state = opt.init(params, KEY)
    step = jax.jit(lambda p, s: opt.apply(p, jax.grad(loss)(p), s))
    l0 = float(loss(params))
    for _ in range(200):
        params, state = step(params, state)
    assert float(loss(params)) < 0.05 * l0
