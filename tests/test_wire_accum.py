"""Rounded-parallelism subsystem tests: wire codecs, rounded collectives,
low-precision gradient accumulation, and the sharded train step.

Single-device tests run in every lane.  Tests suffixed ``_mesh8`` need 8
(fake CPU) devices — the multi-device tier-1 CI lane runs them under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``; on a 1-device
host they skip, and the slow nightly lane re-runs them in a subprocess.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import rounding
from repro.dist import codecs as codecs_lib
from repro.dist.codecs import WireCodec, get_wire_codec, wire_codec_names
from repro.dist.collectives import wire_bytes, wire_reduce
from repro.launch.mesh import make_mesh
from repro.optim.accumulate import (ACCUM_PRESETS, GradAccumulator,
                                    get_accumulator)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src"),
       "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}

mesh8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs 8 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=8)")


def _words(tag=0):
    return codecs_lib.wire_words(jax.random.PRNGKey(7), tag)


# ============================================================ wire codecs ==
def test_codec_registry():
    c = get_wire_codec("int8-rn")
    assert c.kind == "int8" and not c.stochastic and c.bytes_per_elt == 1.0
    c = get_wire_codec("e4m3-sr")
    assert c.kind == "float" and c.stochastic and c.bytes_per_elt == 1.0
    assert get_wire_codec("bf16-sr").bytes_per_elt == 2.0
    assert get_wire_codec(None) is None
    assert get_wire_codec("fp32") is None
    assert get_wire_codec(c) is c
    with pytest.raises(ValueError, match="unknown wire codec"):
        get_wire_codec("int4-sr")
    for name in wire_codec_names():
        if name != "fp32":
            assert get_wire_codec(name).name == name


def test_int8_rn_bit_compat_with_legacy_round():
    """The int8-rn codec must reproduce the historical jnp.round wire."""
    g = jnp.asarray(np.random.default_rng(0).normal(size=512) * 3.0,
                    jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(g)) / 127.0, 1e-30)
    legacy = jnp.clip(jnp.round(g / scale), -127, 127) * scale
    got = get_wire_codec("int8-rn").quantize(g)
    np.testing.assert_array_equal(np.asarray(legacy), np.asarray(got))


def test_rn_wire_deadband_zeroes_small_sr_preserves():
    """Satellite regression: entries below scale/2 vanish under the RN
    wire (the paper's stagnation mechanism) but survive in expectation
    under the SR wire."""
    small = 1e-3                     # scale = 1/127 = 7.9e-3; small < scale/2
    g = jnp.asarray([1.0, small, -small, small], jnp.float32)
    rn = get_wire_codec("int8-rn").quantize(g)
    np.testing.assert_array_equal(np.asarray(rn)[1:], 0.0)
    assert float(rn[0]) == 1.0

    sr = get_wire_codec("int8-sr")
    draws = []
    for k in range(300):
        bits = codecs_lib.codec_bits(sr, _words(k), g.shape)
        draws.append(np.asarray(sr.quantize(g, bits=bits)))
    mean = np.mean(draws, axis=0)
    scale = 1.0 / 127.0
    tol = 5 * (scale / 2) / np.sqrt(300)
    np.testing.assert_allclose(mean, np.asarray(g), atol=tol)


def test_float_codec_sr_unbiased_rn_biased():
    rng = np.random.default_rng(1)
    g = jnp.asarray(rng.uniform(0.5, 1.0, size=2048), jnp.float32)
    c_sr, c_rn = get_wire_codec("e4m3-sr"), get_wire_codec("e4m3-rn")
    bits = codecs_lib.codec_bits(c_sr, _words(), g.shape)
    q = np.asarray(c_sr.quantize(g, bits=bits))
    ulp = np.asarray(rounding.ulp(g, "e4m3"))
    # eq. 3: per-element unbiased; CLT over 2048 elements
    err = (q - np.asarray(g))
    assert abs(err.mean()) < 5 * ulp.mean() / 2 / np.sqrt(g.size)
    # the rounded values sit on the grid
    assert np.all(np.asarray(rounding.is_representable(q, "e4m3")))


def test_signed_sr_wire_bias_shrinks_magnitude():
    """signed-SRε on the wire (v = the gradient itself): E[q] - g has sign
    opposite to g — the paper's Definition-3 descent-direction bias."""
    g = jnp.full((4096,), 0.37, jnp.float32)       # fixed positive value
    c = get_wire_codec("binary8-ssr")
    draws = []
    for k in range(64):
        bits = codecs_lib.codec_bits(c, _words(k), g.shape)
        draws.append(np.asarray(c.quantize(g, bits=bits)))
    bias = np.mean(draws) - 0.37
    ulp = float(rounding.ulp(jnp.float32(0.37), "binary8"))
    assert bias < 0                                 # shrinks toward zero
    assert abs(bias + 0.1 * ulp) < ulp / 2          # ≈ -ε·ulp


def test_wire_reduce_validation():
    g = {"w": jnp.ones((4,))}
    with pytest.raises(ValueError, match="topology"):
        wire_reduce(g, "data", codec=None, topology="ring")
    with pytest.raises(ValueError, match="stochastic"):
        wire_reduce(g, "data", codec="e4m3-sr", words=None)


def test_wire_bytes_model():
    g = {"w": jnp.ones((1000,))}
    total, ratio = wire_bytes(g, "int8-sr", 8)
    assert ratio == pytest.approx(0.25)             # both legs 1 B vs 4 B
    assert total == pytest.approx(2 * 7 / 8 * 1000)
    _, r_bf16 = wire_bytes(g, "bf16-sr", 8)
    assert r_bf16 == pytest.approx(0.5)
    _, r_fp32 = wire_bytes(g, None, 8)
    assert r_fp32 == pytest.approx(1.0)
    # quantized all-reduce: gather phase carries fp32 partial means
    total_ar, r_ar = wire_bytes(g, "int8-sr", 8, topology="allreduce")
    assert r_ar == pytest.approx((1 + 4) / 8)
    assert total_ar == pytest.approx((1 + 4) * 7 / 8 * 1000)
    with pytest.raises(ValueError, match="topology"):
        wire_bytes(g, None, 8, topology="ring")


# ====================================================== accumulation ======
def test_accumulator_registry():
    assert get_accumulator(None).spec.is_identity
    assert get_accumulator("bf16-sr").spec.fmt == "bfloat16"
    assert get_accumulator("bf16-sr-kahan").compensated
    a = GradAccumulator()
    assert get_accumulator(a) is a
    # any canonical spec name resolves through the scheme/grid registries
    # (fp8-rz used to be rejected by the private preset table)
    assert str(get_accumulator("fp8-rz").spec) == "binary8-rz"
    with pytest.raises(ValueError, match="unknown accumulator"):
        get_accumulator("fp8-bogus")
    assert sorted(ACCUM_PRESETS) == sorted(
        ["fp32", "bf16-rn", "bf16-sr", "bf16-sr-kahan", "binary8-sr",
         "e4m3-sr"])


def test_accumulator_fp32_exact():
    acc = get_accumulator("fp32")
    g = {"a": jnp.asarray([1.5, -2.25]), "b": jnp.asarray([[4.0]])}
    st = acc.init(g)
    for i in range(4):
        st = acc.add(st, g, microstep=i)
    out = acc.finalize(st, 4)
    np.testing.assert_array_equal(np.asarray(out["a"]),
                                  np.asarray(g["a"]))
    np.testing.assert_array_equal(np.asarray(out["b"]),
                                  np.asarray(g["b"]))


def test_accumulator_stochastic_needs_words():
    acc = get_accumulator("bf16-sr")
    g = {"a": jnp.ones((2,))}
    with pytest.raises(ValueError, match="stochastic"):
        acc.add(acc.init(g), g)


def _run_accum(preset, g, n):
    """Scan ``n`` adds of the constant microbatch gradient ``g``."""
    acc = get_accumulator(preset)
    words = acc.step_words(jax.random.PRNGKey(3), 0)

    def body(st, i):
        return acc.add(st, {"g": g}, words, i), st.total["g"]

    st, trail = jax.lax.scan(body, acc.init({"g": g}), jnp.arange(n))
    return np.asarray(st.total["g"]), np.asarray(trail)


@pytest.mark.slow
def test_swamping_regression_rn_stalls_sr_tracks():
    """The paper's Fig.-2 stagnation at the accumulator: ~10^4 tiny
    microbatch gradients swamp a bf16-RN running sum (it stops growing
    once ulp(sum)/2 exceeds the addend) while bf16-SR tracks the fp32 sum
    within the eq. 3-5 CLT bound and Kahan compensation tracks to ulps."""
    n, c = 10_000, 1e-4
    g = jnp.full((16,), c, jnp.float32)
    exact = n * c                                   # 1.0

    rn, rn_trail = _run_accum("bf16-rn", g, n)
    sr, _ = _run_accum("bf16-sr", g, n)
    kh, _ = _run_accum("bf16-sr-kahan", g, n)

    # RN: stalls below ~2^-5/ulp threshold and *stops growing* entirely
    assert np.all(rn < 0.1 * exact)
    np.testing.assert_array_equal(rn_trail[6000], rn_trail[-1])

    # SR: unbiased; CLT bound over the fp32 trajectory s_k = k*c
    traj = np.arange(1, n + 1, dtype=np.float32) * c
    ulps = np.asarray(rounding.ulp(jnp.asarray(traj), "bfloat16"))
    std = np.sqrt(np.sum(ulps ** 2) / 4.0)          # var_k <= ulp_k^2/4
    assert np.all(sr > 0.5 * exact)                 # far past the RN stall
    # 16 independent streams: the mean error shrinks by 4x
    assert abs(sr.mean() - exact) < 5 * std / np.sqrt(16) + 1e-6

    # compensated SR: error a few carry-format ulps
    assert np.all(np.abs(kh - exact) < 4 * ulps[-1])


def test_accum_train_step_matches_plain_fp32():
    """accum_steps=4 with the exact fp32 carry reproduces the single-batch
    step (mean of equal-size microbatch means == global mean)."""
    from repro.configs import get_config, reduced
    from repro.launch import steps as steps_lib
    from repro.models import build_model

    cfg = reduced(get_config("smollm-360m"))
    model = build_model(cfg)
    opt = steps_lib.baseline_optimizer(lr=0.05)
    params = model.init(jax.random.PRNGKey(0))
    state = opt.init(params, jax.random.PRNGKey(1))
    rng = np.random.default_rng(2)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)),
                                   jnp.int32)}
    p1, s1, m1 = jax.jit(steps_lib.make_train_step(model, opt))(
        params, state, batch)
    p4, s4, m4 = jax.jit(steps_lib.make_train_step(
        model, opt, accum_steps=4))(params, state, batch)
    assert m4["loss"] == pytest.approx(float(m1["loss"]), abs=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
        # microbatch grads equal the global-batch grads only up to fp
        # roundoff (different reduction shapes), scaled by the lr
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=5e-5)
    assert int(s4.step) == int(s1.step) == 1


# =================================================== multi-device (dp=4) ==
def _tiny_setup(update_path="jnp"):
    from repro.configs import get_config, reduced
    from repro.launch import steps as steps_lib
    from repro.models import build_model

    cfg = reduced(get_config("smollm-360m"))
    model = build_model(cfg)
    opt = steps_lib.paper_optimizer(lr=0.01, update_path=update_path)
    params = model.init(jax.random.PRNGKey(0))
    state = opt.init(params, jax.random.PRNGKey(1))
    rng = np.random.default_rng(5)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)),
                                   jnp.int32)}
    return model, opt, params, state, batch


@mesh8
def test_wire_rn_zeroes_sr_preserves_shard_map_mesh8():
    """Satellite regression through the real collective: a small-gradient
    tree mean-reduced over dp=4 arrives as exact zero through the RN wire
    but survives (in expectation) through the SR wire."""
    mesh = make_mesh((4, 2), ("data", "model"))
    small = 1e-3
    # every participant holds the same tree: a scale-setting entry and
    # sub-deadband entries (scale = 1/127, deadband = scale/2 = 3.9e-3)
    g = jnp.tile(jnp.asarray([[1.0, small, -small, small]], jnp.float32),
                 (4, 1))
    spec = P("data", None)

    def red(codec_name):
        def f(x, w):
            return wire_reduce({"g": x}, "data", codec=codec_name,
                               words=w)["g"]
        return jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(spec, P()), out_specs=spec,
            check_vma=False))

    rn = np.asarray(red("int8-rn")(g, _words()))
    np.testing.assert_array_equal(rn[:, 1:], 0.0)   # deadband: exact zeros
    np.testing.assert_allclose(rn[:, 0], 1.0, rtol=1e-2)

    draws = [np.asarray(red("int8-sr")(g, _words(k))) for k in range(200)]
    mean = np.mean(draws, axis=0)
    tol = 5 * (1 / 127.0 / 2) / np.sqrt(200 * 4)    # 4 participants avg too
    np.testing.assert_allclose(mean, np.asarray(g), atol=tol)


@mesh8
@pytest.mark.parametrize("update_path", ["fused", "jnp"])
def test_sharded_optimizer_step_bit_parity_mesh8(update_path):
    """The rounded optimizer update (eq. 8) has no cross-element
    reductions and partition-invariant PRNG streams, so the same update on
    dp=4-sharded state must be *bitwise* identical to the unsharded one."""
    from repro.dist.sharding import build_param_shardings, set_mesh_axes
    from repro.launch.mesh import mesh_axes_for

    model, opt, params, state, batch = _tiny_setup(update_path)
    grads = jax.tree.map(
        lambda p: jnp.asarray(
            np.random.default_rng(9).normal(size=p.shape) * 1e-3,
            jnp.float32), params)

    p_ref, s_ref = jax.jit(lambda p, g, s: opt.apply(p, g, s))(
        params, grads, state)

    mesh = make_mesh((4, 2), ("data", "model"))
    ax = mesh_axes_for(mesh, batch_size=8)
    sh = build_param_shardings(params, mesh, ax)
    ps = jax.device_put(params, sh)
    gs = jax.device_put(grads, sh)
    ss = state._replace(
        momentum=jax.device_put(state.momentum, sh),
        step=jax.device_put(state.step, NamedSharding(mesh, P())),
        key=jax.device_put(state.key, NamedSharding(mesh, P())))
    # fresh jit: the ambient-mesh branch of the fused path is picked up at
    # trace time (exactly as the trainer traces inside set_mesh_axes)
    with set_mesh_axes(ax), mesh:
        p_sh, s_sh = jax.jit(lambda p, g, s: opt.apply(p, g, s))(ps, gs, ss)
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_sh)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(s_ref.momentum),
                    jax.tree.leaves(s_sh.momentum)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@mesh8
def test_sharded_train_step_parity_mesh8():
    """Full fused-optimizer train step on a dp=4 mesh with wire_spec=None
    vs the unsharded step: identical up to the cross-device gradient
    reduction order (loss to fp32 roundoff, params to ~1 update ulp)."""
    from repro.dist.sharding import build_param_shardings, set_mesh_axes
    from repro.launch import steps as steps_lib
    from repro.launch.mesh import mesh_axes_for

    model, opt, params, state, batch = _tiny_setup("fused")
    train_step = steps_lib.make_train_step(model, opt)
    p_ref, s_ref, m_ref = jax.jit(train_step)(params, state, batch)

    mesh = make_mesh((4, 2), ("data", "model"))
    ax = mesh_axes_for(mesh, batch_size=8)
    sh = build_param_shardings(params, mesh, ax)
    ps = jax.device_put(params, sh)
    ss = state._replace(momentum=jax.device_put(state.momentum, sh))
    bs = jax.device_put(batch, NamedSharding(mesh, P(("data",), None)))
    # fresh jit inside the mesh context (trace-time ambient-mesh branch)
    with set_mesh_axes(ax), mesh:
        p_sh, s_sh, m_sh = jax.jit(train_step)(ps, ss, bs)
        jax.block_until_ready(p_sh)
    # loss: fp32 reduction-order difference only
    assert float(m_sh["loss"]) == pytest.approx(float(m_ref["loss"]),
                                                abs=1e-3)
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_sh)):
        a, b = np.asarray(a), np.asarray(b)
        # identical PRNG streams (jax_threefry_partitionable): params can
        # differ only where the bf16 grad-reduction roundoff flipped an
        # SR draw / grid neighbour — bounded by ~1 update-grid ulp (rare
        # momentum-flip cascades reach a few quanta); the *bitwise* claim
        # for identical grads is test_sharded_optimizer_step_bit_parity
        tol = np.abs(a) * 2.0 ** -6 + 2e-5
        assert np.all(np.abs(a - b) <= tol)


@mesh8
def test_sharded_resume_bit_exact_mesh8(tmp_path):
    """Checkpoint-resume under a sharded mesh + rounded wire is bit-exact:
    the wire/accumulator draws are functions of the checkpointed
    (key, step), so the resumed segment replays the same bits."""
    from repro.data import ShardedPipeline, make_token_pipeline
    from repro.launch import steps as steps_lib
    from repro.launch.mesh import mesh_axes_for
    from repro.dist.sharding import set_mesh_axes
    from repro.train import TrainLoop, TrainLoopConfig

    model, opt, params, state, _ = _tiny_setup()
    mesh = make_mesh((4, 2), ("data", "model"))
    ax = mesh_axes_for(mesh, batch_size=8)
    step = steps_lib.make_train_step(
        model, opt, wire_spec="e4m3-sr", mesh=mesh, ax=ax, accum_steps=2,
        accum_spec="bf16-sr")
    with set_mesh_axes(ax), mesh:
        jitted = jax.jit(step)

    from repro.dist.sharding import build_param_shardings
    p_sh = build_param_shardings(params, mesh, ax)
    rep = NamedSharding(mesh, P())
    o_sh = state._replace(step=rep, key=rep,
                          momentum=build_param_shardings(
                              state.momentum, mesh, ax)
                          if state.momentum != () else ())

    def make_loop(ckpt_dir, total):
        pipe = ShardedPipeline(make_token_pipeline(
            model.cfg.vocab_size, 16, 8, seed=0))

        def step_fn(st, b):
            p_, o_ = st
            with set_mesh_axes(ax), mesh:
                p_, o_, metrics = jitted(p_, o_, b)
            return (p_, o_), metrics

        # state_sharding drives the sharded checkpoint-restore path (the
        # resumed loop below re-places host arrays onto the mesh with it)
        return TrainLoop(step_fn, pipe, (params, state),
                         TrainLoopConfig(total_steps=total,
                                         checkpoint_every=2,
                                         checkpoint_dir=str(ckpt_dir),
                                         log_every=1),
                         state_sharding=(p_sh, o_sh))

    straight = make_loop(tmp_path / "a", 4)
    straight.run()

    part1 = make_loop(tmp_path / "b", 2)
    part1.run()
    resumed = make_loop(tmp_path / "b", 4)   # restores step-2 checkpoint
    resumed.run()

    for a, b in zip(jax.tree.leaves(straight.state[0]),
                    jax.tree.leaves(resumed.state[0])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@mesh8
def test_wire_train_loss_matches_unsharded_mesh8():
    """Acceptance: the rounded-wire sharded step's loss matches the
    unsharded single-batch run within SR noise."""
    from repro.dist.sharding import set_mesh_axes
    from repro.launch import steps as steps_lib
    from repro.launch.mesh import mesh_axes_for

    model, opt, params, state, batch = _tiny_setup()
    mesh = make_mesh((4, 2), ("data", "model"))
    ax = mesh_axes_for(mesh, batch_size=8)
    wired = steps_lib.make_train_step(model, opt, wire_spec="e4m3-sr",
                                      mesh=mesh, ax=ax, accum_steps=2)
    plain = jax.jit(steps_lib.make_train_step(model, opt, accum_steps=2))

    ps, ss = params, state
    pw, sw = params, state
    with set_mesh_axes(ax), mesh:
        jw = jax.jit(wired)
        for i in range(3):
            p_ref, s_ref, m_ref = plain(ps, ss, batch)
            pw, sw, m_w = jw(pw, sw, batch)
            assert float(m_w["loss"]) == pytest.approx(
                float(m_ref["loss"]), abs=0.05), f"step {i}"
            ps, ss = p_ref, s_ref


# ------------------------------------------------- subprocess (nightly) --
def _run(cmd, timeout=900):
    return subprocess.run(cmd, env=ENV, capture_output=True, text=True,
                          timeout=timeout)


@pytest.mark.slow
def test_mesh8_suite_subprocess():
    """Nightly: replay the _mesh8 tests on a faked 8-device host (the
    1-device tier-1 lane skips them)."""
    r = _run([sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
              os.path.join(REPO, "tests", "test_wire_accum.py"),
              "-k", "mesh8 and not subprocess"], timeout=1800)
    assert r.returncode == 0, r.stdout + r.stderr


@pytest.mark.slow
def test_sharded_trainer_cli_subprocess(tmp_path):
    """Acceptance: launch/train.py --mesh 4x2 --gemm-policy binary8-paper
    --wire-spec e4m3-sr --accum-steps 4 trains end to end."""
    r = _run([sys.executable, "-m", "repro.launch.train",
              "--arch", "smollm-360m", "--reduced", "--steps", "2",
              "--batch", "32", "--seq", "16", "--mesh", "4x2",
              "--gemm-policy", "binary8-paper", "--wire-spec", "e4m3-sr",
              "--accum-steps", "4", "--accum-spec", "bf16-sr",
              "--ckpt-dir", str(tmp_path / "ck")])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "steps=2" in r.stdout
    loss = float(r.stdout.split("loss")[1].split()[0])
    assert np.isfinite(loss)
