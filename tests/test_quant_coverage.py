"""Jaxpr coverage audit: the regression guard for quantized-GEMM coverage.

Under ``binary8-paper`` every weight-bearing GEMM of every model family —
forward AND backward — must run inside the quantized Pallas primitives
(``qmatmul_prng_p`` / ``qmatmul_p`` / the batched variants).  SR's
guarantees are per-operation (Stochastic Rounding 2.0; On Stochastic
Rounding with Few Random Bits), so a single full-precision hole re-admits
the deterministic-rounding stagnation of paper §3.  The audit
(``repro.precision.audit``) taints every param leaf, treats pallas_call as
the sanctioned sink, and flags any leaf reaching a ``dot_general``.

Intentional fp32 sites (EXPERIMENTS.md §Quantized GEMM path, allowlist):

* attention logits / probs contractions — activation-activation GEMMs
  (including the absorbed-MLA ``q_eff·c_kv`` and ``probs·c_kv`` forms);
  they carry no weight taint at all, only norm-scale taint via the
  normalized activations;
* the RWKV data-dependent decay MLP (``decay_a``/``decay_b``) and
  first-token bonus ``u`` — their outputs feed ``exp()`` where an 8-bit
  grid would collapse whole heads;
* SSM depthwise conv / decay / dt / skip scalars — elementwise by design,
  they only touch the SSD state contractions through activations.
"""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, reduced
from repro.models import build_model
from repro.precision import audit

KEY = jax.random.PRNGKey(3)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}

FAMILY_ARCHS = [
    "smollm-360m",          # dense GQA transformer
    "tinyllama-1.1b",       # dense, untied lm_head
    "qwen3-moe-30b-a3b",    # MoE (router + shared + batched routed experts)
    "deepseek-v2-236b",     # MLA (+ MoE)
    "zamba2-1.2b",          # hybrid SSM (mamba + shared_attn)
    "rwkv6-7b",             # RWKV6
    "seamless-m4t-medium",  # encoder-decoder (cross-attention)
]


def _batch(cfg, B=2, S=8):
    tk, vk = jax.random.split(KEY)
    batch = {}
    s_text = S
    if cfg.frontend == "vision":
        s_text = S - cfg.frontend_len
        batch["vision_embeds"] = jax.random.normal(
            vk, (B, cfg.frontend_len, cfg.d_model), jnp.float32) * 0.02
    if cfg.frontend == "audio":
        batch["src_embeds"] = jax.random.normal(
            vk, (B, S, cfg.d_model), jnp.float32) * 0.02
    batch["tokens"] = jax.random.randint(tk, (B, s_text), 0, cfg.vocab_size)
    batch["labels"] = jax.random.randint(tk, (B, s_text), 0, cfg.vocab_size)
    return batch


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_fwd_bwd_weight_gemm_coverage(arch):
    """Zero non-allowlisted param leaves reach a dot_general in the full
    train-loss fwd+bwd jaxpr under binary8-paper."""
    cfg = dataclasses.replace(reduced(get_config(arch)),
                              gemm_policy="binary8-paper")
    model = build_model(cfg)
    params = model.init(KEY)
    batch = _batch(cfg)
    rep = audit.audit_fn(
        lambda p, b: jax.grad(
            lambda q: model.loss_fn(q, b, rng=KEY)[0])(p),
        params, batch)
    audit.assert_coverage(rep, min_quantized_calls=4)


def test_absorbed_mla_decode_coverage():
    """Absorbed-MLA decode (the former ROADMAP open item): the q_eff / o_c
    / wo contractions run through the batched quantized kernels; only the
    attention-score sites (tainted by kv_norm alone) stay fp32."""
    cfg = dataclasses.replace(reduced(get_config("deepseek-v2-236b")),
                              gemm_policy="binary8-paper")
    cfg = dataclasses.replace(
        cfg, mla=dataclasses.replace(cfg.mla, absorb=True))
    model = build_model(cfg)
    params = model.init(KEY)
    caches = model.init_decode_cache(batch=2, max_len=8)
    tok = jnp.zeros((2, 1), jnp.int32)
    rep = audit.audit_fn(
        lambda p, c, t: model.decode_step(p, c, t, 4)[0],
        params, caches, tok)
    audit.assert_coverage(rep, min_quantized_calls=4)
    # the only fp32 reach must be through the allowlisted score sites
    assert {r.rsplit("/", 1)[-1] for r in rep.reached} <= {"kv_norm"}


@pytest.mark.parametrize("arch", ["zamba2-1.2b", "rwkv6-7b"])
def test_decode_step_coverage_recurrent(arch):
    """SSM/RWKV one-token decode also keeps every projection quantized."""
    cfg = dataclasses.replace(reduced(get_config(arch)),
                              gemm_policy="binary8-paper")
    model = build_model(cfg)
    params = model.init(KEY)
    caches = model.init_decode_cache(batch=2, max_len=8)
    tok = jnp.zeros((2, 1), jnp.int32)
    rep = audit.audit_fn(
        lambda p, c, t: model.decode_step(p, c, t, 0)[0],
        params, caches, tok)
    audit.assert_coverage(rep, min_quantized_calls=2)


def test_tied_embedding_logits_site_quantized():
    """The 'embed' allowlist entry exists for the residual-stream gather,
    which makes a tied lm-head regression invisible to the family-level
    audit — so the logits projection is guarded directly: its jaxpr must
    contain NO dot_general at all under the policy (reverting _logits to
    `h @ embed.T` fails here even though `embed` is allowlisted)."""
    from repro.precision.policy import make_ctx
    cfg = dataclasses.replace(reduced(get_config("smollm-360m")),
                              gemm_policy="binary8-paper")
    model = build_model(cfg)
    assert cfg.tie_embeddings
    params = model.init(KEY)
    h = jnp.zeros((2, 4, cfg.d_model), jnp.bfloat16)
    ctx = make_ctx(cfg.gemm_policy, KEY)
    rep = audit.audit_fn(
        lambda p, h_: model._logits(p, h_, quant=ctx), params, h)
    assert rep.n_dot_general == 0, rep.reached
    assert rep.n_quantized_calls >= 1


def test_audit_flags_unrouted_weight_gemms():
    """The guard itself must bite: with no policy every weight GEMM is a
    plain dot_general and the audit reports the big weights."""
    cfg = reduced(get_config("tinyllama-1.1b"))
    model = build_model(cfg)
    params = model.init(KEY)
    batch = _batch(cfg)
    rep = audit.audit_fn(
        lambda p, b: jax.grad(
            lambda q: model.loss_fn(q, b, rng=KEY)[0])(p),
        params, batch)
    names = {r.rsplit("/", 1)[-1] for r in rep.offenders()}
    assert {"wq", "wk", "wv", "wo", "lm_head"} <= names, names


# ------------------------------------------------- shard_map layouts (EP) --
def _run(code: str, timeout=540):
    return subprocess.run([sys.executable, "-c", code], env=ENV,
                          capture_output=True, text=True, timeout=timeout)


_EP_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, jax, jax.numpy as jnp
from repro.configs import get_config, reduced
from repro.dist.sharding import MeshAxes, set_mesh_axes
from repro.models import moe as moe_lib
from repro.precision import audit
from repro.precision import policy as QP

cfg = reduced(get_config("qwen3-moe-30b-a3b"))
cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
    cfg.moe, n_experts=8, top_k=2, capacity_factor=4.0))
params = moe_lib.moe_init(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model),
                      jnp.float32) * 0.1
quant = QP.make_ctx("binary8-paper", jax.random.PRNGKey(7))
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
ax = MeshAxes(mesh=mesh, batch=("data",))
"""


@pytest.mark.slow
def test_moe_ep_training_layout_coverage():
    """shard_map EP (experts over `model`) fwd+bwd: expert GEMMs quantized
    on every shard — no weight leaf reaches a dot_general."""
    code = _EP_PRELUDE + """
def loss(p, x_):
    y, aux = moe_lib.moe_apply(p, x_, cfg, quant=quant)
    return jnp.sum(jnp.square(y.astype(jnp.float32))) + aux

with set_mesh_axes(ax), mesh:
    rep = audit.audit_fn(lambda p, x_: jax.grad(loss)(p, x_), params, x)
audit.assert_coverage(rep, min_quantized_calls=4)
print("OK", sorted({r.rsplit("/", 1)[-1] for r in rep.reached}))
"""
    r = _run(code)
    assert "OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_moe_serving_layout_coverage():
    """shard_map serving layout (experts over `data`, F-TP over `model`):
    the decode-path expert GEMMs are quantized on every shard."""
    code = _EP_PRELUDE + """
cfg = dataclasses.replace(cfg, moe_serve_layout=True)
with set_mesh_axes(ax), mesh:
    rep = audit.audit_fn(
        lambda p, x_: moe_lib.moe_apply(p, x_, cfg, quant=quant)[0],
        params, x)
audit.assert_coverage(rep, min_quantized_calls=3)
print("OK", sorted({r.rsplit("/", 1)[-1] for r in rep.reached}))
"""
    r = _run(code)
    assert "OK" in r.stdout, r.stdout + r.stderr
