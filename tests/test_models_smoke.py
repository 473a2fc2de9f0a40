"""Per-architecture smoke tests: REDUCED configs of the same family, one
forward/train step + one decode step on CPU, asserting shapes and finiteness
(as required by the assignment)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, get_config, reduced
from repro.models import build_model

KEY = jax.random.PRNGKey(0)
B, S = 2, 16


def _batch(cfg):
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


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_forward_loss_and_grad(arch):
    cfg = reduced(get_config(arch))
    model = build_model(cfg)
    params = model.init(KEY)
    batch = _batch(cfg)
    loss, metrics = model.loss_fn(params, batch, rng=KEY)
    assert loss.shape == ()
    assert np.isfinite(float(loss)), arch
    # gradient flows through every block type
    g = jax.grad(lambda p: model.loss_fn(p, batch, rng=KEY)[0])(params)
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                      for x in jax.tree_util.tree_leaves(g)))
    assert np.isfinite(float(gn)) and float(gn) > 0, arch


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_decode_step(arch):
    cfg = reduced(get_config(arch))
    model = build_model(cfg)
    params = model.init(KEY)
    caches = model.init_decode_cache(batch=B, max_len=32)
    enc_out = None
    if cfg.encoder_layers:
        enc_out = jax.random.normal(KEY, (B, 8, cfg.d_model),
                                    jnp.float32) * 0.02
    tok = jnp.zeros((B, 1), jnp.int32)
    logits, caches = model.decode_step(params, caches, tok, 0,
                                       enc_out=enc_out)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits, np.float32))), arch
    # a second step advances lengths
    logits2, caches2 = model.decode_step(params, caches, tok, 1,
                                         enc_out=enc_out)
    for t, c in caches2.items():
        if hasattr(c, "length"):
            assert int(np.asarray(c.length).max()) == 2


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_prefill_emits_caches(arch):
    cfg = reduced(get_config(arch))
    model = build_model(cfg)
    params = model.init(KEY)
    batch = _batch(cfg)
    batch.pop("labels")
    logits, caches = model.prefill(params, batch, rng=KEY)
    assert logits.shape[0] == B and logits.shape[-1] == cfg.vocab_size
    assert caches, arch
    for t, c in caches.items():
        for leaf in jax.tree_util.tree_leaves(c):
            assert np.all(np.isfinite(np.asarray(leaf, np.float32))), (arch, t)


def test_decode_matches_forward_dense():
    """Teacher-forced decode must reproduce the full-sequence forward
    (dense GQA path; validates cache correctness)."""
    cfg = reduced(get_config("tinyllama-1.1b"))
    model = build_model(cfg)
    params = model.init(KEY)
    toks = jax.random.randint(KEY, (1, 8), 0, cfg.vocab_size)
    h, _, _ = model.hidden_states(params, {"tokens": toks})
    full_logits = model._logits(params, h)  # (1, 8, V)

    caches = model.init_decode_cache(batch=1, max_len=8)
    outs = []
    for t in range(8):
        lg, caches = model.decode_step(params, caches, toks[:, t:t + 1], t)
        outs.append(lg[:, 0])
    dec_logits = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full_logits, np.float32),
                               np.asarray(dec_logits, np.float32),
                               rtol=0.05, atol=0.05)


def test_decode_matches_forward_ssm():
    """Same equivalence for the Mamba2 path (chunked-scan vs step)."""
    cfg = reduced(get_config("zamba2-1.2b"))
    model = build_model(cfg)
    params = model.init(KEY)
    toks = jax.random.randint(KEY, (1, 8), 0, cfg.vocab_size)
    h, _, _ = model.hidden_states(params, {"tokens": toks})
    full_logits = model._logits(params, h)

    caches = model.init_decode_cache(batch=1, max_len=8)
    outs = []
    for t in range(8):
        lg, caches = model.decode_step(params, caches, toks[:, t:t + 1], t)
        outs.append(lg[:, 0])
    dec_logits = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full_logits, np.float32),
                               np.asarray(dec_logits, np.float32),
                               rtol=0.05, atol=0.05)


def test_decode_matches_forward_rwkv():
    """And for RWKV6 (chunked wkv vs one-step recurrence)."""
    cfg = reduced(get_config("rwkv6-7b"))
    model = build_model(cfg)
    params = model.init(KEY)
    toks = jax.random.randint(KEY, (1, 8), 0, cfg.vocab_size)
    h, _, _ = model.hidden_states(params, {"tokens": toks})
    full_logits = model._logits(params, h)

    caches = model.init_decode_cache(batch=1, max_len=8)
    outs = []
    for t in range(8):
        lg, caches = model.decode_step(params, caches, toks[:, t:t + 1], t)
        outs.append(lg[:, 0])
    dec_logits = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full_logits, np.float32),
                               np.asarray(dec_logits, np.float32),
                               rtol=0.05, atol=0.05)


def test_param_count_estimates_track_actuals():
    """ModelConfig.param_count_estimate within 2x of the true count on the
    reduced configs (the estimate feeds MODEL_FLOPS in §Roofline)."""
    for arch in ("tinyllama-1.1b", "gemma-7b", "qwen3-moe-30b-a3b"):
        cfg = reduced(get_config(arch))
        model = build_model(cfg)
        params = model.init(KEY)
        actual = model.param_count(params)
        est = cfg.param_count_estimate
        assert 0.4 < est / actual < 2.5, (arch, est, actual)


def test_moe_aux_loss_present():
    cfg = reduced(get_config("qwen3-moe-30b-a3b"))
    model = build_model(cfg)
    params = model.init(KEY)
    _, metrics = model.loss_fn(params, _batch(cfg), rng=KEY)
    assert float(metrics["moe_aux"]) > 0


def test_mla_absorbed_decode_matches_naive():
    """The absorbed-matmul MLA decode (§Perf optimization) must be
    numerically equivalent to the naive decompress-then-attend path."""
    import dataclasses
    cfg = reduced(get_config("deepseek-v2-236b"))
    cfg_abs = dataclasses.replace(
        cfg, mla=dataclasses.replace(cfg.mla, absorb=True))
    m1, m2 = build_model(cfg), build_model(cfg_abs)
    params = m1.init(KEY)
    toks = jax.random.randint(KEY, (2, 6), 0, cfg.vocab_size)
    c1 = m1.init_decode_cache(2, 8)
    c2 = m2.init_decode_cache(2, 8)
    for t in range(6):
        l1, c1 = m1.decode_step(params, c1, toks[:, t:t + 1], t)
        l2, c2 = m2.decode_step(params, c2, toks[:, t:t + 1], t)
    np.testing.assert_allclose(np.asarray(l1, np.float32),
                               np.asarray(l2, np.float32),
                               rtol=0.05, atol=0.05)


def test_init_is_the_same_in_every_process():
    """Parameters depend on the key alone: interpreters with different
    string-hash seeds (``PYTHONHASHSEED``) build identical parameters."""
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    code = ("import hashlib, jax, numpy as np\n"
            "from repro.configs import get_config, reduced\n"
            "from repro.models import build_model\n"
            "cfg = reduced(get_config('zamba2-1.2b'))\n"
            "p = build_model(cfg).init(jax.random.PRNGKey(0))\n"
            "print(hashlib.sha256(b''.join(np.asarray(x).tobytes()\n"
            "      for x in jax.tree.leaves(p))).hexdigest())\n")
    digests = [subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        check=True, timeout=300,
        env={**os.environ, "PYTHONPATH": src, "PYTHONHASHSEED": seed}
    ).stdout for seed in ("0", "1")]
    assert digests[0] and digests[0] == digests[1]
