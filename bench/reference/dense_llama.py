"""Plain reference of a dense Llama-family decoder (SmolLM, Phi-3).

Written from the published architecture, in straightforward ``jax.numpy``
at float32 and ``highest`` matmul precision, with no kernels, no cache
and no batching tricks: token embedding, ``n_layers`` pre-norm blocks
(RMSNorm, rotary GQA causal attention, SwiGLU feed-forward), a final
RMSNorm and the output head (tied to the embedding where the
configuration says so), trained on next-token cross-entropy.

It imports nothing of the program under test.  The weights are made
here from the seed (``init_params``); the benchmark hands the same tree
to the program, so both start from one initialisation without the
reference taking anything the program made.

The parameter tree uses the program's layout (``embed``, ``blocks/attn``
stacked over layers, ``final_norm``, ``lm_head``): that layout is the
program's interface for weights, and the benchmark fills it.
RMSNorm scales are stored as offsets from one (``x * (1 + scale)``),
which is the published ``x * weight`` with ``weight`` initialised to one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops
from bench.reference.common import (config_items, follow_steps, product,
                                    seed_key)

# configuration keys -> the program's ModelConfig fields
FIELDS = {"hidden_size": "d_model", "num_hidden_layers": "n_layers",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
          "intermediate_size": "d_ff", "vocab_size": "vocab_size",
          "tie_word_embeddings": "tie_embeddings",
          "rope_theta": "rope_theta", "sliding_window": "sliding_window"}


# ------------------------------------------------------------------ work --
def matmul_params(cfg) -> int:
    """Weights that take part in a matrix product per token: the
    projections of every layer and the output head (an embedding lookup
    is no product)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f = cfg["intermediate_size"]
    per_layer = d * nh * hd + 2 * d * nkv * hd + nh * hd * d + 3 * d * f
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def train_flops_per_token(cfg, seq: int) -> float:
    """Forward and backward model FLOPs per trained token
    (``flops.train_flops_per_token``): every head's query-key and
    probability-value products are ``head_dim`` wide."""
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    return flops.train_flops_per_token(
        matmul_params(cfg), cfg["num_hidden_layers"], width, width, seq,
        cfg["sliding_window"])


def cached_values_per_token(cfg) -> int:
    """Values the KV cache holds for one token over all layers: a key and
    a value of ``head_dim`` for every kv head."""
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * cfg["head_dim"])


def attn_flops_per_cached_token(cfg) -> float:
    """FLOPs one decoded token spends on one cached token over all
    layers: QK^T and PV of every query head, 2 FLOPs a multiply-add."""
    return 2.0 * cfg["num_attention_heads"] * cfg["head_dim"] * 2 \
        * cfg["num_hidden_layers"]


# ------------------------------------------------------------ parameters --
def param_shapes(cfg):
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, v, n = cfg["intermediate_size"], cfg["vocab_size"], \
        cfg["num_hidden_layers"]
    blocks = {"norm1": (n, d), "norm2": (n, d),
              "attn": {"wq": (n, d, nh * hd), "wk": (n, d, nkv * hd),
                       "wv": (n, d, nkv * hd), "wo": (n, nh * hd, d)},
              "mlp": {"w_gate": (n, d, f), "w_up": (n, d, f),
                      "w_down": (n, f, d)}}
    shapes = {"embed": (v, d), "blocks": {"attn": blocks},
              "final_norm": (d,)}
    if not cfg["tie_word_embeddings"]:
        shapes["lm_head"] = (d, v)
    return shapes


def _init_scale(path, shape, cfg):
    name = path[-1]
    if "norm" in name:
        return 0.0
    if name in ("embed", "lm_head"):
        return 0.02
    if name == "wo":
        return 1.0 / np.sqrt(cfg["num_attention_heads"] * cfg["head_dim"])
    return 1.0 / np.sqrt(shape[-2])          # 1/sqrt(fan-in)


def init_params(cfg, seed: int):
    """Float32 weights from the seed, made on the device in one call (the
    key is an argument, so every seed runs the same compiled program)."""
    shapes = param_shapes(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))

    def make(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for k, (path, shape) in zip(keys, flat):
            names = tuple(getattr(p, "key", p) for p in path)
            scale = _init_scale(names, shape, cfg)
            if scale == 0.0:
                out.append(jnp.zeros(shape, jnp.float32))
            else:
                out.append(jax.random.normal(k, shape, jnp.float32) * scale)
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(seed_key(seed))


# ---------------------------------------------------------------- forward --
def _rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _rope(x, positions, theta):
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions[:, None].astype(jnp.float32) * freqs    # (S, hd/2)
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _block(cfg, mm, x, p, attn=None):
    """One decoder layer on one sequence: x (S, d) -> (S, d).  ``mm``
    computes each weight product, ``attn`` (default ``mm``) the
    attention's QK^T and PV products, as ``mm(spec, a, b)``."""
    attn = mm if attn is None else attn
    S = x.shape[0]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    pos = jnp.arange(S)
    h = _rms_norm(x, p["norm1"], eps)
    q = mm("sd,dn->sn", h, p["attn"]["wq"]).reshape(S, nh, hd)
    k = mm("sd,dn->sn", h, p["attn"]["wk"]).reshape(S, nkv, hd)
    v = mm("sd,dn->sn", h, p["attn"]["wv"]).reshape(S, nkv, hd)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    group = nh // nkv
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = attn("qhd,khd->hqk", q, k) / np.sqrt(hd)
    causal = pos[None, :] <= pos[:, None]
    if cfg["sliding_window"]:
        causal &= pos[None, :] > pos[:, None] - cfg["sliding_window"]
    s = jnp.where(causal[None], s, -jnp.inf)
    a = attn("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    x = x + mm("sd,dn->sn", a.reshape(S, nh * hd), p["attn"]["wo"])
    h = _rms_norm(x, p["norm2"], eps)
    g = jax.nn.silu(mm("sd,dn->sn", h, p["mlp"]["w_gate"]))
    u = mm("sd,dn->sn", h, p["mlp"]["w_up"])
    return x + mm("sd,dn->sn", g * u, p["mlp"]["w_down"])


def sequence_logits(cfg, params, tokens, mm=None, attn=None):
    """Next-token logits of one sequence: (S,) -> (S, vocab)."""
    params = jax.tree.map(lambda w: w.astype(jnp.float32), params)
    mm = jnp.einsum if mm is None else mm
    x = params["embed"][tokens]
    blocks = params["blocks"]["attn"]

    @jax.checkpoint
    def layer(x, p):
        return _block(cfg, mm, x, p, attn), None

    x, _ = jax.lax.scan(layer, x, blocks)
    x = _rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
    head = (params["embed"].T if cfg["tie_word_embeddings"]
            else params["lm_head"])
    return mm("sd,dn->sn", x, head)


def sequence_loss(cfg, params, tokens, labels, mm=None):
    """Mean next-token cross-entropy of one sequence (S,) -> ()."""
    logits = sequence_logits(cfg, params, tokens, mm)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


# ------------------------------------------------------------ serving --
@functools.partial(jax.jit, static_argnames=("cfg_items", "control"))
def _token_gaps(params, seq, nxt, cfg_items, control):
    cfg = dict(cfg_items)
    ref = sequence_logits(cfg, params, seq)
    if control is not None:
        gemm, attn = dict(control).get("gemm"), dict(control).get("attn")
        low = sequence_logits(cfg, params, seq, product(gemm),
                              product(attn))
        nxt = jnp.argmax(low, axis=-1)
    picked = jnp.take_along_axis(ref, nxt[:, None], axis=-1)[:, 0]
    return jnp.max(ref, axis=-1) - picked


def served_token_gaps(cfg, params, prompt, served, length, control=None):
    """For each served token: how far the reference's logit of that token
    lies below the reference's best at its position.  With ``control``
    (``{"gemm": ..., "attn": ...}`` lower precisions) the token at each
    position is instead the one the lower-precision reference puts first,
    and its gap is read from the float32 reference the same way.  The
    sequence is padded at its end to ``length`` (causal: padding changes
    no earlier position), so every request shares one program."""
    toks = list(prompt) + list(served)
    seq = np.zeros((length,), np.int32)
    seq[:len(toks) - 1] = toks[:-1]
    nxt = np.zeros((length,), np.int32)
    nxt[:len(toks) - 1] = toks[1:]
    ctl = None if control is None else tuple(sorted(control.items()))
    with jax.default_matmul_precision("highest"):
        gaps = np.asarray(_token_gaps(params, seq, nxt, config_items(cfg),
                                      ctl))
    lo = len(prompt) - 1
    return gaps[lo:lo + len(served)]


# ------------------------------------------------------------ training --
@functools.partial(jax.jit, static_argnames=("cfg_items", "control"))
def _loss_and_grad(params, tokens, labels, cfg_items, control):
    """Batch-mean loss and its float32 gradient, one sequence at a time."""
    cfg = dict(cfg_items)
    mm = product(control)
    f = jax.value_and_grad(
        lambda p, t, l: sequence_loss(cfg, p, t, l, mm))

    def body(acc, row):
        loss, g = f(params, *row)
        return jax.tree.map(jnp.add, acc, (loss, g)), None

    zero = (jnp.float32(0.0), jax.tree.map(jnp.zeros_like, params))
    (loss, g), _ = jax.lax.scan(body, zero, (tokens, labels))
    n = tokens.shape[0]
    return loss / n, jax.tree.map(lambda x: x / n, g)


def train_steps(cfg, job, params, batches, seed, control=None):
    """Follow the job's first ``len(batches)`` steps from ``params``.

    ``seed`` keys the update's stochastic rounding; ``control`` names the
    lower precision of every GEMM result (None: float32).  Returns the
    loss of each step, the per-leaf norms of the first gradient, and the
    per-leaf norms of the parameters' change."""
    cfg_items = config_items(cfg)
    return follow_steps(
        lambda p, b: _loss_and_grad(p, b["tokens"], b["labels"], cfg_items,
                                    control),
        job, params, batches, seed)
