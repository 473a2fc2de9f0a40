"""What every plain reference shares, whatever the model family: the key
from a seed, per-leaf norms, the paper's rounded SGD update on the
bfloat16 grid, the lower-precision controls of a matrix product, and the
loop that follows a job's first steps from a family's loss and gradient.

A family's reference module (``bench/reference/<name>.py``, named by a
configuration's ``reference`` key) imports these and adds what is its
own:

* ``FIELDS``: configuration key -> the program's ``ModelConfig`` field; a
  dotted field (``"moe.n_experts"``) names a field of a nested config;
* ``init_params(cfg, seed)``: float32 weights in the program's layout;
* ``train_steps(cfg, job, params, batches, seed, control=None)``;
* ``served_token_gaps(cfg, params, prompt, served, length, control=None)``;
* ``train_flops_per_token(cfg, seq)``, ``matmul_params(cfg)``,
  ``cached_values_per_token(cfg)`` and
  ``attn_flops_per_cached_token(cfg)``: the work the metrics count.

Nothing here imports the program under test.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (also above 2**32)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def config_items(cfg):
    """The numeric keys of a configuration, hashable for ``jax.jit``."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool))))


# --------------------------------------------------- low-precision control --
def _ste(quant):
    """``quant`` in the forward pass, identity in the backward pass."""
    @jax.custom_vjp
    def q(x):
        return quant(x)
    q.defvjp(lambda x: (quant(x), None), lambda _, g: (g,))
    return q


def int4_absmax(x):
    """Per-tensor symmetric int4: 15 levels, scale from the largest |x|."""
    s = jnp.max(jnp.abs(x)) / 7.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -7, 7) * s


def fp8_e4m3(x):
    """Round to the OCP FP8 E4M3 grid (nearest, saturating at 448)."""
    return jnp.clip(x, -448.0, 448.0).astype(jnp.float8_e4m3fn).astype(
        jnp.float32)


CONTROLS = {"int4": int4_absmax, "fp8": fp8_e4m3}


def product(control=None):
    """A matrix product ``mm(spec, a, b)``: plain float32 for the
    reference; for its control, computed in the named lower precision:
    both operands and the result rounded to that grid (forward, with a
    straight-through backward)."""
    if control is None:
        return jnp.einsum
    q = _ste(CONTROLS[control])
    return lambda spec, a, b: q(jnp.einsum(spec, q(a), q(b)))


# ------------------------------------------------------------ the update --
def _magnitude_split(z):
    """|z| = floor + frac * ulp on the bfloat16 grid (exact in float32)."""
    bits = jax.lax.bitcast_convert_type(jnp.abs(z), jnp.uint32)
    lo_bits = bits & jnp.uint32(0xFFFF0000)
    lo = jax.lax.bitcast_convert_type(lo_bits, jnp.float32)
    hi = jax.lax.bitcast_convert_type(lo_bits + jnp.uint32(0x10000),
                                      jnp.float32)
    frac = (bits & jnp.uint32(0xFFFF)).astype(jnp.float32) / 65536.0
    return lo, hi, frac


def round_bf16(z, key=None, *, eps=0.0, v=None):
    """Round float32 ``z`` onto the bfloat16 grid.

    ``key=None``: nearest (ties to even).  Otherwise stochastic: away from
    zero with probability ``frac`` (SR), or ``clip(frac - sign(z) sign(v)
    eps)`` (the paper's signed-SR-eps) when ``v`` is given.
    """
    if key is None:
        return z.astype(jnp.bfloat16).astype(jnp.float32)
    lo, hi, frac = _magnitude_split(z)
    p = frac if v is None else jnp.clip(
        frac - jnp.sign(z) * jnp.sign(v) * eps, 0.0, 1.0)
    p = jnp.where(frac == 0.0, 0.0, p)
    up = jax.random.uniform(key, z.shape) < p
    return jnp.sign(z) * jnp.where(up, hi, lo)


def sgd_update(params, mom, grads, key, *, lr, momentum, eps):
    """SGD with momentum and the paper's rounded parameter update on the
    bfloat16 grid (eq. 8): the effective gradient rounded to nearest, the
    step ``lr * g`` stochastically rounded, the subtraction rounded with
    signed-SR-eps biased against the gradient's sign.  Momentum is kept
    in float32."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(key, 2 * len(leaves))
    mom = jax.tree.map(lambda m, g: momentum * m + g, mom, grads)
    new = []
    for i, (x, m) in enumerate(zip(leaves, jax.tree_util.tree_leaves(mom))):
        g_hat = round_bf16(m)
        upd = round_bf16(lr * g_hat, keys[2 * i])
        new.append(round_bf16(x - upd, keys[2 * i + 1], eps=eps, v=g_hat))
    return jax.tree_util.tree_unflatten(treedef, new), mom


def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


def follow_steps(loss_and_grad, job, params, batches, seed):
    """Follow a job's first ``len(batches)`` steps from ``params`` with
    the rounded SGD update; ``loss_and_grad(params, batch)`` gives the
    batch-mean loss and its float32 gradient.  ``seed`` keys the update's
    stochastic rounding.  Returns the loss of each step, the per-leaf
    norms of the first gradient, and the per-leaf norms of the
    parameters' change."""
    mom = jax.tree.map(jnp.zeros_like, params)
    start = params
    losses, g1 = [], None
    key = jax.random.fold_in(seed_key(seed), 0x5EF)
    update = jax.jit(functools.partial(
        sgd_update, lr=job["lr"], momentum=job["momentum"], eps=job["eps"]))
    with jax.default_matmul_precision("highest"):
        for i, b in enumerate(batches):
            loss, g = loss_and_grad(params, b)
            losses.append(float(loss))
            if g1 is None:
                g1 = np.asarray(leaf_norms(g))
            params, mom = update(params, mom, g, jax.random.fold_in(key, i))
            del g
        change = np.asarray(leaf_norms(
            jax.tree.map(jnp.subtract, params, start)))
    return {"losses": losses, "grad_norms": g1, "change_norms": change}
