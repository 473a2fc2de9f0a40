"""Shared plumbing for the quantized optimizers.

Per-leaf, per-step PRNG derivation: every parameter leaf gets an independent
key folded from (base_key, step, leaf_index) so that (a) rounding noise is
i.i.d. across parameters and steps, as the paper's analysis assumes, and
(b) the whole optimizer step is a deterministic function of the checkpointed
(key, step) — checkpoint/restart is bit-exact.

Three parameter-update paths, selected by the optimizer's ``update_path``:

* ``"jnp"``       — per-leaf pure-jnp chain (shards trivially under pjit;
                    the historical default and the cross-path reference);
* ``"fused"``     — ONE Pallas kernel over the flattened tree with
                    in-kernel randomness (12 B/elt; the TPU hot path);
* ``"fused_bits"``— same single kernel fed explicit random-bits operands
                    (24 B/elt; bit-exact vs the jnp oracle on the
                    concatenated vector — the audit mode).
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.gd import GDRounding, _resolve_v
from repro.core.rounding import RoundingSpec

UPDATE_PATHS = ("jnp", "fused", "fused_bits")


def leaf_keys(base_key, step, tree):
    """One key per leaf, folded from (base_key, step, leaf_idx)."""
    leaves = jax.tree_util.tree_leaves(tree)
    stepped = jax.random.fold_in(base_key, step)
    keys = [jax.random.fold_in(stepped, i) for i in range(len(leaves))]
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(tree), keys)


def rounded_param_update(x, g, t, cfg: GDRounding, key):
    """The paper's eq.-8 parameter update for one leaf (pure-jnp path).

    This is semantically identical to kernels.fused_update (which is the
    TPU hot path); the jnp form is used under pjit where the elementwise
    chain shards trivially.
    """
    k1, k2, k3 = jax.random.split(key, 3)
    g_hat = cfg.grad(g, key=k1, v=_resolve_v(cfg.grad_v, g, x))
    upd = cfg.mul(jnp.float32(t) * g_hat, key=k2,
                  v=_resolve_v(cfg.mul_v, g_hat, x))
    z = x - upd
    return cfg.sub(z, key=k3, v=_resolve_v(cfg.sub_v, g_hat, x))


def round_state(spec: RoundingSpec, x, key):
    """Round an optimizer-state leaf onto its storage grid."""
    if spec.is_identity:
        return x
    return spec(x, key=key)


def tree_rounded_update(params, grads, t, cfg: GDRounding, key, step,
                        *, update_path: str = "jnp",
                        interpret: Optional[bool] = None):
    """Eq.-8 rounded update over a whole parameter pytree.

    Dispatches between the per-leaf jnp path and the whole-tree fused
    kernel (one ``pallas_call`` regardless of leaf count; see
    kernels/tree_update.py).
    """
    if update_path == "jnp":
        keys = leaf_keys(key, step, params)
        return jax.tree.map(
            lambda p, g, k: rounded_param_update(p, g, t, cfg, k),
            params, grads, keys)
    if update_path not in ("fused", "fused_bits"):
        raise ValueError(f"unknown update_path {update_path!r}; "
                         f"known: {UPDATE_PATHS}")
    # lazy import: keeps Pallas out of the optimizer's import graph unless
    # a kernel path is actually selected
    from repro.kernels.tree_update import fused_tree_update
    mode = "prng" if update_path == "fused" else "bits"

    def run(p, g, k, s):
        return fused_tree_update(p, g, t, cfg, k, s, mode=mode,
                                 interpret=interpret)

    # Under an ambient mesh the whole-tree pallas_call must not be handed
    # sharded operands: GSPMD has no partitioning rule for it and would
    # feed local shards into a kernel that indexes the global flat tree.
    # Run it inside a replicated shard_map instead — every participant
    # gathers the tree and computes the identical update (the counter-
    # keyed PRNG makes this bitwise equal to the single-device step).
    from repro.dist.sharding import _axes
    ax = _axes()
    if ax.active:
        from jax.sharding import PartitionSpec as P
        pspec = jax.tree.map(lambda _: P(), params)
        return jax.shard_map(
            run, mesh=ax.mesh,
            in_specs=(pspec, pspec, P(), P()), out_specs=pspec,
            check_vma=False)(params, grads, key, step)
    return run(params, grads, key, step)


def tree_rounded_adam_update(params, grads, m, v, scal, cfg: GDRounding,
                             key, step, *, m_spec, v_spec, b1: float,
                             b2: float, packed: bool, cm=None, cv=None,
                             interpret: Optional[bool] = None):
    """Fully-fused QAdam step over a pytree (kernels/tree_update.py),
    with the same replicated-shard_map treatment as tree_rounded_update
    under an ambient mesh.  ``m``/``v`` (and ``cm``/``cv``) are flat
    carries; ``scal`` the (5,) [t, c1, c2, eps, wd] vector.  Returns
    ``(params⁺, m', v', cm', cv')`` (``cm'``/``cv'`` None when
    uncompensated)."""
    from repro.kernels.tree_update import fused_tree_adam_update
    kahan = cm is not None

    def run(p, g, m_, v_, s_, k, st, *comp):
        cm_, cv_ = comp if comp else (None, None)
        p2, m2, v2, cm2, cv2 = fused_tree_adam_update(
            p, g, m_, v_, s_, cfg, k, st, m_spec=m_spec, v_spec=v_spec,
            b1=b1, b2=b2, packed=packed, cm=cm_, cv=cv_,
            interpret=interpret)
        return (p2, m2, v2, cm2, cv2) if kahan else (p2, m2, v2)

    args = (params, grads, m, v, scal, key, step) \
        + ((cm, cv) if kahan else ())
    from repro.dist.sharding import _axes
    ax = _axes()
    if ax.active:
        from jax.sharding import PartitionSpec as P
        pspec = jax.tree.map(lambda _: P(), params)
        in_specs = (pspec, pspec, P(), P(), P(), P(), P()) \
            + ((P(), P()) if kahan else ())
        out_specs = (pspec, P(), P()) + ((P(), P()) if kahan else ())
        res = jax.shard_map(run, mesh=ax.mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)(*args)
    else:
        res = run(*args)
    return res if kahan else res + (None, None)
