"""Mixture-of-Experts FFN: shared + routed experts, top-k routing with
capacity.

Two execution paths:

* **shard_map EP path** (active mesh): device (d, m) owns expert set m and
  token shard d.  Because tokens are replicated along the model axis, each
  device builds dispatch buffers for *its own* experts directly (no
  dispatch all-to-all), FSDP-gathers its expert weights' D-shards over
  ``data``, computes, scatters outputs back to token slots, and combines
  with one ``psum`` over ``model`` — per-device FLOPs = top_k·cf-active
  only.  (Leaving the buffers to XLA SPMD replicates the *global* expert
  compute on every chip — a measured 650× FLOP blowup; EXPERIMENTS.md
  §Perf.)
* **dense path** (no mesh / E not divisible): capacity-scatter on one
  device — the functional reference the EP path is tested against.

Capacity per expert per token-shard C = ceil(T_loc·top_k·cf / E);
overflowing tokens are dropped (combine weight zero) — the standard
GShard/Switch discipline.  Load-balance aux loss (Switch eq. 4) returned
alongside.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.dist.sharding import _axes, shard_act
from repro.models import layers as L
from repro.models.ffn import ffn_apply, ffn_init
from repro.precision import policy as QP


def moe_init(key, cfg):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    def expert_stack(k, d_in, d_out):
        keys = jax.random.split(k, m.n_experts)
        return jnp.stack([L.dense_init(kk, d_in, d_out) for kk in keys])
    params = {
        "router": L.dense_init(ks[0], d, m.n_experts, scale=0.02),
        "w_gate": expert_stack(ks[1], d, m.d_expert),
        "w_up": expert_stack(ks[2], d, m.d_expert),
        "w_down": expert_stack(ks[3], m.d_expert, d),
    }
    if m.n_shared:
        params["shared"] = ffn_init(ks[4], d, m.n_shared * m.d_expert,
                                    cfg.ffn_act)
    return params


def _expert_compute(buf, w_gate, w_up, w_down, dtype, quant=None):
    """Batched SwiGLU over stacked experts: (E, C, D) -> (E, C, D).

    The three expert GEMMs run as ONE batched contraction each through
    ``precision.qeinsum`` — with a quant context the batch-gridded rounded
    kernels round every expert's GEMM results, and the expert (batch-slice)
    index is folded into the seed words inside qeinsum so no two experts
    share a bit stream; the post-SwiGLU hidden goes through the act
    rounding site, mirroring ffn_apply.  With ``quant=None`` this is the
    plain einsum path, bit-identical to the unrouted model."""
    gate = jax.nn.silu(QP.qeinsum("ecd,edf->ecf", buf, w_gate.astype(dtype),
                                  quant, QP.TAG_MOE_GATE))
    up = QP.qeinsum("ecd,edf->ecf", buf, w_up.astype(dtype), quant,
                    QP.TAG_MOE_UP)
    h = QP.qact(gate * up, quant, QP.TAG_MOE_ACT)
    return QP.qeinsum("ecf,efd->ecd", h, w_down.astype(dtype), quant,
                      QP.TAG_MOE_DOWN)


def _dispatch_compute_combine(xt, topw, topi, w_gate, w_up, w_down,
                              n_experts, top_k, capacity_factor, dtype,
                              e_offset=0, capacity_experts=None,
                              reduce_fn=None, quant=None):
    """Capacity-scatter → expert FFN → weighted combine on local arrays.

    ``e_offset``/``n_experts`` select the expert window this caller owns
    (the EP path passes its shard; the dense path passes everything).
    ``capacity_experts`` is the *total* expert count for the per-expert
    capacity formula (so EP shards size their buffers correctly)."""
    T, D = xt.shape
    E = n_experts
    ce = capacity_experts or E
    C = max(1, int(T * top_k * capacity_factor / max(ce, top_k)))
    e_all = topi.reshape(-1)                                    # (T*k,)
    local = (e_all >= e_offset) & (e_all < e_offset + E)
    e_flat = jnp.clip(e_all - e_offset, 0, E - 1)
    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32) * local[:, None]
    pos = jnp.cumsum(onehot, axis=0) - onehot
    p_flat = jnp.take_along_axis(pos, e_flat[:, None], axis=1)[:, 0]
    keep = (p_flat < C) & local
    p_flat = jnp.minimum(p_flat, C - 1)

    x_rep = jnp.repeat(xt, top_k, axis=0)                       # (T*k, D)
    buf = jnp.zeros((E, C, D), dtype)
    buf = buf.at[e_flat, p_flat].add(
        jnp.where(keep[:, None], x_rep, 0).astype(dtype))

    out = _expert_compute(buf, w_gate, w_up, w_down, dtype,
                          quant=quant)                          # (E, C, D)
    if reduce_fn is not None:       # TP-within-expert partial-sum combine
        out = reduce_fn(out)

    y_slots = out[e_flat, p_flat]                               # (T*k, D)
    w_flat = topw.reshape(-1) * keep.astype(jnp.float32)
    return (y_slots.astype(jnp.float32) * w_flat[:, None]).reshape(
        T, top_k, D).sum(1).astype(dtype)


def moe_apply(params, x, cfg, router_key=None,
              quant=None) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (y, aux_loss).  ``quant`` routes the router GEMM,
    the shared expert, and the routed experts of ALL THREE execution paths
    (dense, shard_map EP training layout, shard_map serving layout) through
    the rounded-GEMM kernels.  The EP bodies receive the call-site seed
    words as a replicated shard_map operand and fold in their expert-window
    offset (and, for the F-TP serving layout, the model-shard index) so
    expert streams stay globally decorrelated across devices."""
    m = cfg.moe
    B, S, D = x.shape
    dtype = x.dtype
    T = B * S
    xt = x.reshape(T, D)

    logits = L.qdense(xt, params["router"], quant,
                      QP.TAG_ROUTER).astype(jnp.float32)
    if m.router_noise and router_key is not None:
        logits = logits + m.router_noise * jax.random.normal(
            router_key, logits.shape)
    probs = jax.nn.softmax(logits, axis=-1)                     # (T, E)
    topw, topi = jax.lax.top_k(probs, m.top_k)                  # (T, k)
    topw = topw / (jnp.sum(topw, -1, keepdims=True) + 1e-9)

    ax = _axes()
    E = m.n_experts
    serve_layout = getattr(cfg, "moe_serve_layout", False)
    use_ep = (ax.active and ax.mesh.shape[ax.model] > 1
              and (E % ax.mesh.shape[ax.model] == 0 or serve_layout))
    # quant words enter the shard_map bodies as a replicated operand (the
    # policy itself is static and closes over); identity policies pass
    # nothing so the unquantized lowering is untouched
    use_q = quant is not None and not quant.policy.is_identity
    q_args = (quant.words,) if use_q else ()
    y = None
    if use_ep and serve_layout and ax.batch:
        # ----- serving layout: experts over `data`, F-TP over `model` ----
        # Tokens are replicated along model, so each device computes its
        # F-shard of its data-shard's experts for ALL tokens (gathered —
        # tiny at decode), partial-sums over model, and the per-expert
        # contributions sum over data.  No weight movement at all.
        mesh = ax.mesh
        from jax.sharding import PartitionSpec as P
        dp = tuple(a for a in ax.batch if a in mesh.axis_names) or None
        fsdp = tuple(ax.data)
        n_d = 1
        for a in fsdp:
            n_d *= mesh.shape[a]
        if E % n_d == 0:
            E_loc = E // n_d

            def serve_fn(xt_, topw_, topi_, wg_, wu_, wd_, *qw_):
                xt_all = jax.lax.all_gather(xt_, dp, axis=0, tiled=True)
                topw_all = jax.lax.all_gather(topw_, dp, axis=0, tiled=True)
                topi_all = jax.lax.all_gather(topi_, dp, axis=0, tiled=True)
                e0 = jax.lax.axis_index(fsdp[-1]) * E_loc
                q_loc = None
                if use_q:
                    # fold the expert-window offset AND the model-shard
                    # index: each device rounds a distinct F-shard of the
                    # same expert, and the interpret-mode counter hash only
                    # sees local coordinates — without the model fold all
                    # F-shards of one expert would share a bit stream
                    w_loc = QP.fold_words(qw_[0], e0)
                    w_loc = QP.fold_words(w_loc,
                                          jax.lax.axis_index(ax.model))
                    q_loc = QP.QuantCtx(quant.policy, w_loc)
                y_all = _dispatch_compute_combine(
                    xt_all, topw_all, topi_all, wg_, wu_, wd_, E_loc,
                    m.top_k, m.capacity_factor, dtype, e_offset=e0,
                    capacity_experts=E,
                    reduce_fn=lambda o: jax.lax.psum(o, ax.model),
                    quant=q_loc)
                y_all = jax.lax.psum(y_all, dp)        # sum expert owners
                T_loc = xt_.shape[0]
                d_idx = jax.lax.axis_index(dp[-1] if isinstance(dp, tuple)
                                           else dp)
                return jax.lax.dynamic_slice_in_dim(
                    y_all, d_idx * T_loc, T_loc, axis=0)

            tok_spec = P(dp, None)
            y = jax.shard_map(
                serve_fn, mesh=mesh,
                in_specs=(tok_spec, tok_spec, tok_spec,
                          P(fsdp, None, ax.model), P(fsdp, None, ax.model),
                          P(fsdp, ax.model, None)) + (P(),) * len(q_args),
                out_specs=tok_spec, check_vma=False,
            )(xt, topw, topi, params["w_gate"], params["w_up"],
              params["w_down"], *q_args)
        else:
            serve_layout = False
    if use_ep and not serve_layout and E % ax.mesh.shape[ax.model] == 0:
        # ----- training layout: experts over `model` (EP), FSDP over data
        mesh = ax.mesh
        from jax.sharding import PartitionSpec as P
        dp = tuple(a for a in ax.batch if a in mesh.axis_names) or None
        fsdp = tuple(ax.data)
        E_loc = E // mesh.shape[ax.model]

        def local_fn(xt_, topw_, topi_, wg_, wu_, wd_, *qw_):
            # FSDP gather of this shard's expert weights over `data`
            wg_ = jax.lax.all_gather(wg_, fsdp, axis=1, tiled=True)
            wu_ = jax.lax.all_gather(wu_, fsdp, axis=1, tiled=True)
            wd_ = jax.lax.all_gather(wd_, fsdp, axis=2, tiled=True)
            e0 = jax.lax.axis_index(ax.model) * E_loc
            # expert-window fold: qeinsum's per-slice folds are local
            # (0..E_loc), so the window offset keeps streams distinct
            # across the model axis (Threefry folds accept traced tags);
            # the data-axis index is folded too — data shards share e0 and
            # post-gather weights, and without the fold their rounded
            # wgrad partials would draw correlated bits at identical
            # local coordinates before the data-axis reduction
            q_loc = None
            if use_q:
                w_loc = QP.fold_words(qw_[0], e0)
                for a_ in (dp or ()):
                    w_loc = QP.fold_words(w_loc, jax.lax.axis_index(a_))
                q_loc = QP.QuantCtx(quant.policy, w_loc)
            y_ = _dispatch_compute_combine(
                xt_, topw_, topi_, wg_, wu_, wd_, E_loc, m.top_k,
                m.capacity_factor, dtype, e_offset=e0, capacity_experts=E,
                quant=q_loc)
            # combine partial expert outputs across the model axis
            return jax.lax.psum(y_, ax.model)

        tok_spec = P(dp, None)
        y = jax.shard_map(
            local_fn, mesh=mesh,
            in_specs=(tok_spec, tok_spec, tok_spec,
                      P(ax.model, fsdp, None), P(ax.model, fsdp, None),
                      P(ax.model, None, fsdp)) + (P(),) * len(q_args),
            out_specs=tok_spec, check_vma=False,
        )(xt, topw, topi, params["w_gate"], params["w_up"],
          params["w_down"], *q_args)
    if y is None:   # no usable EP layout (incl. serve_layout without a
        # batch axis / indivisible E): single-device dense reference path
        y = _dispatch_compute_combine(
            xt, topw, topi, params["w_gate"], params["w_up"],
            params["w_down"], E, m.top_k, m.capacity_factor, dtype,
            quant=quant)

    if m.n_shared:
        y = y + ffn_apply(params["shared"], xt, cfg.ffn_act, quant=quant)

    # Switch-style load-balance loss
    frac_tokens = jnp.mean(
        jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs)
    return y.reshape(B, S, D), aux
