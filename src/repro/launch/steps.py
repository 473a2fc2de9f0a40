"""Distributed step builders + ShapeDtypeStruct input specs for every
(arch × shape) cell.

``input_specs(cfg, shape)`` returns sharding-annotated ShapeDtypeStructs —
the dry-run lowers against these (no allocation), and the real trainer uses
the same functions to place actual data.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.configs.shapes import SHAPES, ShapeConfig
from repro.core import gd, rounding
from repro.dist.sharding import MeshAxes, activation_spec, \
    build_param_shardings, evenly_divisible_spec, set_mesh_axes
from repro.models import build_model
from repro.optim import qsgd


# ------------------------------------------------------------- optimizers --
def paper_optimizer(lr: float = 1e-3, fmt: str = "bfloat16",
                    update_path: str = "jnp"):
    """The paper's technique as the production update path: SR for the
    stepsize multiply, signed-SRε (ε=0.1, v=gradient) for the subtraction,
    momentum kept on an SR-rounded low-precision grid.

    ``update_path="fused"`` switches the parameter update to the whole-tree
    fused Pallas kernel with in-kernel PRNG (one ``pallas_call`` per step
    for the entire model, 12 B/elt of HBM traffic — EXPERIMENTS.md §Perf);
    "jnp" keeps the per-leaf chain, which shards trivially under pjit."""
    cfg = gd.GDRounding(
        grad=rounding.IDENTITY,              # grads computed in bf16/fp32
        mul=rounding.spec(fmt, "sr"),
        sub=rounding.spec(fmt, "signed_sr_eps", 0.1),
        sub_v="grad")
    return qsgd(lr=lr, momentum=0.9, cfg=cfg,
                momentum_spec=rounding.spec(fmt, "sr"),
                update_path=update_path)


def baseline_optimizer(lr: float = 1e-3):
    """fp32 SGD+momentum baseline (identity rounding)."""
    return qsgd(lr=lr, momentum=0.9)


# -------------------------------------------------------------- step carry --
class StepCarry(NamedTuple):
    """Auxiliary per-step state threaded through the extended train step
    (`make_train_step(loss_scale=..., health=...)`): the dynamic
    loss-scale state and the numeric-health streak counters.  Unused
    slots hold ``()`` (an empty pytree), so the carry checkpoints and
    shards like any other state tree."""

    scale: Any = ()
    health: Any = ()


def init_step_carry(loss_scale=None, health=None) -> StepCarry:
    """Initial carry matching `make_train_step`'s loss_scale/health args."""
    from repro.health import monitor as health_lib
    from repro.optim import scale as scale_lib
    s = scale_lib.resolve_loss_scale(loss_scale)
    h = health_lib.resolve_health(health)
    return StepCarry(
        scale=s if s is not None else (),
        health=health_lib.init_health_state() if h is not None else ())


# ------------------------------------------------------------ step makers --
def _microbatch_split(batch, accum_steps: int):
    """(B, ...) leaves -> (accum_steps, B/accum_steps, ...) scan stacks."""
    def split(x):
        b = x.shape[0]
        if b % accum_steps != 0:
            raise ValueError(
                f"batch {b} not divisible by accum_steps={accum_steps} "
                "(under the rounded wire the split applies to each "
                "participant's local shard: global batch = dp x "
                "accum_steps x microbatch)")
        return x.reshape((accum_steps, b // accum_steps) + x.shape[1:])
    return jax.tree.map(split, batch)


def make_train_step(model, optimizer, *, grad_dtype=jnp.bfloat16,
                    gemm_policy=None, accum_steps: int = 1,
                    accum_spec=None, wire_spec=None, mesh=None,
                    ax: Optional[MeshAxes] = None,
                    wire_topology: str = "reduce_scatter",
                    loss_scale=None, health=None):
    """Mixed-precision train step: the loss is differentiated w.r.t.
    bf16-cast params so gradients (and their cross-device reductions) are
    bf16; the optimizer applies them to the fp32/low-precision master
    params through the paper's rounded update path.

    ``gemm_policy`` (preset name or QuantPolicy) overrides the model
    config's quantized-GEMM policy: every forward/dgrad/wgrad GEMM of the
    step then runs through the rounded Pallas kernels (repro.precision),
    seeded per (step, layer, call site) from the checkpointed optimizer
    key — the end-to-end low-precision training regime of eq. (8a).

    ``accum_steps > 1`` splits the global batch into that many
    microbatches and accumulates their gradients in a ``lax.scan``; the
    running sum is carried on ``accum_spec``'s grid (preset name,
    GradAccumulator, or None = exact fp32; repro.optim.accumulate) —
    bf16-RN is the paper's swamping baseline, the SR carries avoid it.

    ``wire_spec`` (codec name or WireCodec; repro.dist.codecs) turns on
    the explicit rounded gradient wire: the gradient computation then runs
    under ``shard_map`` over the mesh's batch axes, with each participant
    computing microbatch gradients on its local batch shard, accumulating
    locally, and mean-reducing through the rounded collective
    (``wire_topology``: reduce-scatter → rounded shard wire → all-gather,
    or plain all-reduce).  Requires ``mesh`` and ``ax`` (the MeshAxes
    whose ``batch`` axes carry the data-parallel split).  Wire draws are
    seeded per (leaf, step, shard) from the checkpointed optimizer key,
    so sharded resume stays bit-exact.

    ``loss_scale`` (None | initial scale | DynamicLossScale) and
    ``health`` (None | format name | HealthConfig) switch the step to the
    *extended* signature ``(params, opt_state, carry, batch) ->
    (params, opt_state, carry, metrics)`` where ``carry`` is a
    `StepCarry` from `init_step_carry` — the loss is multiplied by the
    carried dynamic scale before differentiation, gradients are unscaled
    after the (accumulated / wire-reduced) sum, overflowed steps are
    skipped with a scale backoff (`optim/scale.py` finally wired in), and
    the numeric-health telemetry of `health/monitor.py` rides the metrics
    dict (``h_*`` keys).  With both left ``None`` the classic 3-arg step
    is returned, bit-identical to before.

    Two ``jax.named_scope``s label the compiled step's operations (their
    ``op_name`` metadata, which a profiler trace carries): ``step.forward``
    around the model loss, so autodiff names the backward pass
    ``transpose(jvp(step.forward))`` and a ``jax.checkpoint`` names the
    recomputed forward ``.../checkpoint/rematted_computation/...``; and
    ``step.update`` around the optimizer update with its tail (unscale,
    finiteness check, skip-step select, health telemetry).
    """
    if gemm_policy is not None:
        model = build_model(dataclasses.replace(model.cfg,
                                                gemm_policy=gemm_policy))
    from repro.optim.accumulate import get_accumulator
    accumulator = get_accumulator(accum_spec)
    from repro.health import monitor as health_lib
    from repro.optim import scale as scale_lib
    scale_on = scale_lib.resolve_loss_scale(loss_scale) is not None
    health_cfg = health_lib.resolve_health(health)
    extras = scale_on or health_cfg is not None

    def cast(p):
        return jax.tree.map(
            lambda x: x.astype(grad_dtype)
            if x.dtype == jnp.float32 else x, p)

    def grads_and_metrics(params, key, step, batch, participant_axes=None,
                          scale=None):
        """Microbatch-accumulated fp32 grads + mean metrics on ``batch``
        (the whole global batch, or one participant's shard of it).

        ``participant_axes``: inside the wire ``shard_map``, the manual
        axes whose ``lax.axis_index`` must fold into the accumulator seed
        words so each participant's carry rounds with an independent
        stream (same decorrelation rule as the wire codec itself)."""
        base_rng = jax.random.fold_in(key, step)

        def one_microbatch(mb, rng):
            def loss_fn(p):
                with jax.named_scope("step.forward"):
                    loss, aux = model.loss_fn(p, mb, rng=rng)
                # differentiate the *scaled* loss; report the true one
                out = loss if scale is None else loss * scale
                return out, (loss, aux)
            (_, (loss, metrics)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(cast(params))
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
            metrics = dict(metrics)
            metrics["loss"] = loss
            return grads, metrics

        if accum_steps == 1:
            return one_microbatch(batch, base_rng)

        micro = _microbatch_split(batch, accum_steps)
        words = accumulator.step_words(key, step)
        if participant_axes is not None and accumulator.stochastic:
            from repro.dist import codecs as codecs_lib
            words = codecs_lib.participant_words(words, participant_axes)

        def scan_body(acc, idx_mb):
            idx, mb = idx_mb
            grads, metrics = one_microbatch(
                mb, jax.random.fold_in(base_rng, idx))
            acc = accumulator.add(acc, grads, words, idx)
            return acc, metrics

        # grads mirror the param tree (f32), so init the carry from params
        acc0 = accumulator.init(params)
        acc, metrics = jax.lax.scan(
            scan_body, acc0,
            (jnp.arange(accum_steps), micro))
        grads = accumulator.finalize(acc, accum_steps)
        metrics = jax.tree.map(lambda m: jnp.mean(m, axis=0), metrics)
        return grads, metrics

    codec = None
    batch_axes: Tuple[str, ...] = ()
    if wire_spec is not None:
        from repro.dist.codecs import get_wire_codec
        codec = get_wire_codec(wire_spec)
    if codec is not None:
        if mesh is None or ax is None:
            raise ValueError("wire_spec needs a mesh and MeshAxes "
                             "(the data-parallel axes to reduce over)")
        batch_axes = tuple(a for a in ax.batch if mesh.shape[a] > 1)
        if not batch_axes:
            codec = None     # single-participant wire: nothing to round

    @jax.named_scope("step.update")
    def apply_update(params, opt_state, carry, grads, metrics):
        """Extended-step tail shared by the plain and wire paths:
        unscale → overflow skip-step + scale update → health telemetry →
        optimizer apply."""
        new_scale = carry.scale
        if scale_on:
            grads = scale_lib.unscale_grads(carry.scale, grads)
        new_params, new_state = optimizer.apply(params, grads, opt_state)
        if scale_on:
            finite = scale_lib.all_finite(grads)
            # overflowed step: keep params + momentum, but advance the
            # step counter / rng key so the retry draws fresh rounding bits
            new_params = scale_lib.maybe_skip_update(finite, new_params,
                                                     params)
            merged = scale_lib.maybe_skip_update(finite, new_state,
                                                 opt_state)
            new_state = merged._replace(step=new_state.step,
                                        key=new_state.key)
            new_scale = scale_lib.update_scale(carry.scale, finite)
            metrics["h_loss_scale"] = carry.scale.scale
            metrics["h_grads_finite"] = finite.astype(jnp.float32)
            metrics["h_skipped"] = (~finite).astype(jnp.float32)
        new_health = carry.health
        if health_cfg is not None:
            new_health, hmetrics = health_lib.observe_health(
                carry.health, params, grads,
                getattr(optimizer, "lr", 1.0), health_cfg)
            metrics.update(hmetrics)
        return (new_params, new_state,
                StepCarry(scale=new_scale, health=new_health), metrics)

    if codec is None:
        def train_step(params, opt_state, batch):
            grads, metrics = grads_and_metrics(
                params, opt_state.key, opt_state.step, batch)
            with jax.named_scope("step.update"):
                new_params, new_state = optimizer.apply(params, grads,
                                                        opt_state)
            return new_params, new_state, metrics

        if not extras:
            return train_step

        def train_step_ex(params, opt_state, carry, batch):
            s = carry.scale.scale if scale_on else None
            grads, metrics = grads_and_metrics(
                params, opt_state.key, opt_state.step, batch, scale=s)
            return apply_update(params, opt_state, carry, grads, metrics)
        return train_step_ex

    # -- explicit rounded-wire path (shard_map over the batch axes) --------
    # The body is *manual over every mesh axis*: batch axes carry the
    # data-parallel split and the explicit rounded collectives; the other
    # axes (``model``) see replicated operands, so the per-shard loss/grad
    # computation is redundantly replicated across them (semantically
    # exact).  The ambient shard_act constraints are therefore disabled
    # inside (a manual region may not mention manual axes).
    from repro.dist import codecs as codecs_lib
    from repro.dist.collectives import wire_reduce

    def wire_body(params, key, step, batch, words, scale):
        with set_mesh_axes(MeshAxes()):
            grads, metrics = grads_and_metrics(
                params, key, step, batch, participant_axes=batch_axes,
                scale=scale if scale_on else None)
        grads = wire_reduce(grads, batch_axes, codec=codec, words=words,
                            topology=wire_topology)
        metrics = jax.tree.map(
            lambda m: jax.lax.pmean(m, batch_axes), metrics)
        return grads, metrics

    def run_wire(params, opt_state, batch, scale):
        words = codecs_lib.wire_words(opt_state.key, opt_state.step)
        batch_spec = jax.tree.map(lambda _: P(batch_axes), batch)
        sharded = jax.shard_map(
            wire_body, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(), params), P(), P(),
                      batch_spec, P(), P()),
            out_specs=(jax.tree.map(lambda _: P(), params), P()),
            check_vma=False)
        return sharded(params, opt_state.key, opt_state.step, batch, words,
                       scale)

    def train_step(params, opt_state, batch):
        grads, metrics = run_wire(params, opt_state, batch,
                                  jnp.float32(1.0))
        with jax.named_scope("step.update"):
            new_params, new_state = optimizer.apply(params, grads, opt_state)
        return new_params, new_state, metrics

    if not extras:
        return train_step

    def train_step_ex(params, opt_state, carry, batch):
        s = carry.scale.scale if scale_on else jnp.float32(1.0)
        grads, metrics = run_wire(params, opt_state, batch, s)
        return apply_update(params, opt_state, carry, grads, metrics)
    return train_step_ex


def make_prefill_step(model):
    def prefill_step(params, batch):
        return model.prefill(params, batch, rng=jax.random.PRNGKey(0))
    return prefill_step


def make_serve_step(model, *, enc_len: int = 0):
    def serve_step(params, caches, tokens, pos, enc_out=None):
        logits, new_caches = model.decode_step(
            params, caches, tokens, pos, enc_out=enc_out)
        next_tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None]
        return next_tok, logits, new_caches
    return serve_step


# ------------------------------------------------------------ input specs --
def _sds(shape, dtype, mesh=None, spec=None):
    if mesh is None:
        return jax.ShapeDtypeStruct(shape, dtype)
    spec = evenly_divisible_spec(spec or P(), shape, mesh)
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def batch_specs(cfg: ModelConfig, shape: ShapeConfig, mesh=None,
                ax: Optional[MeshAxes] = None) -> Dict[str, Any]:
    """Training/prefill batch ShapeDtypeStructs for one cell."""
    B, S = shape.global_batch, shape.seq_len
    bt = tuple(ax.batch) if (ax and ax.batch) else None
    tok_spec = P(bt, None) if mesh else None
    emb_spec = P(bt, None, None) if mesh else None
    out: Dict[str, Any] = {}
    s_text = S
    if cfg.frontend == "vision":
        s_text = S - cfg.frontend_len
        out["vision_embeds"] = _sds((B, cfg.frontend_len, cfg.d_model),
                                    jnp.bfloat16, mesh, emb_spec)
    if cfg.frontend == "audio":
        out["src_embeds"] = _sds((B, S, cfg.d_model), jnp.bfloat16, mesh,
                                 emb_spec)
    out["tokens"] = _sds((B, s_text), jnp.int32, mesh, tok_spec)
    if shape.kind == "train":
        out["labels"] = _sds((B, s_text), jnp.int32, mesh, tok_spec)
    return out


def _cache_sharding_tree(model, caches_shape, mesh, ax: MeshAxes):
    """NamedShardings for a decode-cache spec tree."""
    dp = tuple(ax.batch) if ax.batch else None

    n_model = mesh.shape[ax.model]

    def spec_for(path_leaf):
        path, leaf = path_leaf
        nd = len(leaf.shape)
        # leading dim is layers; batch dim is index 1; shard model-ish dims
        if nd == 5:    # (L, B, S, KV, hd) or (L, B, H, P, N)
            if leaf.shape[3] % n_model != 0 and leaf.shape[2] % n_model == 0:
                # GQA with few KV heads: shard the *sequence* over model
                # (context-parallel decode) instead of replicating
                return P(None, dp, ax.model, None, None)
            return P(None, dp, None, ax.model, None)
        if nd == 4:    # (L, B, S, rank) — MLA compressed cache has no head
            # dim, so shard the *sequence* over the model axis (context-
            # parallel decode); (L, B, W, conv) conv windows fall back to
            # replication via the divisibility filter.
            return P(None, dp, ax.model, None)
        if nd == 3:    # (L, B, D) shift states
            return P(None, dp, None)
        return P(*([None] * nd))

    flat, treedef = jax.tree_util.tree_flatten_with_path(caches_shape)
    shardings = [
        NamedSharding(mesh, evenly_divisible_spec(spec_for(x), x[1].shape,
                                                  mesh))
        for x in flat]
    return jax.tree_util.tree_unflatten(treedef, shardings)


def decode_input_specs(cfg: ModelConfig, shape: ShapeConfig, mesh=None,
                       ax: Optional[MeshAxes] = None):
    """(cache_specs, token_spec, pos, enc_out_spec) for a decode cell."""
    model = build_model(cfg)
    B, S = shape.global_batch, shape.seq_len
    caches_shape = jax.eval_shape(
        lambda: model.init_decode_cache(B, S, dtype=jnp.bfloat16))
    if mesh is not None:
        sh = _cache_sharding_tree(model, caches_shape, mesh, ax)
        caches_shape = jax.tree.map(
            lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
            caches_shape, sh)
    dp = tuple(ax.batch) if (ax and ax.batch) else None
    tokens = _sds((B, 1), jnp.int32, mesh, P(dp, None) if mesh else None)
    enc_out = None
    if cfg.encoder_layers:
        enc_out = _sds((B, S, cfg.d_model), jnp.bfloat16, mesh,
                       P(dp, None, None) if mesh else None)
    return caches_shape, tokens, jnp.int32(S - 1), enc_out


def param_and_opt_specs(cfg: ModelConfig, optimizer, mesh=None,
                        ax: Optional[MeshAxes] = None, serve: bool = False):
    """ShapeDtypeStructs (sharded) for params + optimizer state."""
    model = build_model(cfg)
    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt_shape = jax.eval_shape(
        lambda p: optimizer.init(p, jax.random.PRNGKey(1)), params_shape)
    if mesh is None:
        return params_shape, opt_shape

    p_sh = build_param_shardings(params_shape, mesh, ax, serve=serve)
    params_spec = jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        params_shape, p_sh)

    # optimizer-agnostic rule (mirrors launch/train._state_shardings):
    # fields whose pytree mirrors the params (momentum, Adam moment trees)
    # shard like the params; scalars, keys and flat fused carries replicate
    rep = NamedSharding(mesh, P())
    pstruct = jax.tree_util.tree_structure(params_shape)

    def field_spec(val):
        if isinstance(val, tuple) and val == ():
            return ()
        if jax.tree_util.tree_structure(val) == pstruct:
            sh = build_param_shardings(val, mesh, ax)
            return jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                                  sharding=s), val, sh)
        return jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=rep),
            val)

    opt_spec = type(opt_shape)(*[field_spec(v) for v in opt_shape])
    return params_spec, opt_spec
