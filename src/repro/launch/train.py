"""End-to-end training driver.

Composes: arch config → model → paper-rounded optimizer → synthetic token
pipeline → fault-tolerant TrainLoop (checkpoints, restart, elastic resume),
optionally sharded over an explicit dp×tp mesh with the rounded gradient
wire and low-precision microbatch accumulation.

Examples:
  # CPU-sized smoke run of the full stack
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced \
      --steps 50 --batch 8 --seq 128

  # paper-faithful rounding ablation
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b --reduced \
      --steps 100 --rounding signed_sr_eps --fmt binary8

  # sharded end-to-end low-precision training: dp=4 x tp=2 host-device
  # mesh, e4m3-SR rounded gradient wire (reduce-scatter topology), 4-way
  # microbatch accumulation, quantized GEMMs
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
    python -m repro.launch.train --arch smollm-360m --reduced --steps 10 \
      --mesh 4x2 --gemm-policy binary8-paper --wire-spec e4m3-sr \
      --accum-steps 4 --accum-spec bf16-sr
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, reduced as reduce_cfg
from repro.core import gd, rounding
from repro.data import ShardedPipeline, make_token_pipeline
from repro.launch import steps as steps_lib
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, mesh_axes_for, parse_mesh
from repro.dist.sharding import (build_param_shardings,
                                 evenly_divisible_spec, set_mesh_axes)
from repro.models import build_model
from repro.optim import base as optim_base, qadam, qsgd
from repro.train import TrainLoop, TrainLoopConfig


def rounding_config(kind: str, fmt: str, eps: float) -> gd.GDRounding:
    if kind == "fp32":
        return gd.GDRounding()
    if kind == "rn":
        return gd.make_config(fmt, "rn", "rn", "rn")
    if kind == "sr":
        return gd.make_config(fmt, "rn", "sr", "sr")
    if kind == "sr_eps":
        return gd.GDRounding(grad=rounding.spec(fmt, "rn"),
                             mul=rounding.spec(fmt, "sr_eps", eps),
                             sub=rounding.spec(fmt, "sr"))
    if kind == "signed_sr_eps":
        return gd.GDRounding(grad=rounding.spec(fmt, "rn"),
                             mul=rounding.spec(fmt, "sr"),
                             sub=rounding.spec(fmt, "signed_sr_eps", eps),
                             sub_v="grad")
    # any other registered scheme (sr2, ...): residual step RN, the
    # scheme on the mul/sub sites with its registry defaults
    scheme = rounding.get_scheme(kind)          # raises on unknown kinds
    sp = rounding.spec(fmt, kind, scheme.default_eps,
                       scheme.default_rand_bits)
    if scheme.needs_v:
        return gd.GDRounding(grad=rounding.spec(fmt, "rn"),
                             mul=rounding.spec(fmt, "sr"), sub=sp,
                             sub_v="grad")
    return gd.GDRounding(grad=rounding.spec(fmt, "rn"), mul=sp, sub=sp)


def parse_moments_spec(name):
    """``'bf16-sr[-kahan]'`` -> (RoundingSpec, kahan flag).

    Canonical spec grammar (core/schemes.parse_spec_name) with the same
    optional ``-kahan`` suffix as the accumulator presets; raises on
    unknown grids/schemes, so a bad ``--moments-spec`` dies at launch,
    not at step time."""
    kahan = False
    if name.endswith("-kahan"):
        kahan, name = True, name[: -len("-kahan")]
    return rounding.parse_spec(name), kahan


def build_optimizer(optimizer: str, *, lr, momentum, cfg, update_path,
                    moments_spec=None):
    """The CLI's optimizer factory (also the watchdog-rebuild hook's)."""
    if optimizer == "sgd":
        return qsgd(lr=lr, momentum=momentum, cfg=cfg,
                    update_path=update_path)
    if optimizer != "adam":
        raise ValueError(f"unknown optimizer {optimizer!r}")
    spec, kahan = parse_moments_spec(moments_spec or "fp32")
    # the fully-fused path stores non-fp32 moments as packed grid codes
    packed = update_path == "fused" and not spec.is_identity
    return qadam(lr=lr, cfg=cfg, m_spec=spec, v_spec=spec, kahan=kahan,
                 update_path=update_path, moments_packed=packed)


def _state_shardings(params, opt_state, mesh, ax):
    """(param, opt-state) NamedSharding trees, optimizer-agnostic: any
    opt-state field whose pytree mirrors the params (momentum, Adam m/v
    moment trees, Kahan compensations) shards like the params; everything
    else (step counters, keys, flat fused-path carries) is replicated —
    the whole-tree fused kernel runs inside a replicated shard_map."""
    p_sh = build_param_shardings(params, mesh, ax)
    rep = NamedSharding(mesh, P())
    pstruct = jax.tree_util.tree_structure(params)

    def field_sh(val):
        if isinstance(val, tuple) and val == ():
            return ()
        if jax.tree_util.tree_structure(val) == pstruct:
            return build_param_shardings(val, mesh, ax)
        return jax.tree.map(lambda _: rep, val)

    o_sh = type(opt_state)(*[field_sh(v) for v in opt_state])
    return p_sh, o_sh


def run(arch: str, *, reduced: bool, steps: int, batch: int, seq: int,
        lr: float, rounding_kind: str, fmt: str, eps: float,
        ckpt_dir: str, log_every: int = 10, momentum: float = 0.9,
        update_path: str = "jnp", gemm_policy: str = None,
        mesh_spec: str = None, wire_spec: str = None,
        accum_steps: int = 1, accum_spec: str = None,
        wire_topology: str = "reduce_scatter",
        loss_scale: float = 0.0, watchdog: bool = False,
        health_fmt: str = None, fault_schedule: str = None,
        fault_seed: int = 0, restart_window: int = 1000,
        optimizer: str = "sgd", moments_spec: str = None,
        ckpt_fmt: str = None):
    # partition-invariant jax.random streams: the rounded update/wire/
    # accumulator draws must not change with the mesh placement, or the
    # sharded run would silently diverge from the single-device one and
    # elastic resume onto a different topology would lose bit-exactness
    jax.config.update("jax_threefry_partitionable", True)
    cfg = get_config(arch)
    if reduced:
        cfg = reduce_cfg(cfg)
    cfg = dataclasses.replace(
        cfg, remat="none" if reduced else cfg.remat,
        # CLI overrides the config's policy only when actually given
        gemm_policy=gemm_policy if gemm_policy is not None
        else cfg.gemm_policy)
    model = build_model(cfg)
    # fail fast on malformed CLI spec names (same contract as the
    # watchdog ladder's import-time validation)
    if moments_spec is not None:
        parse_moments_spec(moments_spec)
    from repro.checkpoint.manager import resolve_ckpt_grid
    if ckpt_fmt is not None:
        resolve_ckpt_grid(ckpt_fmt)
    opt = build_optimizer(optimizer, lr=lr, momentum=momentum,
                          cfg=rounding_config(rounding_kind, fmt, eps),
                          update_path=update_path,
                          moments_spec=moments_spec)

    mesh = parse_mesh(mesh_spec) if mesh_spec else make_local_mesh()
    ax = mesh_axes_for(mesh, batch_size=batch)
    params = model.init(jax.random.PRNGKey(0))
    opt_state = opt.init(params, jax.random.PRNGKey(1))

    # explicit sharded placement (and the resume path: jit in_shardings
    # re-place checkpoint-restored host arrays onto the same layout)
    p_sh, o_sh = _state_shardings(params, opt_state, mesh, ax)
    params = jax.device_put(params, p_sh)
    opt_state = jax.device_put(opt_state, o_sh)
    bt = tuple(ax.batch) if ax.batch else None

    def batch_shardings(b):
        return jax.tree.map(
            lambda x: NamedSharding(mesh, evenly_divisible_spec(
                P(bt), x.shape, mesh)), b)

    pipe_src = make_token_pipeline(cfg.vocab_size, seq, batch, seed=0)
    pipe = ShardedPipeline(pipe_src,
                           sharding=batch_shardings(pipe_src.batch_at(0)))

    # ---- numeric-health / loss-scale extras (health/ subsystem) ----------
    health_cfg = None
    if watchdog:
        from repro.health import monitor as health_mon
        health_cfg = health_mon.resolve_health(health_fmt or fmt)
    ls = loss_scale if loss_scale and loss_scale > 0 else None
    extras = ls is not None or health_cfg is not None

    carry0 = steps_lib.init_step_carry(loss_scale=ls, health=health_cfg)
    c_sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), carry0)
    batch_sh = batch_shardings(pipe_src.batch_at(0))

    def build_step_fn(level_name=None):
        """Build (and jit) the train step — the initial one, or a
        precision-ladder rung's (the watchdog escalation rebuild hook)."""
        if level_name is None:
            opt_l = opt
            g_pol = None          # model already carries cfg.gemm_policy
        else:
            from repro.health import watchdog as wd_lib
            lvl = wd_lib.get_level(level_name)
            opt_l = build_optimizer(
                optimizer, lr=lr, momentum=momentum,
                cfg=wd_lib.rounding_for_level(level_name),
                update_path=update_path, moments_spec=moments_spec)
            # only escalate the GEMM policy if the run quantized GEMMs
            g_pol = lvl.gemm_policy if cfg.gemm_policy is not None else None
        train_step = steps_lib.make_train_step(
            model, opt_l, accum_steps=accum_steps, accum_spec=accum_spec,
            wire_spec=wire_spec, mesh=mesh, ax=ax,
            wire_topology=wire_topology, gemm_policy=g_pol,
            loss_scale=ls, health=health_cfg)
        # out_shardings pinned to the input layout: GSPMD is otherwise free
        # to re-shard a replicated state leaf on output, and the re-sharded
        # array then mismatches in_shardings on the *next* call
        with set_mesh_axes(ax), mesh:
            if extras:
                jitted = jax.jit(train_step, in_shardings=(
                    p_sh, o_sh, c_sh, batch_sh),
                    out_shardings=(p_sh, o_sh, c_sh, None))

                def step_fn(state, batch_):
                    params_, opt_, carry_ = state
                    with set_mesh_axes(ax), mesh:
                        params_, opt_, carry_, metrics = jitted(
                            params_, opt_, carry_, batch_)
                    return (params_, opt_, carry_), metrics
            else:
                jitted = jax.jit(train_step, in_shardings=(
                    p_sh, o_sh, batch_sh),
                    out_shardings=(p_sh, o_sh, None))

                def step_fn(state, batch_):
                    params_, opt_ = state
                    with set_mesh_axes(ax), mesh:
                        params_, opt_, metrics = jitted(
                            params_, opt_, batch_)
                    return (params_, opt_), metrics
        return step_fn

    wd = None
    if watchdog:
        from repro.health import watchdog as wd_lib
        wd = wd_lib.Watchdog(level=wd_lib.initial_level(fmt, rounding_kind),
                             rebuild=build_step_fn)

    fault_hook = None
    if fault_schedule:
        from repro.health.inject import FaultInjector
        fault_hook = FaultInjector(fault_schedule, seed=fault_seed)

    init_state = ((params, opt_state, carry0) if extras
                  else (params, opt_state))
    state_sharding = (p_sh, o_sh, c_sh) if extras else (p_sh, o_sh)
    loop = TrainLoop(build_step_fn(), pipe, init_state,
                     TrainLoopConfig(total_steps=steps,
                                     checkpoint_every=max(10, steps // 5),
                                     checkpoint_dir=ckpt_dir,
                                     log_every=log_every,
                                     restart_window=restart_window,
                                     checkpoint_fmt=ckpt_fmt),
                     fault_hook=fault_hook,
                     state_sharding=state_sharding, watchdog=wd)
    t0 = time.time()
    out = loop.run()
    dt = time.time() - t0
    n_params = model.param_count(params)
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M steps={out['final_step']} "
          f"wall={dt:.1f}s restarts={out['restarts']} "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"wire={wire_spec or 'fp32'} accum={accum_steps}x"
          f"{'/' + accum_spec if accum_spec else ''}")
    for h in out["history"]:
        print(f"  step {h['step']:>5}  loss {h['loss']:.4f}  ce {h.get('ce', float('nan')):.4f}")
    for ev in out.get("watchdog_events", []):
        detail = (f" {ev['from']} -> {ev['to']}" if "to" in ev else "")
        print(f"  watchdog: step {ev['step']} trigger={ev['trigger']} "
              f"action={ev['action']}{detail}")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.05)
    from repro.core.schemes import ALL_MODES
    ap.add_argument("--rounding", default="signed_sr_eps",
                    choices=["fp32"] + list(ALL_MODES))
    ap.add_argument("--fmt", default="bfloat16")
    ap.add_argument("--eps", type=float, default=0.1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--update-path", default="jnp",
                    choices=list(optim_base.UPDATE_PATHS),
                    help="parameter-update engine: per-leaf jnp chain, "
                         "whole-tree fused kernel (in-kernel PRNG), or "
                         "whole-tree kernel with explicit bits")
    ap.add_argument("--optimizer", default="sgd", choices=["sgd", "adam"],
                    help="qsgd (momentum) or qadam; adam honours "
                         "--moments-spec and, with --update-path fused, "
                         "carries packed low-precision moments inside the "
                         "fully-fused kernel")
    ap.add_argument("--moments-spec", default=None,
                    help="Adam moment-carry grid: any canonical spec name "
                         "with an optional -kahan suffix, e.g. 'bf16-sr', "
                         "'e4m3-sr-kahan', 'bf16-sr-bittrick' (PRF-free "
                         "bit-trick SR); default fp32.  Validated at "
                         "launch like the watchdog ladder")
    ap.add_argument("--ckpt-fmt", default=None,
                    help="packed-checkpoint grid: float32 state leaves "
                         "already on this grid (rounded params, moment "
                         "carries) are stored as uint8/uint16 codes — "
                         "self-validating per leaf, restore stays "
                         "bit-exact.  A grid or canonical spec name, "
                         "e.g. 'bf16-sr' or 'e4m3'; default raw fp32")
    from repro.precision import PRESETS
    ap.add_argument("--gemm-policy", default=None,
                    help="quantized-GEMM precision policy (eq. 8a): round "
                         "every forward/dgrad/wgrad GEMM result onto the "
                         "low-precision grid via the Pallas kernels.  A "
                         f"preset ({', '.join(sorted(PRESETS))}) or any "
                         "canonical spec name, e.g. 'fxp16.8-sr2' or "
                         "'e4m3-sr2-r16'; default: full-precision GEMMs")
    from repro.dist.codecs import wire_codec_names
    from repro.optim.accumulate import ACCUM_PRESETS
    ap.add_argument("--mesh", default=None, metavar="DPxTP",
                    help="explicit mesh topology, e.g. 4x2 (data x model) "
                         "or 2x2x2 (pod x data x model); default: all "
                         "devices on the data axis")
    ap.add_argument("--wire-spec", default=None,
                    help="gradient-wire codec: quantize the cross-device "
                         "gradient reduction payload through this rounded "
                         "grid (dist/codecs.py).  A named codec "
                         f"({', '.join(wire_codec_names())}) or any "
                         "canonical spec name, e.g. 'fxp16.8-sr2'; "
                         "default: fp32 wire")
    ap.add_argument("--wire-topology", default="reduce_scatter",
                    choices=["reduce_scatter", "allreduce"],
                    help="rounded-reduction topology: reduce-scatter + "
                         "rounded shard wire + all-gather (half the wire "
                         "bytes), or quantized all-reduce")
    ap.add_argument("--accum-steps", type=int, default=1,
                    help="microbatch gradient-accumulation factor (the "
                         "global batch is split this many ways)")
    ap.add_argument("--accum-spec", default=None,
                    help="accumulator carry grid (optim/accumulate.py): "
                         "bf16-rn is the swamping baseline, the -sr "
                         "carries keep small microbatch gradients alive.  "
                         f"A preset ({', '.join(sorted(ACCUM_PRESETS))}) "
                         "or any canonical spec name with an optional "
                         "-kahan suffix, e.g. 'fxp16.8-sr2-kahan'; "
                         "default: exact fp32")
    ap.add_argument("--loss-scale", type=float, default=0.0,
                    help="initial dynamic loss scale (optim/scale.py): "
                         "scale the loss before backprop, unscale the "
                         "reduced grads, skip + back off on overflow; "
                         "0 = off (bit-identical to the unscaled step)")
    ap.add_argument("--watchdog", action="store_true",
                    help="numeric-health telemetry + watchdog: detect "
                         "RN-stagnation deadband / overflow / non-finite "
                         "streaks and escalate the precision ladder "
                         "(health/watchdog.py)")
    ap.add_argument("--health-fmt", default=None,
                    help="format grid the health telemetry measures "
                         "against (default: --fmt)")
    ap.add_argument("--fault-schedule", default=None,
                    help="chaos-testing fault schedule, e.g. "
                         "'bitflip@20:bit=30,preempt@40,corrupt@60' "
                         "(health/inject.py)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for unspecified fault-schedule choices")
    ap.add_argument("--restart-window", type=int, default=1000,
                    help="sliding step window the restart budget is "
                         "counted over (0 = run-lifetime budget)")
    args = ap.parse_args()
    enable_compile_cache()
    run(args.arch, reduced=args.reduced, steps=args.steps, batch=args.batch,
        seq=args.seq, lr=args.lr, rounding_kind=args.rounding, fmt=args.fmt,
        eps=args.eps, ckpt_dir=args.ckpt_dir, update_path=args.update_path,
        gemm_policy=args.gemm_policy, mesh_spec=args.mesh,
        wire_spec=args.wire_spec, accum_steps=args.accum_steps,
        accum_spec=args.accum_spec, wire_topology=args.wire_topology,
        loss_scale=args.loss_scale, watchdog=args.watchdog,
        health_fmt=args.health_fmt, fault_schedule=args.fault_schedule,
        fault_seed=args.fault_seed,
        restart_window=args.restart_window or None,
        optimizer=args.optimizer, moments_spec=args.moments_spec,
        ckpt_fmt=args.ckpt_fmt)


if __name__ == "__main__":
    main()
