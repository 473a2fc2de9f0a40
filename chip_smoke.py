#!/usr/bin/env python3
"""Smoke run of the main path on a TPU, at smollm-360m's published widths.

Everything runs in this one process, through the entry points a user
calls, at full width and full depth with random weights from ``--seed``:

1. device: the first JAX device must be a TPU (there is no CPU fallback);
2. hardware PRNG: the in-kernel SR cast (binary8 and bfloat16) of a
   constant between two grid points has the eq. 3-5 mean and variance
   within CLT bounds, and distinct grid blocks draw distinct bits;
3. trainer: ``repro.launch.train.run`` for a few steps of batch 4 x seq
   2048 with the paper's rounded update in the fused kernel and binary8-SR
   GEMMs and attention: no restarts, finite losses, parameters on the
   bf16 grid, and Pallas kernels (``tpu_custom_call``) in the train step;
4. engine: ``ContinuousBatchingEngine`` with rounded attention and a packed
   e4m3-SR KV cache serves 8 requests (prompts of 64-1024 tokens, 32 new
   tokens each) under two arrival orders that place them in different
   slots, and every request's stream is identical in both.

``--chips 4`` runs only the four-chip phase instead: the trainer on a 4x1
mesh with the e4m3-SR gradient wire (full-precision GEMMs, loss scale
2^10), 4 sequences of 1024 per chip, against the single-device step of the
same global batch and seed.

Smoke figures (step ms, peak device bytes, compile seconds) are printed
along the way; they are not benchmark metrics.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failed phase exits non-zero without it.

Usage: python3 chip_smoke.py [--chips 4] [--out DIR] [--seed N]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "smollm-360m"
# the trainer phase: the paper's signed-SRε update on the bf16 grid in the
# fused whole-tree kernel, binary8-SR GEMMs and rounded flash attention
TRAIN = dict(arch=ARCH, reduced=False, steps=6, batch=4, seq=2048, lr=0.05,
             rounding_kind="signed_sr_eps", fmt="bfloat16", eps=0.1,
             update_path="fused", gemm_policy="binary8-paper-attn")
# the engine phase: prompt lengths are multiples of the prefill chunk, so
# every prefill call has one shape and the phase compiles three programs
ENGINE = dict(arch=ARCH, reduced=False,
              prompt_lens=(64, 1024, 192, 512, 128, 768, 320, 896),
              new_tokens=32, n_slots=4, page_size=64, prefill_chunk=64,
              token_budget=256)
# the four-chip phase (``--chips 4``): the trainer's default full-precision
# GEMMs under the rounded e4m3-SR wire; steps, stepsize and loss tolerance
# as in tests/test_wire_accum.py::test_wire_train_loss_matches_unsharded_mesh8.
# The loss scale lifts the payload into e4m3's range: at this size the
# median |gradient| is 2.7e-5, below e4m3's smallest subnormal (2^-9), and
# the unscaled wire keeps a median cosine of 0.63 to the exact gradient
# against 0.999 at 2^8..2^12 (PERF.md, PR 12).  A power of two, so it is
# exact in the single-device step.
MESH4 = dict(TRAIN, gemm_policy=None, lr=0.01, steps=3, per_chip=4,
             seq=1024, wire_spec="e4m3-sr", loss_scale=2.0 ** 10,
             loss_tol=0.05)
N_MC = 1 << 19          # Monte-Carlo samples per PRNG check
X0 = 1.1                # between two grid points of binary8 and bfloat16


def say(msg: str) -> None:
    print(msg, flush=True)


def phase_prng(seed: int) -> None:
    """In-kernel SR cast on the hardware PRNG: eq. 3-5 statistics and
    distinct bits in distinct grid blocks."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import rounding
    from repro.kernels import common
    from repro.kernels.sr_cast import LANES, sr_cast_prng_p

    words = common.derive_seed(jax.random.PRNGKey(seed), 0)
    block_rows = 512                       # 8 grid blocks of 512 x 128
    x0 = np.float32(X0)
    x = jnp.full((N_MC,), x0, jnp.float32)
    for fmt in ("binary8", "bfloat16"):
        cast = jax.jit(lambda x_, w_: sr_cast_prng_p(
            x_, w_, fmt, "sr", block_rows=block_rows))
        y = np.asarray(cast(x, words), np.float64)
        lo, hi = (float(v) for v in rounding.floor_ceil(jnp.float32(x0), fmt))
        if not np.all((y == lo) | (y == hi)):
            raise AssertionError(f"{fmt}: outputs off the two neighbours "
                                 f"{lo}, {hi} of {x0}")
        q = hi - lo
        frac = (float(x0) - lo) / q
        err = y - float(x0)
        mean, var = float(err.mean()), float(err.var())
        want_var = frac * (1.0 - frac) * q * q          # eq. 5
        tol = 4.0 * math.sqrt(want_var / N_MC)          # CLT, 4 sigma
        say(f"prng {fmt}: mean_err={mean:.3e} (|.|<{tol:.3e}) "
            f"var={var:.4e} (eq.5 {want_var:.4e})")
        if abs(mean) >= tol:
            raise AssertionError(f"{fmt}: SR mean error {mean} outside "
                                 f"the CLT bound {tol}")
        if abs(var - want_var) >= 0.02 * want_var:
            raise AssertionError(f"{fmt}: SR variance {var} is not the "
                                 f"eq. 5 value {want_var}")
        ups = (y == hi).reshape(N_MC // (block_rows * LANES), -1)
        n_distinct = len({row.tobytes() for row in ups})
        if n_distinct != ups.shape[0]:
            raise AssertionError(f"{fmt}: {ups.shape[0]} grid blocks drew "
                                 f"only {n_distinct} distinct bit patterns")


def _peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def phase_train(out_dir: Path, *, cfg=None) -> dict:
    """One short training run through ``repro.launch.train.run``."""
    import jax
    import jax.numpy as jnp

    from repro.launch import train

    kw = dict(TRAIN if cfg is None else cfg)
    arch = kw.pop("arch")
    ckpt = Path(tempfile.mkdtemp(prefix="ckpt-", dir=out_dir))
    ir = Path(tempfile.mkdtemp(prefix="ir-", dir=out_dir))
    jax.config.update("jax_dump_ir_to", str(ir))
    try:
        res = train.run(arch, ckpt_dir=str(ckpt), log_every=1, **kw)
        step_files = sorted(ir.glob("*jit_train_step*"))
        n_custom = sum(f.read_text().count("tpu_custom_call")
                       for f in step_files)
    finally:
        jax.config.update("jax_dump_ir_to", "")
        shutil.rmtree(ckpt, ignore_errors=True)
        shutil.rmtree(ir, ignore_errors=True)

    if res["restarts"]:
        raise AssertionError(f"trainer restarted {res['restarts']} times")
    losses = [h["loss"] for h in res["history"]]
    if len(losses) != kw["steps"] or not all(map(math.isfinite, losses)):
        raise AssertionError(f"losses not finite for every step: {losses}")
    params = res["state"][0]
    off_grid = sum(int(jnp.sum(p != p.astype(jnp.bfloat16).astype(p.dtype)))
                   for p in jax.tree.leaves(params))
    if off_grid:
        raise AssertionError(f"{off_grid} updated parameters are off the "
                             "bf16 grid")
    if not step_files:
        raise AssertionError("no train-step program was dumped")
    if not n_custom:
        raise AssertionError("the train step holds no tpu_custom_call: "
                             "the kernels did not lower to Mosaic")
    ms = [h["step_ms"] for h in res["history"]]
    steady = statistics.median(ms[1:])
    say(f"train {arch} batch={kw['batch']} seq={kw['seq']}: "
        f"losses={[round(v, 4) for v in losses]} "
        f"tpu_custom_call={n_custom} restarts=0")
    say(f"train step_ms={steady:.1f} (median of steps 2-{len(ms)}) "
        f"compile_s={(ms[0] - steady) / 1e3:.1f} (first step less a "
        f"steady step) peak_bytes={_peak_bytes(jax.devices()[0])}")
    return {"losses": losses, "step_ms": steady}


def phase_engine(seed: int, *, cfg=None) -> None:
    """Serve the same requests under two arrival orders; streams must be
    identical (the engine's determinism contract) and complete."""
    import jax
    import numpy as np

    from repro.configs import get_config, reduced as reduce_cfg
    from repro.core.rounding import spec
    from repro.models import build_model
    from repro.precision.policy import make_policy
    from repro.serving.engine import (ContinuousBatchingEngine,
                                      EngineConfig, Request)

    kw = dict(ENGINE if cfg is None else cfg)
    mcfg = get_config(kw["arch"])
    if kw["reduced"]:
        mcfg = reduce_cfg(mcfg)
    pol = make_policy(attn=spec("binary8", "sr"), kv_cache_fmt="e4m3-sr")
    model = build_model(dataclasses.replace(mcfg, gemm_policy=pol))
    params = model.init(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    new = kw["new_tokens"]
    reqs = [Request(rid=i, prompt=rng.integers(1, mcfg.vocab_size, n).tolist(),
                    max_new_tokens=new, seed=1000 + i)
            for i, n in enumerate(kw["prompt_lens"])]
    n_max = -(-(max(kw["prompt_lens"]) + new) // kw["page_size"])
    ec = EngineConfig(n_slots=kw["n_slots"], page_size=kw["page_size"],
                      total_pages=kw["n_slots"] * n_max + 1,
                      max_pages_per_request=n_max,
                      prefill_chunk=kw["prefill_chunk"],
                      token_budget=kw["token_budget"])
    runs = []
    for order in (reqs, reqs[::-1]):
        eng = ContinuousBatchingEngine(model, params, ec)
        t0 = time.perf_counter()
        res = eng.run([dataclasses.replace(r) for r in order])
        wall = time.perf_counter() - t0
        short = [rid for rid, r in res.items() if len(r.tokens) != new]
        if short or len(res) != len(reqs):
            raise AssertionError(f"requests {short} did not finish with "
                                 f"{new} tokens")
        runs.append(res)
        say(f"engine order={'fwd' if order is reqs else 'rev'}: "
            f"{len(res)} requests x {new} tokens in {wall:.1f}s "
            f"({eng.iterations} iterations, incl. compiles)")
    moved = [rid for rid in runs[0] if runs[0][rid].slot != runs[1][rid].slot]
    if not moved:
        raise AssertionError("both arrival orders used the same slots")
    diff = [rid for rid in runs[0]
            if runs[0][rid].tokens != runs[1][rid].tokens]
    if diff:
        raise AssertionError(f"streams of requests {diff} depend on the "
                             "arrival order")
    say(f"engine: {len(moved)}/{len(reqs)} requests changed slot, all "
        f"streams identical; peak_bytes={_peak_bytes(jax.devices()[0])}")


def phase_mesh4(out_dir: Path, *, cfg=None) -> None:
    """Trainer on a 4x1 mesh with the rounded e4m3-SR gradient wire vs the
    single-device step of the same global batch, seed and optimizer."""
    import jax

    from repro.configs import get_config, reduced as reduce_cfg
    from repro.data import make_token_pipeline
    from repro.launch import steps as steps_lib, train
    from repro.models import build_model

    kw = dict(MESH4 if cfg is None else cfg)
    n = len(jax.devices())
    if n != 4:
        raise AssertionError(f"--chips 4 needs 4 devices, found {n}")
    batch = kw["per_chip"] * n
    ckpt = Path(tempfile.mkdtemp(prefix="ckpt-", dir=out_dir))
    try:
        res = train.run(kw["arch"], reduced=kw["reduced"], steps=kw["steps"],
                        batch=batch, seq=kw["seq"], lr=kw["lr"],
                        rounding_kind=kw["rounding_kind"], fmt=kw["fmt"],
                        eps=kw["eps"], ckpt_dir=str(ckpt), log_every=1,
                        update_path=kw["update_path"],
                        gemm_policy=kw["gemm_policy"], mesh_spec=f"{n}x1",
                        wire_spec=kw["wire_spec"],
                        loss_scale=kw["loss_scale"])
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    if res["restarts"]:
        raise AssertionError(f"trainer restarted {res['restarts']} times")
    wire = [h["loss"] for h in res["history"]]
    del res

    # the single-device reference, built as train.run builds its model,
    # optimizer, initial state and batches; n microbatches keep one
    # chip's share of the global batch per accumulation step
    mcfg = get_config(kw["arch"])
    if kw["reduced"]:
        mcfg = reduce_cfg(mcfg)
    mcfg = dataclasses.replace(
        mcfg, remat="none" if kw["reduced"] else mcfg.remat,
        gemm_policy=kw["gemm_policy"])
    model = build_model(mcfg)
    opt = train.build_optimizer(
        "sgd", lr=kw["lr"], momentum=0.9,
        cfg=train.rounding_config(kw["rounding_kind"], kw["fmt"], kw["eps"]),
        update_path=kw["update_path"])
    dev = jax.devices()[0]
    params = jax.device_put(model.init(jax.random.PRNGKey(0)), dev)
    state = jax.device_put(opt.init(params, jax.random.PRNGKey(1)), dev)
    step = jax.jit(steps_lib.make_train_step(
        model, opt, accum_steps=n, loss_scale=kw["loss_scale"]))
    carry = steps_lib.init_step_carry(loss_scale=kw["loss_scale"])
    pipe = make_token_pipeline(mcfg.vocab_size, kw["seq"], batch, seed=0)
    ref = []
    for i in range(kw["steps"]):
        params, state, carry, m = step(
            params, state, carry, jax.device_put(pipe.batch_at(i), dev))
        ref.append(float(m["loss"]))
    say(f"mesh {n}x1 wire={kw['wire_spec']} loss_scale={kw['loss_scale']:g} "
        f"batch={batch} seq={kw['seq']}: "
        f"losses={[round(v, 4) for v in wire]} single-device="
        f"{[round(v, 4) for v in ref]} (tol {kw['loss_tol']})")
    bad = [i for i, (a, b) in enumerate(zip(wire, ref))
           if not abs(a - b) <= kw["loss_tol"]]
    if bad or len(wire) != len(ref):
        raise AssertionError(f"sharded losses differ from the single-device "
                             f"step at steps {bad}")
    say(f"mesh peak_bytes={[_peak_bytes(d) for d in jax.devices()]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: only the four-chip "
                         "wire-vs-single-device phase")
    ap.add_argument("--out", default=str(ROOT / ".chip_smoke"),
                    help="scratch directory (checkpoints, IR dumps); "
                         "emptied of what a phase writes when it ends")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no src/repro next to {__file__}: run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from repro.launch.cache import enable_compile_cache

    cache = enable_compile_cache()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devs[0].platform!r}); this smoke runs only on the chip",
              file=sys.stderr)
        return 1
    say(f"device {devs[0].device_kind} x{len(devs)}, jax {jax.__version__}, "
        f"compile cache {cache}")
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    if args.chips == 4:
        phases = [("mesh4", lambda: phase_mesh4(out))]
    else:
        phases = [("prng", lambda: phase_prng(args.seed)),
                  ("train", lambda: phase_train(out)),
                  ("engine", lambda: phase_engine(args.seed))]
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:             # report which phase failed, then exit
            traceback.print_exc()
            print(f"chip_smoke: phase {name} FAILED", file=sys.stderr)
            return 1
        say(f"phase {name} ok in {time.perf_counter() - t0:.1f}s")
    cpu_tools = {"repro.launch.dryrun", "repro.launch.perf"}
    if cpu_tools & set(sys.modules):     # they set XLA_FLAGS on import
        print("chip_smoke: a CPU-only launch tool was imported",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
