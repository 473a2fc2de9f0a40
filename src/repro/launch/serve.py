"""Serving driver: batched prefill + decode with KV/state caches.

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --reduced --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced as reduce_cfg
from repro.launch import steps as steps_lib
from repro.launch.cache import enable_compile_cache
from repro.models import build_model


def serve_batch(model, params, prompts, gen: int, enc_out=None):
    """Fixed-batch serving: one jitted prompt-absorption scan + AOT-compiled
    cache-donating decode steps.  Returns (tokens (B, gen), timings) — the
    timings measure execution only, never XLA compiles.  Shared by the CLI
    driver below and the serving benchmark's fixed-batch comparator."""
    batch, prompt_len = prompts.shape
    max_len = prompt_len + gen
    caches = model.init_decode_cache(batch, max_len)
    tok = prompts[:, -1:]

    # teacher-forced prompt absorption as ONE jitted lax.scan over the
    # prompt: a single dispatch instead of prompt_len unjitted python-loop
    # steps (each of which re-traced and re-dispatched every layer — the
    # O(prompt_len) overhead this replaces).
    def absorb_prompt(params_, caches_, prompts_, enc_):
        def body(c, inp):
            pos, tok_t = inp
            # compute_logits=False: absorption only needs the caches — the
            # vocab-sized lm-head GEMM would be discarded per token
            _, c_new = model.decode_step(params_, c, tok_t[:, None], pos,
                                         enc_out=enc_, compute_logits=False)
            # keep the carry dtype stable (RWKV emits bf16 shift states
            # into an fp32-initialized cache; scan requires a fixed type)
            return jax.tree.map(lambda n, o: n.astype(o.dtype), c_new, c), ()
        caches_, _ = jax.lax.scan(
            body, caches_, (jnp.arange(prompt_len), prompts_.T))
        return caches_

    # AOT-compile so the reported prefill tok/s measures execution, not
    # the one-time XLA compile; enc_out rides as a traced argument rather
    # than a baked-in closure constant
    absorb = jax.jit(absorb_prompt).lower(params, caches, prompts,
                                          enc_out).compile()
    t0 = time.time()
    caches = jax.block_until_ready(absorb(params, caches, prompts, enc_out))
    t_prefill = time.time() - t0

    # decode is AOT-compiled the same way (the python loop used to pay the
    # trace+compile on its first iteration, polluting the decode tok/s),
    # and the caches are donated: each step's update writes in place
    # instead of allocating a second full KV cache per token
    serve_step = jax.jit(steps_lib.make_serve_step(model),
                         donate_argnums=(1,)).lower(
        params, caches, tok, jnp.int32(prompt_len), enc_out).compile()
    outs = []
    t1 = time.time()
    for t in range(gen):
        tok, logits, caches = serve_step(params, caches, tok,
                                         jnp.int32(prompt_len + t), enc_out)
        outs.append(tok)
    toks = jax.block_until_ready(jnp.concatenate(outs, axis=1))
    t_decode = time.time() - t1
    return toks, {
        "t_prefill": t_prefill, "t_decode": t_decode,
        "prefill_tokps": batch * prompt_len / max(t_prefill, 1e-9),
        "decode_tokps": batch * gen / max(t_decode, 1e-9)}


def run(arch: str, *, reduced: bool, batch: int, prompt_len: int, gen: int,
        seed: int = 0, gemm_policy: str = None, kv_cache_fmt: str = None):
    cfg = get_config(arch)
    if reduced:
        cfg = reduce_cfg(cfg)
    if gemm_policy is not None:
        # quantized serving (eq. 8a at inference): prefill-scan and decode
        # both honor the policy — including the absorbed-MLA decode path
        cfg = dataclasses.replace(cfg, gemm_policy=gemm_policy)
    if kv_cache_fmt is not None:
        # packed low-precision KV cache: appended k/v round onto the fmt
        # grid and are stored as code words the decode kernel unpacks on
        # load (1 B/elt in HBM for 8-bit grids)
        from repro.precision import policy as QP
        cfg = dataclasses.replace(
            cfg, gemm_policy=QP.policy_with_kv_fmt(cfg.gemm_policy,
                                                   kv_cache_fmt))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))

    key = jax.random.PRNGKey(seed + 1)
    prompts = jax.random.randint(key, (batch, prompt_len), 0,
                                 cfg.vocab_size)
    enc_out = None
    batch_in = {"tokens": prompts}
    if cfg.frontend == "vision":
        batch_in["vision_embeds"] = jax.random.normal(
            key, (batch, cfg.frontend_len, cfg.d_model)) * 0.02
    if cfg.frontend == "audio":
        batch_in["src_embeds"] = jax.random.normal(
            key, (batch, prompt_len, cfg.d_model)) * 0.02
    if cfg.encoder_layers:
        enc_out = model._encode(params, batch_in, jax.random.PRNGKey(0))

    toks, t = serve_batch(model, params, prompts, gen, enc_out)
    print(f"arch={cfg.name} batch={batch} prompt={prompt_len} gen={gen}")
    print(f"prefill {t['t_prefill']:.2f}s ({t['prefill_tokps']:.1f} tok/s); "
          f"decode {t['t_decode']:.2f}s ({t['decode_tokps']:.1f} tok/s)")
    print("sample:", toks[0].tolist())
    return toks


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    from repro.precision import PRESETS
    ap.add_argument("--gemm-policy", default=None, choices=sorted(PRESETS),
                    help="quantized-GEMM precision policy for prefill and "
                         "decode (default: full-precision GEMMs)")
    ap.add_argument("--kv-cache-fmt", default=None,
                    help="KV-cache storage spec (e.g. 'e4m3-sr', "
                         "'binary8-rn'): appended k/v round onto this grid "
                         "and the cache is stored packed (uint8 codes); "
                         "overrides the policy's kv_cache_fmt")
    args = ap.parse_args()
    enable_compile_cache()
    run(args.arch, reduced=args.reduced, batch=args.batch,
        prompt_len=args.prompt_len, gen=args.gen,
        gemm_policy=args.gemm_policy, kv_cache_fmt=args.kv_cache_fmt)


if __name__ == "__main__":
    main()
