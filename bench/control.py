#!/usr/bin/env python3
"""Readings of the correctness check's control and faults, on the chip.

    python3 bench/control.py --workload <training cell> --seeds 1 2 3 \\
        [--variant control|half_batch|no_exchange|program]

For a training cell, for each seed it runs the configuration's plain
reference twice from the same weights and batches as a run of the cell:
once as the reference, once in the program's place with the variant
applied, and prints the compared numbers the variant reads (one JSON
line per seed).  ``control`` computes every GEMM result in the lower
precision the job names (``control`` in its traffic file: int4 below
fp8-class GEMMs, fp8 below bfloat16 ones); ``half_batch`` takes the mean
over half of each batch's rows; ``no_exchange`` over the rows of the
first of the cell's chips alone, as a chip steps when the gradient
exchange between chips is left out.  These run the reference alone, on
one chip whatever the cell asks for.  ``program`` reads the program
itself on many seeds in one process (the compiled step is built once),
on the cell's chips, for the lower readings.  A state left unchanged
reads 1 by construction and needs no run.  For a serving
cell it serves a short window of the cell's own load and reads, at each
position of a sample of the served prompts and tokens, the float32
reference's gap of the token the lower-precision reference puts first.  The limits in
the traffic files are set between these readings and the program's.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def variant_numbers(cfg, job, seed, variant, chips=1):
    import jax
    from bench.drivers import train
    from bench.run import load_reference

    ref = load_reference(cfg)
    feed = train.TokenFeed(cfg["vocab_size"], job["batch"], job["seq"], seed)
    batches = feed.reference_batches()
    base = ref.train_steps(cfg, job, ref.init_params(cfg, seed), batches,
                           seed)
    control = job["control"] if variant == "control" else None
    keep = {"half_batch": job["batch"] // 2,
            "no_exchange": job["batch"] // chips}.get(variant)
    if keep is not None:
        batches = [jax.tree.map(lambda x: x[:keep], b) for b in batches]
    other = ref.train_steps(cfg, job, ref.init_params(cfg, seed), batches,
                            seed + 1, control=control)
    return train.gap_numbers(other, base)


def program_numbers(cfg, job, seeds, devs):
    """The program's readings on many seeds in one process: the compiled
    step is built once and driven through each seed's first steps from
    that seed's weights, as a run's set-up does, then the reference."""
    import jax
    from bench.drivers import train
    from bench.reference import common
    from repro.dist.sharding import set_mesh_axes

    j = train.Job.from_file(job)
    b = train.build(cfg, j, devs)
    norms = jax.jit(common.leaf_norms)
    compiled = None
    for seed in seeds:
        feed = train.TokenFeed(cfg["vocab_size"], j.batch, j.seq, seed,
                               b.batch_sharding)
        state = train.initial_state(b, cfg, j, seed)
        with set_mesh_axes(b.ax), b.mesh:
            if compiled is None:
                compiled = b.jitted.lower(
                    *state, feed.device_batch(0)).compile()
            state, prog = train.first_steps(
                compiled, state, feed, lambda: b.weights(cfg, seed), norms)
        del state
        refr = b.ref.train_steps(cfg, job, b.ref.init_params(cfg, seed),
                                 feed.reference_batches(), seed)
        yield seed, train.gap_numbers(prog, refr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variant", default="control",
                    choices=("control", "half_batch", "no_exchange",
                             "program"))
    ap.add_argument("--seconds", type=float, default=20.0,
                    help="serving cells: the short window of the cell's "
                         "own load that the readings follow")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from bench import harness
    from bench.run import load_cell
    _, cell, cfg, job = load_cell(args.workload)
    reference_only = job["driver"] == "train" and args.variant != "program"
    devs = harness.require_tpu(1 if reference_only else cell["chips"])
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    if args.variant == "program":
        for seed, nums in program_numbers(cfg, job, args.seeds, devs):
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "variant": "program",
                              "device": devs[0].device_kind, **nums}),
                  flush=True)
        return 0
    for seed in args.seeds:
        t0 = time.perf_counter()
        if job["driver"] == "serve":
            from bench.drivers import serve
            ctl, prog = serve.control_gap(cfg, job, seed, args.seconds,
                                          devs)
            nums = {"served_logit_gap": ctl, "program_served_logit_gap": prog}
        else:
            nums = variant_numbers(cfg, job, seed, args.variant,
                                   cell["chips"])
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "variant": args.variant,
                          "device": devs[0].device_kind,
                          "seconds": time.perf_counter() - t0, **nums}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
