"""Run the tiny ``train-wire4`` job on four virtual CPU devices, sound or
with a fault planted under the timed path; one JSON line per run.

    python3 bench/tests/four_devices.py SEED [FAULT ...]

FAULT is ``sound``, ``state_unchanged``, ``half_batch`` or
``no_exchange`` (the gradient exchange between chips left out: each chip
steps on its own rows' gradient).  Virtual devices need the XLA flag set
before JAX starts, so the tests run this in a process of its own.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
N_DEVICES = 4


def plant(fault: str):
    """Patch the program for ``fault``; returns the undo."""
    from bench.tests import faults
    from repro.dist import collectives
    from repro.launch import steps as steps_lib
    if fault == "no_exchange":
        real = collectives.wire_reduce
        collectives.wire_reduce = lambda grads, *a, **kw: grads

        def undo():
            collectives.wire_reduce = real
        return undo
    real = steps_lib.make_train_step
    if fault != "sound":
        steps_lib.make_train_step = faults.wrap(real, getattr(faults, fault))

    def undo():
        steps_lib.make_train_step = real
    return undo


def main(argv) -> int:
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               f" --xla_force_host_platform_device_count="
                               f"{N_DEVICES}")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import harness
    from bench.drivers import train
    from bench.tests import tiny
    harness.load_peaks = lambda kind: tiny.PEAKS
    seed, faults = int(argv[0]), argv[1:] or ["sound"]
    devs = jax.devices()
    assert len(devs) == N_DEVICES, devs
    cfg, job = tiny.config(), tiny.traffic("train-wire4")
    for fault in faults:
        undo = plant(fault)
        try:
            with tempfile.TemporaryDirectory() as out_dir:
                out = train.run({"name": "test"}, cfg, job, seed, 0.5, False,
                                devs, time.perf_counter(), Path(out_dir))
        finally:
            undo()
        print(json.dumps({"fault": fault, "correct": out.correct,
                          "attempted": out.attempted,
                          "failed": out.failed,
                          "chips": out.device["count"],
                          "checks": {c.name: [c.value, c.limit]
                                     for c in out.checks}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
