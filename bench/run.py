#!/usr/bin/env python3
"""Run one benchmark cell and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data: ``BENCHMARK.json`` names its
configuration (``bench/configs/<config>.json``) and its traffic mix or
training job (``bench/traffic/<traffic>.json``, whose ``driver`` names
``bench/drivers/<driver>.py``).  A per-layer metric is a reader of its own,
``bench/metrics/<metric>.py``.  A run loads, warms up every shape it uses
(set-up), measures for ``--seconds`` (the window; ``--trace 1`` traces a
short window instead and reports the per-layer metrics), then checks what
the timed path produced against the plain reference.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), with the compared numbers and their limits under ``checks``,
last.  The same numbers are the last lines of standard error.  With no TPU,
or fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_module(path: Path):
    """Import a file of ``bench/`` by its path (metric files carry dots in
    their names), registered in ``sys.modules`` like any module."""
    name = "bench._files." + path.stem.replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str):
    """(benchmark, cell, configuration, traffic) for the named cell."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json "
                         f"(known: {sorted(cells)})")
    cell = cells[name]
    cfg = json.loads((BENCH / "configs" / f"{cell['config']}.json")
                     .read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    return bench, cell, cfg, traffic


def metrics_for(bench: dict, cell: str, trace: bool) -> list:
    """The cell's metric entries: end-to-end ones, or per-layer ones."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def read_layer_metrics(entries: list, inputs: dict) -> dict:
    """Each per-layer metric from its own reader; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in entries:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        value = reader.read(inputs)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench, cell, cfg, traffic = load_cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import harness
    try:
        devs = harness.require_tpu(cell["chips"])
    except harness.NoDevice as e:
        print(f"run.py: {e}; this benchmark runs only on the chip",
              file=sys.stderr)
        return 3
    harness.load_peaks(devs[0].device_kind)
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    out_dir = ROOT / ".bench_out" / args.workload
    out_dir.mkdir(parents=True, exist_ok=True)
    driver = load_module(BENCH / "drivers" / f"{traffic['driver']}.py")
    outcome = driver.run(cell, cfg, traffic, args.seed, args.seconds,
                         bool(args.trace), devs, T_START, out_dir)
    return report(bench, cell, outcome, bool(args.trace))


def report(bench: dict, cell: dict, outcome, trace: bool) -> int:
    entries = metrics_for(bench, cell["name"], trace)
    result = {"correct": outcome.correct, "attempted": outcome.attempted,
              "failed": outcome.failed}
    device = dict(outcome.device)
    if trace:
        from bench import trace as trace_lib
        red = outcome.layer_inputs.get("reduced")
        if red is None:
            red = trace_lib.reduce_dir(outcome.layer_inputs["trace_dir"])
            outcome.layer_inputs["reduced"] = red
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        result["metrics"] = read_layer_metrics(entries, outcome.layer_inputs)
        calls = outcome.layer_inputs.get("calls", {})
        result["breakdown"] = red.breakdown(
            rename=lambda n: f"{calls[n].kind}:{n}" if n in calls else n)
    else:
        result["metrics"] = {
            m["name"]: {"value": outcome.end_to_end[m["name"]],
                        "unit": m["unit"]}
            for m in entries if m["name"] in outcome.end_to_end}
    result["device"] = device
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in outcome.checks}
    for line in outcome.notes:
        print(line, file=sys.stderr)
    for c in outcome.checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
