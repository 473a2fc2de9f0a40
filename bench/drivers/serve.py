"""Serving driver: open-loop traffic into the continuous-batching engine.

Set-up makes the weights on the device from the seed (bfloat16, the type
they are served in), builds ``ContinuousBatchingEngine`` with the cell's
engine settings and precision policy, and warms every program the
traffic uses: one prefill program for each chunk shape the prompt
lengths produce (with and without the last chunk's logits) and the
batched decode step, through a throw-away engine of the same shape.  The
engine that serves the window is built afterwards; the jitted programs
are shared per model, so it compiles nothing.

The window offers requests at the traffic file's fixed rate: arrival
times and request sizes come from the traffic file's own generator seed,
and ``--seed`` permutes which size arrives when and draws the prompt
tokens, so every seed offers the same work.  One thread submits each request when
it is due and calls ``engine.step()`` while anything is in flight;
token emission and slot assignment are observed after each step.  Each
request is timed from when it was due.  After ``--seconds`` the offered
load continues, uncounted, until every request that arrived inside the
window has finished (or the drain limit passes: it then counts as
failed).

The check: once the engine is freed, the plain reference runs over a
sample of finished requests drawn from the seed, the longest among them,
and reads how far each served token's logit lies below the reference's
best at its position.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import shutil
import time
from pathlib import Path

import numpy as np

from bench import harness
from bench.drivers.train import model_config
from bench.run import load_reference


def _lognormal_sizes(rng, spec: dict, n: int) -> np.ndarray:
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    x = np.clip(np.round(x), spec["min"], spec["max"])
    m = spec.get("multiple", 1)
    return (np.ceil(x / m) * m).astype(np.int64)


@dataclasses.dataclass
class Arrival:
    due: float            # seconds after the window opens
    prompt: list
    max_new: int
    rid: int


def offered_load(traffic: dict, seed: int, vocab: int, seconds: float,
                 horizon: float):
    """Requests due in ``[0, horizon)``.  The window ``[0, seconds)``
    holds rate x seconds requests and the drain after it rate x (horizon
    - seconds), each at arrival times spread as a Poisson process's are
    given its count (uniform, sorted) and with lognormal sizes, all drawn
    from the traffic's generator seed: the same times and sizes for every
    run.  ``seed`` permutes which size arrives when, and draws the
    prompt tokens."""
    base = np.random.default_rng(traffic["sizes_seed"])
    rng = np.random.default_rng([seed, 0x5E7])
    out = []
    for lo, hi in ((0.0, seconds), (seconds, horizon)):
        n = int(round(traffic["rate_per_s"] * (hi - lo)))
        due = lo + np.sort(base.uniform(0.0, hi - lo, n))
        prompts = _lognormal_sizes(base, traffic["prompt"], n)
        outputs = _lognormal_sizes(base, traffic["output"], n)
        order = rng.permutation(n)
        for k in range(n):
            toks = rng.integers(1, vocab, int(prompts[order[k]])).tolist()
            out.append(Arrival(float(due[k]), toks,
                               int(outputs[order[k]]), len(out)))
    return out


def prefill_shapes(traffic: dict):
    """Prompt lengths that together warm every prefill chunk shape."""
    chunk, m = traffic["engine"]["prefill_chunk"], traffic["prompt"].get(
        "multiple", 1)
    tails = sorted({(k * m) % chunk or chunk
                    for k in range(1, chunk // m + 1)})
    return [chunk + t for t in tails]


def make_policy(traffic: dict):
    from repro.core.rounding import parse_spec
    from repro.precision.policy import make_policy as mk
    p = traffic["policy"]
    return mk(attn=parse_spec(p["attn"]), kv_cache_fmt=p["kv_cache_fmt"])


def serve_weights(cfg: dict, seed: int, dtype: str):
    import jax
    import jax.numpy as jnp
    params = load_reference(cfg).init_params(cfg, seed)
    return jax.jit(lambda p: jax.tree.map(
        lambda w: w.astype(jnp.dtype(dtype)), p), donate_argnums=0)(params)


class Record:
    """Host-side observations of one request."""

    def __init__(self, arrival: Arrival, t_open: float):
        self.a = arrival
        self.due = t_open + arrival.due
        self.submitted = None
        self.admitted = None
        self.emits = []

    @property
    def ttft(self):
        return self.emits[0] - self.due if self.emits else None


def _pct(values, q):
    if not values:
        return None
    v = sorted(values)
    return float(np.percentile(np.asarray(v), q))


def drive(engine, load, seconds, drain_s, counter, trace_cb=None):
    """The open loop.  Returns (records, per-step counters)."""
    import jax
    from repro.serving.engine import Request
    span = jax.profiler.TraceAnnotation
    t_open = time.perf_counter()
    recs = [Record(a, t_open) for a in load]
    live = {}
    steps = []
    nxt = 0
    window_end = t_open + seconds
    in_window = [r for r in recs if r.due < window_end]
    backlog = {}
    with counter.window():
        while True:
            now = time.perf_counter()
            if trace_cb is not None:
                trace_cb(now - t_open)
            while nxt < len(recs) and recs[nxt].due <= now:
                r = recs[nxt]
                with span("bench.submit"):
                    engine.submit(Request(rid=r.a.rid, prompt=r.a.prompt,
                                          max_new_tokens=r.a.max_new,
                                          seed=r.a.rid))
                r.submitted = time.perf_counter()
                live[r.a.rid] = r
                nxt += 1
            if live:
                decoding = [r for r in live.values()
                            if r.emits and len(r.emits) < r.a.max_new]
                kv_live = sum(len(r.a.prompt) + len(r.emits)
                              for r in decoding)
                d0, p0 = engine.decode_tokens, engine.prefill_tokens
                t0 = time.perf_counter()
                with span("bench.engine_step"):
                    engine.step()
                t1 = time.perf_counter()
                steps.append((t0, t1, engine.decode_tokens - d0, kv_live,
                              engine.prefill_tokens - p0))
                for rid in list(live):
                    r, res = live[rid], engine.results[rid]
                    if r.admitted is None and res.slot is not None:
                        r.admitted = t1
                    while len(r.emits) < len(res.tokens):
                        r.emits.append(t1)
                    if res.finish_time is not None:
                        del live[rid]
            elif nxt < len(recs):
                time.sleep(max(0.0, min(recs[nxt].due - now, 0.002)))
            now = time.perf_counter()
            if now >= window_end and not backlog:
                backlog["waiting"] = sum(r.admitted is None
                                         for r in live.values())
                backlog["in_flight"] = len(live)
            if now >= window_end and all(len(r.emits) == r.a.max_new
                                         for r in in_window):
                break
            if now >= window_end + drain_s or (nxt >= len(recs)
                                               and not live):
                break
    return t_open, recs, steps, backlog


def run(cell: dict, cfg: dict, traffic: dict, seed: int, seconds: float,
        trace: bool, devs, t_start: float, out_dir) -> harness.Outcome:
    import jax

    from repro.models import build_model
    from repro.serving.engine import (ContinuousBatchingEngine,
                                      EngineConfig, Request)

    phases = harness.Phases(t_start)
    phases.mark("start and imports")
    counter = harness.CompileCounter()
    e = traffic["engine"]
    ec = EngineConfig(n_slots=e["n_slots"], page_size=e["page_size"],
                      total_pages=e["n_slots"] * e["max_pages_per_request"]
                      + 1,
                      max_pages_per_request=e["max_pages_per_request"],
                      prefill_chunk=e["prefill_chunk"],
                      token_budget=e["token_budget"],
                      max_queue=e["max_queue"])
    model = build_model(model_config(cfg, load_reference(cfg),
                                     make_policy(traffic)))
    params = serve_weights(cfg, seed, traffic["weights_dtype"])
    horizon = seconds + traffic["drain_seconds"]
    load = offered_load(traffic, seed, cfg["vocab_size"], seconds, horizon)
    jax.block_until_ready(params)
    phases.mark("weights and load")

    warm = ContinuousBatchingEngine(model, params, ec)
    warm.run([Request(rid=i, prompt=[1] * n, max_new_tokens=3, seed=i)
              for i, n in enumerate(prefill_shapes(traffic))])
    del warm
    gc.collect()
    engine = ContinuousBatchingEngine(model, params, ec)
    phases.mark("warm-up")
    setup_s = time.perf_counter() - t_start

    trace_dir = out_dir / "trace"
    traced = {}

    def trace_cb(t):
        if not trace:
            return
        a = seconds / 2 - traffic["trace_seconds"] / 2
        if "start" not in traced and t >= a:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir))
            traced["start"] = time.perf_counter()
            traced["span"] = jax.profiler.TraceAnnotation("bench.window")
            traced["span"].__enter__()
        elif "start" in traced and "stop" not in traced and \
                t >= a + traffic["trace_seconds"]:
            traced["span"].__exit__(None, None, None)
            jax.profiler.stop_trace()
            traced["stop"] = time.perf_counter()

    t_open, recs, steps, backlog = drive(
        engine, load, seconds, traffic["drain_seconds"], counter, trace_cb)
    if "start" in traced and "stop" not in traced:
        traced["span"].__exit__(None, None, None)
        jax.profiler.stop_trace()
        traced["stop"] = time.perf_counter()
    device = harness.device_record(devs)
    results = {rid: list(r.tokens) for rid, r in engine.results.items()}
    del engine
    gc.collect()

    window = [r for r in recs if r.due < t_open + seconds]
    done = [r for r in window if len(r.emits) == r.a.max_new
            and len(results.get(r.a.rid, ())) == r.a.max_new]
    failed = len(window) - len(done)
    ttft = [r.ttft for r in done]
    itl = [b - a for r in done for a, b in zip(r.emits, r.emits[1:])]
    late = [r.submitted - r.due for r in recs if r.submitted is not None]
    notes = [phases.line(),
             f"offered {len(window)} requests in {seconds:g} s at "
             f"{traffic['rate_per_s']} /s; generator late p50 "
             f"{_pct(late, 50) * 1e3:.3f} ms, max {max(late) * 1e3:.3f} ms"
             if late else "no request was offered",
             f"at the window's close: {backlog.get('waiting')} requests "
             f"waiting for a slot, {backlog.get('in_flight')} in flight; "
             f"{len(steps)} engine steps"]

    sample = sample_requests(done, seed, traffic["check_sample"])
    gap = served_gap(cfg, traffic, sample, results, params)
    if traffic.get("with_control"):
        ctl = served_gap(cfg, traffic, sample, results, params,
                         traffic["control"])
        notes.append(f"control {traffic['control']}: served_logit_gap "
                     f"{ctl!r}")
    del params
    checks = [harness.Check("served_logit_gap", gap,
                            traffic["limits"]["served_logit_gap"]),
              harness.Check("compiles_in_window", counter.count, 0)]
    notes.append(f"time to first token over {len(ttft)} requests: p50 "
                 f"{_pct(ttft, 50)!r} s, p90 {_pct(ttft, 90)!r} s")
    e2e = {"itl_p95_ms": _pct([t * 1e3 for t in itl], 95),
           "setup_s": setup_s}
    e2e = {k: v for k, v in e2e.items() if v is not None}
    layer = {"trace_dir": trace_dir if trace else None,
             "steps": steps, "traced": traced,
             "window": (t_open, t_open + seconds),
             "cfg": cfg, "job": traffic,
             "peaks": harness.load_peaks(devs[0].device_kind)}
    if traffic.get("with_control"):
        layer["control_gap"] = ctl
        layer["program_gap"] = gap
    return harness.Outcome(attempted=len(window), failed=failed,
                           checks=checks, end_to_end=e2e, device=device,
                           layer_inputs=layer, notes=notes)


def sample_requests(done, seed: int, k: int):
    """``k`` finished requests drawn from the seed, the longest among
    them."""
    if not done:
        return []
    longest = max(done, key=lambda r: r.a.max_new + len(r.a.prompt))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 0xC4EC])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def served_gap(cfg, traffic, sample, results, params, control=None):
    """Widest gap, over the sampled requests' served tokens, between the
    reference's best logit and that of the served token (with
    ``control``: of the token the lower-precision reference puts first at
    the same positions)."""
    if not sample:
        return None
    length = traffic["engine"]["max_pages_per_request"] * \
        traffic["engine"]["page_size"]
    ref = load_reference(cfg)
    worst = 0.0
    for r in sample:
        g = ref.served_token_gaps(cfg, params, r.a.prompt,
                                  results[r.a.rid], length, control)
        worst = max(worst, float(np.max(g)))
    return worst


def control_gap(cfg, traffic, seed, seconds=2.0, devs=None, out_dir=None):
    """The control's reading and the program's, on the same requests: a
    short window of the cell's own load, then the gap of the
    lower-precision reference's first token at each position of the same
    prompts and served tokens, and that of the served tokens."""
    import tempfile
    import jax
    out = run({"name": "control"}, cfg, dict(traffic, with_control=True),
              seed, seconds, False, devs or jax.devices(),
              time.perf_counter(), out_dir or Path(tempfile.mkdtemp()))
    return out.layer_inputs["control_gap"], out.layer_inputs["program_gap"]
