"""The program's own spans and scopes, as a profiler trace records them.

The serving engine's host spans (``serving/engine.py``) run under
``jax.profiler.trace`` on the CPU: every span is there, nests as the
engine's module docstring says, and counts what the engine did.  The
train step's named scopes (``launch/steps.make_train_step``) label the
compiled step's operations, so the benchmark's phases
(``bench/scopes.phase``) find forward, backward, recomputed forward and
update in the ``op_name``s of a step compiled with full remat.
"""
import dataclasses
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from bench import scopes
from repro.configs import get_config, reduced
from repro.core.rounding import parse_spec
from repro.launch import steps as steps_lib
from repro.models import build_model
from repro.precision import policy as QP
from repro.serving.engine import (ContinuousBatchingEngine, EngineConfig,
                                  Request)

CHUNK = 4
PROMPTS = [5, 9, 3, 12]


@pytest.fixture(scope="module")
def served():
    pol = QP.make_policy(attn=parse_spec("binary8-sr"),
                         kv_cache_fmt="e4m3-sr")
    cfg = dataclasses.replace(reduced(get_config("tinyllama-1.1b")),
                              gemm_policy=pol)
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _engine(model, params):
    return ContinuousBatchingEngine(model, params, EngineConfig(
        n_slots=2, page_size=8, total_pages=9, max_pages_per_request=4,
        prefill_chunk=CHUNK, token_budget=8))


def _requests():
    return [Request(rid=i, prompt=list(range(1, n + 1)), max_new_tokens=3,
                    seed=7 + i) for i, n in enumerate(PROMPTS)]


@pytest.fixture(scope="module")
def traced(served, tmp_path_factory):
    """A warm engine's run under the profiler: (engine, host spans,
    modules the CPU ran, the kernel lengths of each decode call)."""
    model, params = served
    _engine(model, params).run(_requests())           # compile outside
    eng = _engine(model, params)
    kernel_lengths, decode = [], eng._decode_fn

    def spy(params, kp, vp, tables, lengths, words, append, *rest):
        # the paged kernel sees each slot's length plus its appended token
        kernel_lengths.append(np.asarray(lengths)[0]
                              + np.asarray(append)[0])
        return decode(params, kp, vp, tables, lengths, words, append, *rest)
    eng._decode_fn = spy
    d = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(d)):
        eng.run(_requests())
    pd = ProfileData.from_file(glob.glob(f"{d}/**/*.xplane.pb",
                                         recursive=True)[0])
    spans, modules = [], set()
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    spans.append(scopes.Span(
                        ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                        {k: v for k, v in ev.stats}))
                for k, v in ev.stats:
                    if k == "hlo_module":
                        modules.add(v)
    return eng, spans, modules, kernel_lengths


def _inside(inner, outer):
    return outer.start <= inner.start and inner.end <= outer.end


def test_engine_spans_nest(traced):
    eng, spans, *_ = traced
    names = {s.name for s in spans}
    assert names == {"engine.step", "engine.admit", "engine.decode",
                     "engine.decode.wait", "engine.prefill",
                     "engine.prefill.wait"}
    steps = [s for s in spans if s.name == "engine.step"]
    for s in spans:
        if s.name != "engine.step":
            assert any(_inside(s, t) for t in steps), s
    for s in spans:
        if s.name.endswith(".wait"):
            phase = s.name[:-len(".wait")]
            parents = [p for p in spans if p.name == phase
                       and _inside(s, p)]
            assert len(parents) == 1, s
            if phase == "engine.prefill":
                assert parents[0].args["rid"] == s.args["rid"]
    # phases of one step never overlap one another
    phases = sorted((s for s in spans if s.name in (
        "engine.admit", "engine.decode", "engine.prefill")),
        key=lambda s: s.start)
    assert all(a.end <= b.start for a, b in zip(phases, phases[1:]))


def test_engine_spans_count_the_engine_work(traced):
    eng, spans, *_ = traced

    def named(n):
        return [s for s in spans if s.name == n]
    assert len(named("engine.step")) == eng.iterations
    assert len(named("engine.admit")) == eng.iterations
    # one span a prefill chunk; a wait for each request's first token
    assert len(named("engine.prefill")) == sum(-(-n // CHUNK)
                                               for n in PROMPTS)
    assert sorted(s.args["rid"] for s in named("engine.prefill.wait")) == \
        list(range(len(PROMPTS)))
    assert sum(s.args["tokens"] for s in named("engine.prefill")) == \
        eng.prefill_tokens == sum(PROMPTS)
    # one decode span (and wait) per batched call; ``active`` slots decoded
    assert len(named("engine.decode")) == len(named("engine.decode.wait"))
    assert sum(s.args["active"] for s in named("engine.decode")) == \
        eng.decode_tokens


def test_engine_decode_span_counts_live_pages(traced):
    eng, spans, _, kernel_lengths = traced
    decodes = [s for s in spans if s.name == "engine.decode"]
    page, width = eng.cfg.page_size, eng.cfg.max_pages_per_request
    assert len(decodes) == len(kernel_lengths)
    for span, lengths in zip(sorted(decodes, key=lambda s: s.start),
                             kernel_lengths):
        assert span.args["live_pages"] == sum(-(-int(n) // page)
                                              for n in lengths)
        assert span.args["table_pages"] == eng.cfg.n_slots * width
    # a request longer than a page was seen, and no call walked the table
    assert max(s.args["live_pages"] for s in decodes) > \
        max(s.args["active"] for s in decodes)
    assert all(s.args["live_pages"] < s.args["table_pages"]
               for s in decodes)


def test_engine_programs_carry_stable_names(traced):
    _, _, modules, _ = traced
    assert {"jit_engine_decode", "jit_engine_prefill"} <= modules


def test_engine_spans_change_no_token(served, traced):
    model, params = served
    eng, *_ = traced
    plain = _engine(model, params).run(_requests())
    assert {r: v.tokens for r, v in plain.items()} == \
        {r: v.tokens for r, v in eng.results.items()}


def _op_names(step_fn, *args):
    text = jax.jit(step_fn).lower(*args).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


@pytest.fixture(scope="module")
def train_setup():
    cfg = dataclasses.replace(reduced(get_config("smollm-360m")),
                              remat="full")
    model = build_model(cfg)
    opt = steps_lib.paper_optimizer(lr=0.01)
    params = model.init(jax.random.PRNGKey(0))
    state = opt.init(params, jax.random.PRNGKey(1))
    batch = {"tokens": jnp.zeros((2, 8), jnp.int32),
             "labels": jnp.zeros((2, 8), jnp.int32)}
    return model, opt, params, state, batch


def test_train_step_scopes_name_every_phase(train_setup):
    model, opt, params, state, batch = train_setup
    step = steps_lib.make_train_step(model, opt, loss_scale=2.0 ** 10)
    carry = steps_lib.init_step_carry(loss_scale=2.0 ** 10)
    by_phase = {}
    for n in _op_names(step, params, state, carry, batch):
        by_phase.setdefault(scopes.phase(n), set()).add(n)
    for p in ("forward", "backward", "recompute", "update"):
        assert by_phase.get(p), p
    # the update tail: unscale, finiteness check and skip-step select
    assert any("step.update" in n and "select_n" in n
               for n in by_phase["update"])


def test_plain_train_step_scopes_the_update(train_setup):
    model, opt, params, state, batch = train_setup
    names = _op_names(steps_lib.make_train_step(model, opt), params, state,
                      batch)
    phases = {scopes.phase(n) for n in names}
    assert {"forward", "backward", "recompute", "update"} <= phases
    assert np.mean([scopes.phase(n) == "unscoped" for n in names]) < 0.5
