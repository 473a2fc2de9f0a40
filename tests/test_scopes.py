"""The benchmark's reduction of the program's own spans and scopes
(``bench/scopes.py``) and the per-layer metrics that read it, on
hand-written traces whose every number is known."""
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from bench import scopes

BENCH = Path(__file__).resolve().parents[1] / "bench"


def reader(metric: str):
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reduce_text(text: str) -> scopes.Scopes:
    return scopes.reduce_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))


def events(rows):
    """XEvents of (metadata id, start ns, duration ns, stats text)."""
    return " ".join(
        f"events {{ metadata_id: {m} offset_ps: {a * 1000} "
        f"duration_ps: {d * 1000} {st} }}" for m, a, d, st in rows)


def plane(pid, name, lines=(), names=None, stats=None, stat_names=()):
    """One XPlane: ``lines`` of (line name, event rows), event metadata
    ``names`` (id -> name) with metadata ``stats`` (id -> stats text),
    statistic names numbered from 1."""
    out = [f"planes {{ id: {pid} name: {json.dumps(name)}"]
    for i, (line, rows) in enumerate(lines, 1):
        out.append(f"lines {{ id: {i} name: {json.dumps(line)} "
                   f"timestamp_ns: 1000 {events(rows)} }}")
    for k, n in (names or {}).items():
        out.append(f"event_metadata {{ key: {k} value {{ id: {k} "
                   f"name: {json.dumps(n)} {(stats or {}).get(k, '')} }} }}")
    for k, n in enumerate(stat_names, 1):
        out.append(f"stat_metadata {{ key: {k} value {{ id: {k} "
                   f"name: {json.dumps(n)} }} }}")
    return "\n".join(out) + " }\n"


def stat(sid, value):
    kind = "str_value" if isinstance(value, str) else "int64_value"
    v = json.dumps(value) if isinstance(value, str) else value
    return f"stats {{ metadata_id: {sid} {kind}: {v} }}"


# Serving, times in ns.  Device: modules decode [0,30), prefill [40,60),
# decode [70,90); ops [0,10) [12,30) | [40,50) [52,56) | [70,90): busy 62
# of the 100 ns window.  Host: two engine steps,
#   step [0,66): admit [0,2), decode [2,34) > wait [8,34),
#                prefill [36,64) (rid 5, 256 tokens) > wait [55,64);
#   step [67,99): admit [67,68), decode [68,98) > wait [75,98).
# Idle [10,12) decode.wait 2, [30,40) step 10, [50,52) prefill 2,
# [56,70) prefill.wait 14, [90,100) decode.wait 10: host 12, sync 26.
DECODE_OP = "jit(engine_decode)/attn.paged_decode/paged_decode"
APPEND_OP = "jit(engine_decode)/attn.kv_append/scatter"
SERVE = plane(
    1, "/device:TPU:0",
    [("XLA Modules", [(1, 0, 30, ""), (2, 40, 20, ""), (1, 70, 20, "")]),
     ("XLA Ops", [(3, 0, 10, ""), (4, 12, 18, ""), (5, 40, 10, ""),
                  (6, 52, 4, ""), (3, 70, 20, "")])],
    {1: "jit_engine_decode(11)", 2: "jit_engine_prefill(12)",
     3: "%paged_decode.1 = f32[8]{0} custom-call()",
     4: "%copy.2 = u8[8]{0} copy(u8[8]{0} %p)",
     5: "%fusion.3 = f32[8]{0} fusion()",
     6: "%fusion.4 = f32[8]{0} fusion()"},
    {3: stat(1, DECODE_OP), 4: stat(1, APPEND_OP)}, ["tf_op"]) + plane(
    2, "/host:CPU",
    [("python", [(1, 0, 100, ""), (2, 0, 66, ""), (3, 0, 2, ""),
                 (4, 2, 32, stat(1, 3)), (5, 8, 26, ""),
                 (6, 36, 28, stat(2, 5) + " " + stat(3, 256)),
                 (7, 55, 9, stat(2, 5)),
                 (2, 67, 32, ""), (3, 67, 1, ""),
                 (4, 68, 30, stat(1, 4)), (5, 75, 23, "")])],
    {1: "bench.window", 2: "engine.step", 3: "engine.admit",
     4: "engine.decode", 5: "engine.decode.wait", 6: "engine.prefill",
     7: "engine.prefill.wait"}, None, ["active", "rid", "tokens"])

NS_MS = 1e-6            # one ns in ms


@pytest.fixture(scope="module")
def serve():
    return reduce_text(SERVE)


def test_idle_goes_to_the_innermost_engine_span(serve):
    assert serve.window == pytest.approx((1000e-9, 1100e-9))
    assert serve.busy_s == pytest.approx(62e-9)
    idle = {}
    for name, s in serve.idle:
        idle[name] = idle.get(name, 0.0) + s
    assert idle == pytest.approx({"engine.decode.wait": 12e-9,
                                  "engine.step": 10e-9,
                                  "engine.prefill": 2e-9,
                                  "engine.prefill.wait": 14e-9})
    assert serve.busy_s + sum(idle.values()) == pytest.approx(100e-9)
    assert serve.count("engine.step") == 2
    prefill = [s for s in serve.spans if s.name == "engine.prefill"]
    assert [(s.args["rid"], s.args["tokens"]) for s in prefill] == [(5, 256)]


def test_each_op_gets_its_module_and_op_name(serve):
    got = [(o.name, o.module, o.op_name) for o in serve.ops]
    assert got == [
        ("paged_decode.1", "jit_engine_decode", DECODE_OP),
        ("copy.2", "jit_engine_decode", APPEND_OP),
        ("fusion.3", "jit_engine_prefill", ""),
        ("fusion.4", "jit_engine_prefill", ""),
        ("paged_decode.1", "jit_engine_decode", DECODE_OP)]
    assert serve.op_name_sources == ("op statistic",)
    assert serve.module_busy("jit_engine_decode") == (
        pytest.approx(48e-9), 2)
    assert serve.module_busy("jit_engine_prefill") == (
        pytest.approx(14e-9), 1)


@pytest.mark.parametrize("metric,value", [
    ("host_idle_ms.serve", 12 / 2 * NS_MS),
    ("sync_idle_ms.serve", 26 / 2 * NS_MS),
    ("decode_call_ms.serve", 48 / 2 * NS_MS),
    ("prefill_ms_per_ktok.serve", 14 * NS_MS / 256 * 1000),
])
def test_serve_readers_on_the_hand_trace(serve, metric, value):
    inputs = {"scopes": serve, "job": {"driver": "serve"}}
    assert reader(metric)(inputs) == pytest.approx(value, rel=1e-12)
    # a training run's inputs are not this metric's
    assert reader(metric)({"scopes": serve,
                           "job": {"driver": "train"}}) is None


# The same serving trace as the program before it had spans and names:
# the benchmark's own span, and modules named after Python's functions.
PARENT = (SERVE.replace("jit_engine_decode", "jit_decode")
          .replace("jit_engine_prefill", "jit_decode_step")
          .replace('"engine.', '"bench.engine_'))


@pytest.mark.parametrize("metric", [
    "host_idle_ms.serve", "sync_idle_ms.serve", "decode_call_ms.serve",
    "prefill_ms_per_ktok.serve", "recompute_share.train",
    "update_share.train"])
def test_readers_find_nothing_in_a_program_without_spans(metric):
    sc = reduce_text(PARENT)
    assert sc.spans == [] and sc.busy_s == pytest.approx(62e-9)
    driver = "train" if metric.endswith(".train") else "serve"
    assert reader(metric)({"scopes": sc, "job": {"driver": driver}}) is None
    assert reader(metric)({"trace_dir": None,
                           "job": {"driver": driver}}) is None


@pytest.mark.parametrize("op_name,want", [
    ("jit(train_step_ex)/step.update/fused_update/pallas_call", "update"),
    ("jit(train_step_ex)/transpose(jvp(step.forward))/while/body/"
     "closed_call/checkpoint/rematted_computation/flash_fwd", "recompute"),
    ("jit(train_step_ex)/transpose(jvp(step.forward))/while/body/"
     "closed_call/checkpoint/flash_dkv", "backward"),
    ("jit(train_step_ex)/jvp(step.forward)/while/body/closed_call/qmatmul",
     "forward"),
    ("jit(train_step_ex)/convert_element_type", "unscoped"),
    ("", "unscoped"),
])
def test_train_phase_of_an_op_name(op_name, want):
    assert scopes.phase(op_name) == want


def _scoped(x):
    with jax.named_scope("step.forward"):
        y = jnp.tanh(x @ x)
    with jax.named_scope("step.update"):
        return x - 0.1 * y


def test_hlo_op_names_read_a_module_proto():
    """The wire-format reader against a real module's own text."""
    c = jax.jit(_scoped).lower(jnp.ones((8, 8))).compile()
    module = c.runtime_executable().hlo_modules()[0]
    body = module.as_serialized_hlo_module_proto()
    n, size = len(body), bytearray()
    while True:                       # HloProto { hlo_module = 1 }
        size.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            break
    table = scopes.hlo_op_names(b"\x0a" + bytes(size) + body)
    text = module.to_string()
    assert table and any("step.forward" in v for v in table.values())
    assert any("step.update" in v for v in table.values())
    for name, op_name in table.items():
        assert f"%{name} = " in text or f" {name} = " in text, name
        if op_name:
            assert f'op_name="{op_name}"' in text, name


def test_op_names_from_the_module_hlo_of_a_cpu_trace(tmp_path):
    """A trace recorded on the CPU keeps each program's HLO in its
    metadata plane; the reduction names every operation from it."""
    from bench import trace
    f, x = jax.jit(_scoped), jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            f(x).block_until_ready()
    sc = scopes.reduce_file(trace.find_xplane(tmp_path))
    assert sc.op_name_sources == ("module HLO",)
    mine = [o for o in sc.ops if o.module == "jit__scoped"]
    assert mine and all(o.op_name.startswith("jit(_scoped)/")
                        for o in mine)
    assert {scopes.phase(o.op_name) for o in mine} == {"forward", "update"}
    inputs = {"scopes": sc, "job": {"driver": "train"}}
    t = sc.phase_time()
    assert reader("update_share.train")(inputs) == pytest.approx(
        100 * t["update"] / sum(t.values()))
    assert reader("recompute_share.train")(inputs) == 0.0


# Training, one module [0,100): ops of every phase, a loop that encloses
# two of them (busy time, no phase), and an op no scope names.
TRAIN_OPS = {
    2: ("%fusion.1 = f32[8]{0} fusion()",
        "jit(train_step_ex)/jvp(step.forward)/while/body/dot_general", 20),
    3: ("%fusion.2 = f32[8]{0} fusion()",
        "jit(train_step_ex)/transpose(jvp(step.forward))/while/body/"
        "checkpoint/rematted_computation/dot_general", 15),
    4: ("%fusion.3 = f32[8]{0} fusion()",
        "jit(train_step_ex)/transpose(jvp(step.forward))/while/body/"
        "checkpoint/dot_general", 40),
    5: ("%fused_update.1 = f32[8]{0} custom-call()",
        "jit(train_step_ex)/step.update/fused_update/pallas_call", 20),
    6: ("%copy.7 = f32[8]{0} copy()", None, 5),
}


def test_train_phase_shares_sum_to_the_busy_time():
    rows, t = [], 0
    for k, (_, _, d) in TRAIN_OPS.items():
        rows.append((k, t, d, ""))
        t += d
    rows.append((7, 20, 55, ""))               # while: encloses 2 ops
    names = {k: n for k, (n, _, _) in TRAIN_OPS.items()}
    names.update({1: "jit_train_step_ex(3)",
                  7: "%while.9 = (s32[]) while((s32[]) %t)"})
    sc = reduce_text(plane(
        1, "/device:TPU:0",
        [("XLA Modules", [(1, 0, 100, "")]), ("XLA Ops", rows)], names,
        {k: stat(1, o) for k, (_, o, _) in TRAIN_OPS.items() if o},
        ["tf_op"]))
    assert sc.busy_s == pytest.approx(100e-9)
    assert sc.phase_time() == pytest.approx(
        {"forward": 20e-9, "recompute": 15e-9, "backward": 40e-9,
         "update": 20e-9, "unscoped": 5e-9})
    shares = {p: scopes.phase_share(sc, p) for p in scopes.PHASES}
    assert sum(shares.values()) == pytest.approx(100.0)
    inputs = {"scopes": sc, "job": {"driver": "train"}}
    assert reader("recompute_share.train")(inputs) == pytest.approx(15.0)
    assert reader("update_share.train")(inputs) == pytest.approx(20.0)
    assert reader("host_idle_ms.serve")(inputs) is None
