"""The trace reduction on a hand-made trace and on one recorded on the CPU."""
import json
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"

# times in ns on one clock: device ops [0,10) [5,15) [20,30) on TPU:0 and
# [0,40) on TPU:1; host spans window [0,40), step [0,18), data [18,35)
HAND = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000 }
    events { metadata_id: 2 offset_ps: 5000 duration_ps: 10000 }
    events { metadata_id: 1 offset_ps: 20000 duration_ps: 10000 }
    events { metadata_id: 4 offset_ps: 20000 duration_ps: 10000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 40000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)" } }
  event_metadata { key: 2 value { id: 2 name: "%custom-call.2 = f32[8]{0} custom-call(f32[8]{0} %p)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step" } }
  event_metadata { key: 4 value { id: 4 name: "%while.3 = (s32[]) while((s32[]) %t)" } }
}
planes {
  id: 3 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 40000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 40000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 18000 }
    events { metadata_id: 3 offset_ps: 18000 duration_ps: 17000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.step" } }
  event_metadata { key: 3 value { id: 3 name: "bench.data" } }
}
"""


@pytest.fixture(scope="module")
def hand():
    from jax.profiler import ProfileData
    return trace.reduce_profile(ProfileData.from_text_proto(HAND))


def test_window_is_the_window_span(hand):
    assert hand.window == pytest.approx((1000e-9, 1040e-9))
    assert hand.window_s == pytest.approx(40e-9)


def test_busy_is_the_union_averaged_over_devices(hand):
    # TPU:0 union [0,15) + [20,30) = 25 ns; TPU:1 40 ns; mean 32.5 ns
    assert hand.n_devices == 2
    assert hand.busy_s == pytest.approx(32.5e-9)


def test_kernel_time_by_name_ignores_the_module_line_and_containers(hand):
    t = hand.op_time()
    assert t == pytest.approx({"fusion.1": 60e-9, "custom-call.2": 10e-9})
    renamed = hand.op_time(rename=lambda n: n.split(".")[0])
    assert renamed == pytest.approx({"fusion": 60e-9, "custom-call": 10e-9})


def test_gaps_go_to_the_innermost_enclosing_span(hand):
    # TPU:0 idles [15,20) (midpoint 17.5 in bench.step) and [30,40)
    # (midpoint 35 in bench.data); TPU:1 never idles
    gaps = dict(hand.gaps)
    assert sorted(gaps) == ["bench.data", "bench.step"]
    assert gaps["bench.step"] == pytest.approx(5e-9)
    assert gaps["bench.data"] == pytest.approx(10e-9)
    b = hand.breakdown()
    assert [n for n, _ in b["idle_gaps"]] == ["bench.data", "bench.step"]
    assert b["device_ops"][0][0] == "fusion.1"
    assert b["device_ops"][0][1] == pytest.approx(60e-9)


def test_instruction_texts_classify_kernels():
    from bench import kernels
    from jax.profiler import ProfileData
    text = ('%checkpoint.7 = f32[64,32]{1,0} custom-call(u32[2]{0} %s, f32'
            '[64,16]{1,0} %x, f32[16,32]{1,0} %w), custom_call_target="tpu_c'
            'ustom_call", operand_layout_constraints={u32[2]{0}, f32[64,16]{'
            '1,0}, f32[16,32]{1,0}}, frontend_attributes={kernel_metadata={}}')
    proto = f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 4000 }}
    events {{ metadata_id: 1 offset_ps: 8000 duration_ps: 4000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: {json.dumps(text)} }} }}
}}"""
    red = trace.reduce_profile(ProfileData.from_text_proto(proto))
    calls = kernels.custom_calls(red.instructions())
    assert list(calls) == ["checkpoint.7"]
    assert calls["checkpoint.7"].kind == "qmatmul"
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e12}
    share = kernels.roofline_share(red.ops, calls, {"qmatmul"}, peaks)
    flops, nbytes = 2 * 64 * 32 * 16, calls["checkpoint.7"].bytes
    assert share == pytest.approx(
        100 * 2 * max(flops, nbytes) / 1e12 / 8e-9)


def test_recorded_cpu_trace():
    red = trace.reduce_profile(trace.load(str(DATA / "cpu_window.xplane.pb")))
    names = [n for n, _, _ in red.spans]
    assert names.count("bench.step") == 2 and "bench.window" in names
    assert red.ops, "no XLA operation found in the recorded trace"
    assert 0 < red.busy_s <= red.window_s
    # busy and idle partition the window
    assert red.busy_s + sum(s for _, s in red.gaps) == pytest.approx(
        red.window_s, rel=1e-9)
    assert sum(red.op_time().values()) >= red.busy_s * (1 - 1e-9)
    assert {n for n, _ in red.gaps} <= {"bench.data", "bench.step",
                                        "no span"}
