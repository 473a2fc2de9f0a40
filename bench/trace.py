"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

* device operations: the events of each device plane's ``XLA Ops`` line
  (on a backend with no device plane, such as the CPU, the host events
  that carry an ``hlo_op`` statistic);
* busy time: the union of those intervals inside the window, averaged
  over the devices; the window is the host span ``bench.window`` where
  the run recorded one, else the extent of the device operations;
* kernel time by name: device time summed per operation name, where a
  name is the operation's HLO instruction name (a TPU trace names each
  event by the instruction's text, ``%name = type op(...)``; the CPU by
  an ``hlo_op`` statistic) unless the caller maps it to a stable one;
  control-flow containers (``while``, ``cond``, ``conditional``,
  ``call``), whose events enclose their bodies' operations, count for
  busy time only;
* idle gaps: each stretch inside the window in which no operation runs,
  attributed to the innermost ``bench.*`` host span that encloses its
  midpoint ("no span" where none does).

Times are in seconds.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class DeviceOp:
    name: str          # HLO instruction name
    device: str        # plane name
    start: float       # seconds
    dur: float         # seconds
    text: str = ""     # the instruction's text, where the trace gives it


@dataclasses.dataclass
class Reduced:
    ops: List[DeviceOp]
    spans: List[Tuple[str, float, float]]      # (name, start, end)
    window: Tuple[float, float]
    n_devices: int
    busy_s: float
    gaps: List[Tuple[str, float]]              # (enclosing span, seconds)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def op_time(self, rename=None) -> Dict[str, float]:
        """Device seconds per operation name inside the window, summed
        over devices; ``rename`` maps an HLO name to a stable one."""
        out: Dict[str, float] = defaultdict(float)
        for op in self.ops:
            if is_container(op.name):
                continue
            name = rename(op.name) if rename else op.name
            out[name] += op.dur
        return dict(out)

    def instructions(self) -> str:
        """The distinct instruction texts of the traced operations, one a
        line, in the compiled module's own syntax."""
        return "\n".join(sorted({op.text for op in self.ops if op.text}))

    def breakdown(self, rename=None, top: int = 10) -> dict:
        ops = sorted(self.op_time(rename).items(), key=lambda kv: -kv[1])
        idle: Dict[str, float] = defaultdict(float)
        for name, secs in self.gaps:
            idle[name] += secs
        gaps = sorted(idle.items(), key=lambda kv: -kv[1])
        return {"device_ops": [[n, s] for n, s in ops[:top]],
                "idle_gaps": [[n, s] for n, s in gaps[:top]]}


CONTAINERS = ("while", "conditional", "cond", "call")


def instruction_name(event_name: str) -> str:
    """``%fusion.12 = f32[8]{0} fusion(...)`` -> ``fusion.12``."""
    if event_name.startswith("%"):
        return event_name[1:].split(" ", 1)[0]
    return event_name


def is_container(name: str) -> bool:
    return name.split(".", 1)[0] in CONTAINERS


def _hlo_op(event) -> Optional[str]:
    for k, v in event.stats:
        if k == "hlo_op":
            return str(v)
    return None


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def find_xplane(trace_dir) -> str:
    paths = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def reduce_profile(pd, window: Optional[Tuple[float, float]] = None
                   ) -> Reduced:
    device_ops: List[DeviceOp] = []
    host_ops: List[DeviceOp] = []
    spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        is_device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                start, dur = ev.start_ns * 1e-9, ev.duration_ns * 1e-9
                if is_device:
                    if line.name == "XLA Ops":
                        device_ops.append(DeviceOp(
                            instruction_name(ev.name), plane.name, start,
                            dur, ev.name))
                    continue
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, start, start + dur))
                    continue
                hlo_op = _hlo_op(ev)
                if hlo_op is not None and dur > 0:
                    host_ops.append(DeviceOp(hlo_op, plane.name, start,
                                             dur))
    ops = device_ops or host_ops
    if window is None:
        wins = [(a, b) for n, a, b in spans if n == WINDOW_SPAN]
        if wins:
            window = (min(a for a, _ in wins), max(b for _, b in wins))
        elif ops:
            window = (min(o.start for o in ops),
                      max(o.start + o.dur for o in ops))
        else:
            window = (0.0, 0.0)
    lo, hi = window
    ops = [o for o in ops if o.start + o.dur > lo and o.start < hi]
    per_dev: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    for o in ops:
        per_dev[o.device].append((o.start, o.start + o.dur))
    n_dev = max(1, len(per_dev))
    busy = 0.0
    gaps: List[Tuple[str, float]] = []
    inner = [(n, a, b) for n, a, b in spans if n != WINDOW_SPAN]
    for dev, iv in per_dev.items():
        merged = _clip(_union(iv), lo, hi)
        busy += sum(b - a for a, b in merged)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((_enclosing(inner, (a + b) / 2), b - a))
    return Reduced(ops=ops, spans=spans, window=window, n_devices=n_dev,
                   busy_s=busy / n_dev, gaps=gaps)


def _enclosing(spans, t: float) -> str:
    best, width = "no span", float("inf")
    for name, a, b in spans:
        if a <= t <= b and b - a < width:
            best, width = name, b - a
    return best


def reduce_dir(trace_dir) -> Reduced:
    return reduce_profile(load(find_xplane(trace_dir)))
