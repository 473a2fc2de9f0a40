"""The program's own spans and scopes in a profiler trace (``.xplane.pb``).

``trace.py`` reduces a trace to device busy time and idle gaps named by
the benchmark's spans; this module reads the same file for what the
program itself writes into it:

* host spans: the serving engine's ``engine.*`` spans
  (``serving/engine.py``), with start, end and their arguments;
* for each device operation inside the window, its module (the
  ``XLA Modules`` event that encloses it on its device plane; on a
  backend with no device plane, the host event's ``hlo_module``
  statistic) and its ``op_name``, the scope path of the JAX operation it
  came from.  The ``op_name`` is the operation's own ``tf_op`` statistic
  where the trace carries one; otherwise it is read from the module's
  optimized HLO, which the trace keeps in its ``/host:metadata`` plane;
* idle intervals inside the window, found as ``trace.py`` finds them,
  each given to the innermost ``engine.*`` span enclosing its midpoint
  ("no span" where none does);
* a train phase for each ``op_name`` (``phase``): the scopes of
  ``launch/steps.make_train_step`` and the names autodiff and
  ``jax.checkpoint`` give them.

``jax.profiler.ProfileData`` gives events and their own statistics, but
not the statistics of an event's metadata nor the metadata plane, so
those two are read from the file's protobuf wire format directly
(``_fields``): the XSpace and HloProto field numbers below are those of
``xplane.proto`` and ``xla/service/hlo.proto``.

Times are in seconds.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from bench import trace

SPAN_PREFIX = "engine."
WAIT_SUFFIX = ".wait"
NO_SPAN = "no span"
OP_NAME_STAT = "tf_op"          # on a TPU device plane's operations
HLO_STAT = "Hlo Proto"          # on the metadata plane, keyed by program
PHASES = ("update", "recompute", "backward", "forward", "unscoped")


def phase(op_name: str) -> str:
    """The train phase of one operation, from its ``op_name``."""
    if "step.update" in op_name:
        return "update"
    if "/rematted_computation/" in op_name:
        return "recompute"
    if "transpose(" in op_name:
        return "backward"
    if "step.forward" in op_name:
        return "forward"
    return "unscoped"


# ------------------------------------------------------- protobuf wire --
def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf: bytes):
    """(field number, value) of one message: an int for varints, the
    bytes of a length-delimited field, raw bytes for fixed widths."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        no, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            val, i = buf[i:i + ln], i + ln
        elif wt == 1:
            val, i = buf[i:i + 8], i + 8
        elif wt == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield no, val


def _str(b) -> str:
    return bytes(b).decode("utf-8", "replace")


# XStat: metadata_id 1; str_value 5, bytes_value 6; int64/uint64 3, 4
def _stat(buf, names: Dict[int, str]):
    sid, val = None, None
    for no, v in _fields(buf):
        if no == 1:
            sid = v
        elif no in (3, 4, 7):
            val = v
        elif no == 5:
            val = _str(v)
        elif no == 6:
            val = bytes(v)
    return names.get(sid, str(sid)), val


@dataclasses.dataclass
class _Plane:
    name: str
    events: Dict[int, Tuple[str, dict]]   # metadata id -> (name, stats)


def _planes(raw: bytes, keep) -> List[_Plane]:
    """Event metadata (name and statistics) of the XSpace's planes whose
    name ``keep`` accepts."""
    out = []
    for no, pbuf in _fields(raw):                     # XSpace.planes = 1
        if no != 1:
            continue
        name, ev_raw, st_raw = "", [], []
        for fno, v in _fields(pbuf):
            if fno == 2:                                # XPlane.name
                name = _str(v)
            elif fno == 4:                              # event_metadata
                ev_raw.append(v)
            elif fno == 5:                              # stat_metadata
                st_raw.append(v)
        if not keep(name):
            continue
        stat_names = {}
        for entry in st_raw:                            # map entry: 1, 2
            for eno, v in _fields(entry):
                if eno == 2:
                    sm = dict(_fields(v))               # id 1, name 2
                    stat_names[sm.get(1, 0)] = _str(sm.get(2, b""))
        events = {}
        for entry in ev_raw:
            for eno, v in _fields(entry):
                if eno != 2:
                    continue
                mid, mname, stats = 0, "", {}
                for mno, mv in _fields(v):              # XEventMetadata
                    if mno == 1:
                        mid = mv
                    elif mno == 2:
                        mname = _str(mv)
                    elif mno == 5:
                        k, sv = _stat(mv, stat_names)
                        stats[k] = sv
                events[mid] = (mname, stats)
        out.append(_Plane(name, events))
    return out


def hlo_op_names(hlo_proto: bytes) -> Dict[str, str]:
    """Instruction name -> ``op_name`` of every instruction of an
    HloProto (``hlo_module`` 1; HloModuleProto ``computations`` 3;
    HloComputationProto ``instructions`` 2; HloInstructionProto ``name``
    1 and ``metadata`` 7; OpMetadata ``op_name`` 2)."""
    out = {}
    module = next((v for no, v in _fields(hlo_proto) if no == 1), b"")
    for no, comp in _fields(module):
        if no != 3:
            continue
        for cno, inst in _fields(comp):
            if cno != 2:
                continue
            name, op_name = "", ""
            for ino, v in _fields(inst):
                if ino == 1:
                    name = _str(v)
                elif ino == 7:
                    op_name = next((_str(m) for mno, m in _fields(v)
                                    if mno == 2), "")
            out[name] = op_name
    return out


def module_name(event_name: str) -> str:
    """``jit_engine_decode(17)`` -> ``jit_engine_decode``."""
    return event_name.split("(", 1)[0]


def _program_id(event_name: str, stats: dict) -> Optional[int]:
    """The id that keys a program's HLO in the metadata plane: a host
    event's ``program_id`` statistic (CPU), or the number in a device
    module event's name, ``jit_f(1234)`` (TPU)."""
    if "program_id" in stats:
        return int(stats["program_id"])
    m = re.search(r"\((\d+)\)\s*$", event_name)
    return int(m.group(1)) if m else None


class OpNames:
    """``op_name`` look-ups from a trace's raw bytes: each device plane's
    operation statistics, and each program's HLO from the metadata
    plane, parsed when first asked for."""

    def __init__(self, raw: bytes):
        self.by_text: Dict[Tuple[str, str], str] = {}
        self._hlo: Dict[int, bytes] = {}
        self._parsed: Dict[int, Dict[str, str]] = {}
        self.sources = set()
        for pl in _planes(raw, lambda n: n.startswith("/device:")
                          or n == "/host:metadata"):
            for mid, (name, stats) in pl.events.items():
                if pl.name == "/host:metadata":
                    if isinstance(stats.get(HLO_STAT), bytes):
                        self._hlo[mid] = stats[HLO_STAT]
                elif isinstance(stats.get(OP_NAME_STAT), str):
                    self.by_text[(pl.name, name)] = stats[OP_NAME_STAT]

    def lookup(self, device: str, text: str, inst: str,
               pid: Optional[int]) -> str:
        got = self.by_text.get((device, text))
        if got is not None:
            self.sources.add("op statistic")
            return got
        if pid not in self._hlo:
            return ""
        if pid not in self._parsed:
            self._parsed[pid] = hlo_op_names(self._hlo[pid])
        if inst not in self._parsed[pid]:
            return ""
        self.sources.add("module HLO")
        return self._parsed[pid][inst]


# ----------------------------------------------------------- reduction --
@dataclasses.dataclass
class Op:
    name: str          # HLO instruction name
    module: str        # module name, program id dropped
    op_name: str       # the JAX scope path; "" where the trace has none
    device: str
    start: float
    dur: float


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    args: dict


@dataclasses.dataclass
class Scopes:
    ops: List[Op]                       # containers too (busy time)
    modules: List[Tuple[str, str, float, float]]    # (device, name, a, b)
    spans: List[Span]                               # engine.*, in window
    window: Tuple[float, float]
    n_devices: int
    busy_s: float
    idle: List[Tuple[str, float]]       # (innermost engine span, seconds)
    op_name_sources: Tuple[str, ...]

    def count(self, span: str) -> int:
        return sum(s.name == span for s in self.spans)

    def idle_s(self, keep) -> float:
        """Idle seconds (mean over devices) in spans ``keep`` accepts."""
        return sum(s for n, s in self.idle if keep(n)) / self.n_devices

    def module_busy(self, module: str) -> Tuple[float, int]:
        """(device-busy seconds inside the module's executions, summed
        over devices, and the number of executions) in the window."""
        busy, count = 0.0, 0
        per_dev: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
        for o in self.ops:
            per_dev[o.device].append((o.start, o.start + o.dur))
        lo, hi = self.window
        for dev, name, a, b in self.modules:
            if name != module:
                continue
            count += 1
            a, b = max(a, lo), min(b, hi)
            busy += sum(y - x for x, y in
                        trace._clip(trace._union(per_dev[dev]), a, b))
        return busy, count

    def phase_time(self) -> Dict[str, float]:
        """Device seconds per train phase, summed over devices."""
        out = dict.fromkeys(PHASES, 0.0)
        for o in self.ops:
            if not trace.is_container(o.name):
                out[phase(o.op_name)] += o.dur
        return out


def _innermost(spans: List[Span], t: float) -> str:
    best, width = NO_SPAN, float("inf")
    for s in spans:
        if s.start <= t <= s.end and s.end - s.start < width:
            best, width = s.name, s.end - s.start
    return best


def reduce_bytes(raw: bytes,
                 window: Optional[Tuple[float, float]] = None) -> Scopes:
    """``raw``: the serialized trace; ``window``: as
    ``trace.reduce_profile`` takes it (default: the ``bench.window``
    span, else the extent of the device operations)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(raw)
    names = OpNames(raw)
    dev_ops, host_ops, modules, spans, wins = [], [], [], [], []
    for plane in pd.planes:
        is_device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                start, dur = ev.start_ns * 1e-9, ev.duration_ns * 1e-9
                if is_device:
                    if line.name == "XLA Modules":
                        stats = {k: v for k, v in ev.stats}
                        modules.append((plane.name, module_name(ev.name),
                                        start, start + dur,
                                        _program_id(ev.name, stats)))
                    elif line.name == "XLA Ops":
                        dev_ops.append((plane.name, ev.name, start, dur))
                    continue
                if ev.name == trace.WINDOW_SPAN:
                    wins.append((start, start + dur))
                elif ev.name.startswith(SPAN_PREFIX):
                    # a span's arguments are statistics of its event
                    spans.append(Span(ev.name, start, start + dur,
                                      {k: v for k, v in ev.stats}))
                else:
                    stats = {k: v for k, v in ev.stats}
                    if "hlo_op" in stats and dur > 0:
                        inst = str(stats["hlo_op"])
                        mod = str(stats.get("hlo_module", ""))
                        host_ops.append(Op(inst, mod, names.lookup(
                            plane.name, "", inst, _program_id("", stats)),
                            plane.name, start, dur))
    if window is None:
        if wins:
            window = (min(a for a, _ in wins), max(b for _, b in wins))
        else:
            every = [(o[2], o[2] + o[3]) for o in dev_ops] or \
                [(o.start, o.start + o.dur) for o in host_ops]
            window = ((min(a for a, _ in every), max(b for _, b in every))
                      if every else (0.0, 0.0))
    lo, hi = window
    # each device operation in the module execution that encloses it
    by_dev: Dict[str, list] = defaultdict(list)
    for m in sorted(modules, key=lambda m: m[2]):
        by_dev[m[0]].append(m)
    starts = {d: [m[2] for m in v] for d, v in by_dev.items()}
    ops: List[Op] = []
    for dev, text, start, dur in dev_ops:
        inst = trace.instruction_name(text)
        mod, pid = "", None
        k = bisect.bisect_right(starts.get(dev, []), start) - 1
        if k >= 0 and start + dur <= by_dev[dev][k][3]:
            mod, pid = by_dev[dev][k][1], by_dev[dev][k][4]
        ops.append(Op(inst, mod, names.lookup(dev, text, inst, pid),
                      dev, start, dur))
    ops = ops or host_ops
    ops = [o for o in ops if o.start + o.dur > lo and o.start < hi]
    per_dev: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    for o in ops:
        per_dev[o.device].append((o.start, o.start + o.dur))
    spans = [s for s in spans if s.end > lo and s.start < hi]
    busy, idle = 0.0, []
    for dev, iv in per_dev.items():
        merged = trace._clip(trace._union(iv), lo, hi)
        busy += sum(b - a for a, b in merged)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                idle.append((_innermost(spans, (a + b) / 2), b - a))
    n_dev = max(1, len(per_dev))
    return Scopes(
        ops=ops, modules=[(d, n, a, b) for d, n, a, b, _ in modules
                 if b > lo and a < hi],
        spans=spans, window=window, n_devices=n_dev, busy_s=busy / n_dev,
        idle=idle, op_name_sources=tuple(sorted(names.sources)))


def reduce_file(path: str, window=None) -> Scopes:
    with open(path, "rb") as f:
        return reduce_bytes(f.read(), window)


def of(inputs: dict) -> Optional[Scopes]:
    """The run's reduction, made once and kept in ``inputs``; None where
    the run recorded no trace."""
    if "scopes" not in inputs:
        d = inputs.get("trace_dir")
        red = inputs.get("reduced")
        inputs["scopes"] = None if d is None else reduce_file(
            trace.find_xplane(d), red.window if red is not None else None)
    return inputs["scopes"]


def phase_share(sc: Scopes, name: str) -> Optional[float]:
    """Percent of the operations' device time in train phase ``name``;
    None where no operation carries the ``step.forward`` scope (a
    program without the step's scopes)."""
    if not any("step.forward" in o.op_name for o in sc.ops):
        return None
    t = sc.phase_time()
    total = sum(t.values())
    return 100.0 * t[name] / total if total > 0 else None
