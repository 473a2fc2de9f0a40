"""What every driver shares: the device check, the table of peaks, the
set-up phases, the compile counter inside the window, and the compared
numbers.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import time
from pathlib import Path
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parent


class NoDevice(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int):
    """The TPU devices to run on; never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devs)}")
    return devs[:chips]


def load_peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error."""
    table = json.loads((BENCH / "peaks.json").read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table['devices'])})")


def device_record(devs) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


class Phases:
    """Host-clock marks through set-up, printed as one line of notes."""

    def __init__(self, t_start: float):
        self.last = t_start
        self.marks: List[tuple] = []

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.marks.append((name, now - self.last))
        self.last = now

    def line(self) -> str:
        return "set-up phases: " + ", ".join(f"{n} {s:.2f} s"
                                            for n, s in self.marks)


class CompileCounter:
    """Counts tracing, compilation and compile-cache lookups while armed.

    JAX reports each through ``jax.monitoring``; a warm window has none."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_hits",
              "/jax/compilation_cache/cache_misses")

    def __init__(self):
        import jax.monitoring as mon
        self.armed = False
        self.count = 0
        self.names: List[str] = []
        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, name, **_):
        if self.armed and name in self.EVENTS:
            self.count += 1
            self.names.append(name)

    def _on_duration(self, name, _secs, **_):
        self._on_event(name)

    @contextlib.contextmanager
    def window(self):
        self.armed = True
        try:
            yield self
        finally:
            self.armed = False


@dataclasses.dataclass
class Check:
    """One compared number and its limit: the run is correct only where
    ``value <= limit`` (a number that is missing or not finite fails)."""

    name: str
    value: Optional[float]
    limit: float

    @property
    def ok(self) -> bool:
        v = self.value
        return v is not None and v == v and abs(v) != float("inf") \
            and v <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a driver hands back to ``run.py``."""

    attempted: int
    failed: int
    checks: List[Check]
    end_to_end: Dict[str, float]
    device: dict
    layer_inputs: dict = dataclasses.field(default_factory=dict)
    notes: List[str] = dataclasses.field(default_factory=list)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)
