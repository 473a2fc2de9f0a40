"""Which device operation is which kernel, and the work each call needs.

The program's Pallas kernels reach the compiled module as
``tpu_custom_call`` instructions with no stable name, so a kernel is
known here by its signature, the operand and result shapes that its
``pallas_call`` fixes:

* ``qmatmul``: seed words u32[2], a (M, K), b (K, N) -> (M, N);
* ``qmatmul_glu`` (the fused gate/up GEMM with its activation): seed
  words u32[3, 2], x (M, K), w_gate (K, F), w_up (K, F) -> three (M, F);
* ``flash_fwd``: words u32[BH, 6], q (BH, S, dk), k, v (BKV, S, d)
  -> (out, m, l);
* ``flash_dq``: words u32[BH, 4], q, k, v, dO, m, l, d -> dq;
* ``flash_dkv``: words u32[BH, 6], q, k, v, dO, m, l, d -> (dk, dv);
* ``fused_update``: u32[2], f32[1], two (R, 128) -> (R, 128);
* ``paged_decode``: words u32[B·KV, 6], lengths s32[B], block tables
  s32[B, n_max], q (B·KV, G, dk), key and value pages (P·KV, page, d)
  -> (B·KV, G, dv).

A TPU trace names each device event by its instruction's text, so the
signatures are read from the trace itself.
Operations and bytes come from ``flops.py``; bytes are the call's
operands read once and results written once.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

from bench import flops

DTYPE_BYTES = {"f32": 4, "u32": 4, "s32": 4, "bf16": 2, "f16": 2,
               "u16": 2, "s16": 2, "u8": 1, "s8": 1, "pred": 1, "f64": 8}

_LINE = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*?) custom-call\(")
_SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
_OPERANDS = re.compile(r"operand_layout_constraints=\{(.*?)\}, [a-z_]+=")


Shape = Tuple[str, Tuple[int, ...]]


def _shapes(text: str) -> List[Shape]:
    text = re.sub(r"\{[^{}]*\}", "", text)        # drop layouts
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in _SHAPE.findall(text) if dt in DTYPE_BYTES]


def nbytes(shapes: List[Shape]) -> int:
    total = 0
    for dt, dims in shapes:
        n = 1
        for d in dims:
            n *= d
        total += n * DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class Call:
    kind: str
    operands: List[Shape]
    results: List[Shape]

    @property
    def bytes(self) -> int:
        return nbytes(self.operands) + nbytes(self.results)


def classify(operands: List[Shape], results: List[Shape]) -> Optional[str]:
    dims = [d for _, d in operands]
    if not dims or operands[0][0] != "u32":
        return None
    words = dims[0]
    if len(dims) == 6 and operands[1][0] == "s32" and len(dims[1]) == 1 \
            and len(dims[2]) == 2 and len(dims[4]) == 3:
        return "paged_decode"
    if words == (2,) and len(dims) == 3 and len(results) == 1:
        a, b = dims[1], dims[2]
        if len(a) == 2 and len(b) == 2 and a[1] == b[0]:
            return "qmatmul"
    if words == (3, 2) and len(dims) == 4 and len(results) == 3:
        return "qmatmul_glu"
    if len(words) == 2 and len(dims) >= 4 and len(dims[1]) == 3:
        if len(dims) == 4 and len(results) == 3:
            return "flash_fwd"
        if len(dims) == 8 and len(results) == 1:
            return "flash_dq"
        if len(dims) == 8 and len(results) == 2:
            return "flash_dkv"
    if words == (2,) and len(dims) == 4 and dims[1] == (1,):
        return "fused_update"
    return None


def custom_calls(hlo_text: str) -> Dict[str, Call]:
    """HLO instruction name -> classified kernel call, from HLO text (a
    module's, or the traced instructions' one a line)."""
    out: Dict[str, Call] = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = _LINE.match(line)
        ops = _OPERANDS.search(line)
        if not m or not ops:
            continue
        operands, results = _shapes(ops.group(1)), _shapes(m.group(2))
        kind = classify(operands, results)
        if kind is not None:
            out[m.group(1)] = Call(kind, operands, results)
    return out


def call_flops(call: Call, *, causal: bool = True, window: int = 0) -> float:
    d = [dims for _, dims in call.operands]
    if call.kind == "qmatmul":
        (m, k), (_, n) = d[1], d[2]
        return 2.0 * m * n * k
    if call.kind == "qmatmul_glu":
        (m, k), (_, f) = d[1], d[2]
        return 2.0 * 2.0 * m * k * f
    if call.kind.startswith("flash_"):
        bh, sq, dk = d[1]
        skv, dv = d[2][1], d[3][2]
        return flops.attention_flops(call.kind[len("flash_"):], bh, sq, skv,
                                     dk, dv, causal=causal, window=window)
    raise ValueError(f"no operation count for {call.kind}")


def roofline_share(events, calls: Dict[str, Call], kinds, peaks: dict,
                   **kw) -> Optional[float]:
    """Percent: summed least time of the kernel calls of ``kinds`` over
    their summed device time; None where the trace holds no such call."""
    least = spent = 0.0
    for ev in events:
        call = calls.get(ev.name)
        if call is None or call.kind not in kinds:
            continue
        least += flops.least_time(call_flops(call, **kw), call.bytes, peaks)
        spent += ev.dur
    if spent <= 0:
        return None
    return 100.0 * least / spent
