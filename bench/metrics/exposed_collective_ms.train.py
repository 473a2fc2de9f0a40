"""Exposed collective time of the train step, in ms per traced step: the
device time during which a collective operation (all-reduce,
reduce-scatter, all-gather, all-to-all, collective-permute, and their
``-start``/``-done`` halves) runs and no other operation does, summed
over each chip's traced window, averaged over the chips, over the traced
steps.  A collective that overlaps compute costs nothing here; one the
step waits on counts in full.  None where no collective ran."""
import re

from bench import trace
from bench.metrics import _common

COLLECTIVE = re.compile(r"(all-reduce|reduce-scatter|all-gather|all-to-all"
                        r"|collective-permute)(-start|-done)?")
# the opcode in an instruction's text, ``%name = type opcode(operands)``
# (a tuple type holds no ``word(``)
OPCODE = re.compile(r"%\S+ = .*? ([a-z][a-z0-9-]*)\(")


def is_collective(op) -> bool:
    """By the opcode where the trace gives the instruction's text (a TPU
    trace does), else by the instruction's name (``all-gather.3``)."""
    m = OPCODE.match(op.text)
    word = m.group(1) if m else op.name.rsplit(".", 1)[0]
    return COLLECTIVE.fullmatch(word) is not None


def exposed_s(red) -> float:
    """Seconds, summed over devices, in which a collective runs alone."""
    lo, hi = red.window
    per_dev = {}
    for op in red.ops:
        if trace.is_container(op.name):
            continue
        coll, other = per_dev.setdefault(op.device, ([], []))
        (coll if is_collective(op) else other).append(
            (op.start, op.start + op.dur))
    total = 0.0
    for coll, other in per_dev.values():
        busy = trace._clip(trace._union(other), lo, hi)
        for a, b in trace._clip(trace._union(coll), lo, hi):
            total += b - a - sum(max(0.0, min(b, y) - max(a, x))
                                 for x, y in busy)
    return total


def read(inputs):
    red = inputs.get("reduced")
    if not _common.is_train(inputs) or red is None or not inputs["steps"]:
        return None
    if not any(is_collective(op) for op in red.ops):
        return None
    return 1e3 * exposed_s(red) / red.n_devices / inputs["steps"]
