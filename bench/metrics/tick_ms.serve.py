"""Mean host time of one engine iteration (``engine.step()``: admit, the
batched decode call, prefill chunks), in ms, from the harness's span
around each call over the whole window."""
from bench.metrics import _common


def read(inputs):
    if not _common.is_serve(inputs):
        return None
    lo, hi = inputs["window"]
    d = [t1 - t0 for t0, t1, *_ in inputs["steps"] if lo <= t0 < hi]
    if not d:
        return None
    return 1e3 * sum(d) / len(d)
