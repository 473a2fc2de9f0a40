"""Share of the traced training window in which no operation ran on the
device, in percent: 1 - busy union / window."""
from bench.metrics import _common


def read(inputs):
    red = inputs.get("reduced")
    if not _common.is_train(inputs) or red is None or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
