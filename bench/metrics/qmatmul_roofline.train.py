"""Roofline share of the rounded GEMM kernels (``kernels/qmatmul.py``:
the 2-D qmatmul and the fused gate/up GLU kernel) in the traced train
steps, in percent: summed least time (2MNK FLOPs over the bf16 peak, or
operand and result bytes over HBM bandwidth) over summed device time."""
from bench import kernels
from bench.metrics import _common


def read(inputs):
    red = inputs.get("reduced")
    if not _common.is_train(inputs) or red is None:
        return None
    return kernels.roofline_share(red.ops, _common.calls(inputs),
                                  {"qmatmul", "qmatmul_glu"},
                                  inputs["peaks"])
