"""Roofline share of the flash-attention kernels
(``kernels/flash_attention.py``: forward, dq and dk/dv backward) in the
traced train steps, in percent, counting only the query-key pairs the
causal mask (and the configuration's window) keeps."""
from bench import kernels
from bench.metrics import _common


def read(inputs):
    red = inputs.get("reduced")
    if not _common.is_train(inputs) or red is None:
        return None
    return kernels.roofline_share(
        red.ops, _common.calls(inputs), {"flash_fwd", "flash_dq",
                                         "flash_dkv"},
        inputs["peaks"], causal=True,
        window=inputs["cfg"].get("sliding_window", 0))
