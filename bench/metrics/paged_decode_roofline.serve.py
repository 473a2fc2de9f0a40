"""Roofline share of the paged decode attention kernel
(``kernels/flash_attention.flash_decode_paged_p``) in the traced
interval, in percent: the live cache bytes the decode calls had to read
(each decoding request's actual length, not the block table's width)
over HBM bandwidth, or their attention FLOPs over the bf16 peak,
whichever bounds, over the kernel's summed device time."""
from bench import flops
from bench.metrics import _common


def read(inputs):
    red = inputs.get("reduced")
    if not _common.is_serve(inputs) or red is None:
        return None
    calls = _common.calls(inputs)
    spent = sum(ev.dur for ev in red.ops
                if ev.name in calls and calls[ev.name].kind == "paged_decode")
    if spent <= 0:
        return None
    per_tok = _common.kv_bytes_per_token(inputs)
    attn = _common.reference(inputs).attn_flops_per_cached_token(
        inputs["cfg"])
    least = 0.0
    for _, _, decoded, kv_live, _ in _common.traced_steps(inputs):
        if decoded:
            least += flops.least_time(attn * kv_live, per_tok * kv_live,
                                      inputs["peaks"])
    return 100.0 * least / spent
