"""The whole engine step's share of the chip's roofline, in percent: the
summed least time of the engine's device calls in the traced interval
over the device's busy time there.  A decode call needs its tokens'
matmul FLOPs and attention FLOPs, or one read of the bfloat16 weights
plus the live cache, whichever bounds; the prefill chunks of a step need
their tokens' matmul FLOPs, or one weight read per chunk (counted as the
fewest chunks their tokens fill), whichever bounds.  Every count is a
lower bound, so the share cannot pass 100% by a miscount; it stays
defined whichever kernels serve the step."""
from bench import flops
from bench.metrics import _common


def read(inputs):
    red = inputs.get("reduced")
    if not _common.is_serve(inputs) or red is None or red.busy_s <= 0:
        return None
    cfg, peaks, job = inputs["cfg"], inputs["peaks"], inputs["job"]
    n_mm = flops.matmul_params(cfg)
    w_bytes = 2.0 * n_mm
    per_tok = _common.kv_bytes_per_token(inputs)
    attn = 2.0 * cfg["num_attention_heads"] * cfg["head_dim"] * 2 \
        * cfg["num_hidden_layers"]
    chunk = job["engine"]["prefill_chunk"]
    least = 0.0
    for _, _, decoded, kv_live, prefilled in _common.traced_steps(inputs):
        if decoded:
            least += flops.least_time(2.0 * n_mm * decoded + attn * kv_live,
                                      w_bytes + per_tok * kv_live, peaks)
        if prefilled:
            n_calls = -(-prefilled // chunk)
            least += flops.least_time(2.0 * n_mm * prefilled,
                                      n_calls * w_bytes, peaks)
    if least <= 0:
        return None
    return 100.0 * least / red.busy_s
