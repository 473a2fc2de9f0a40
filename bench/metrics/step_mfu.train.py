"""Model FLOP utilization of the train step, in percent of one chip's
peak: model FLOPs a token (the configuration's reference module's
``train_flops_per_token``, recomputation not counted) times the tokens
the traced steps trained on all chips, over the traced window, over the
cell's chips times one chip's bf16 peak."""
from bench.metrics import _common


def read(inputs):
    red = inputs.get("reduced")
    if not _common.is_train(inputs) or red is None or red.window_s <= 0:
        return None
    cfg, job = inputs["cfg"], inputs["job"]
    per_token = _common.reference(inputs).train_flops_per_token(
        cfg, job["seq"])
    work = per_token * inputs["steps"] * inputs["tokens_per_step"]
    peak = inputs["chips"] * inputs["peaks"]["bf16_flops_per_s"]
    return 100.0 * work / red.window_s / peak
