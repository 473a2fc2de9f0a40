"""Model FLOP utilization of the train step, in percent: model FLOPs a
token (``flops.train_flops_per_token``, recomputation not counted) times
the tokens the traced steps trained, over the traced window, over the
chip's bf16 peak."""
from bench import flops
from bench.metrics import _common


def read(inputs):
    red = inputs.get("reduced")
    if not _common.is_train(inputs) or red is None or red.window_s <= 0:
        return None
    cfg, job = inputs["cfg"], inputs["job"]
    work = (flops.train_flops_per_token(cfg, job["seq"]) * inputs["steps"]
            * inputs["tokens_per_step"])
    return 100.0 * work / red.window_s / inputs["peaks"]["bf16_flops_per_s"]
