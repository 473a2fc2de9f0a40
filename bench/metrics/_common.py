"""Shared look-ups for the metric readers: the kernel calls named in the
reduced trace, made once per run."""
from __future__ import annotations

from bench import kernels


def calls(inputs: dict):
    """HLO instruction name -> kernel call, from the traced instructions'
    own text (a TPU trace names each operation by it)."""
    if "calls" not in inputs:
        red = inputs.get("reduced")
        inputs["calls"] = (kernels.custom_calls(red.instructions())
                           if red is not None else {})
    return inputs["calls"]


def is_train(inputs: dict) -> bool:
    return inputs.get("job", {}).get("driver") == "train"


def is_serve(inputs: dict) -> bool:
    return inputs.get("job", {}).get("driver") == "serve"


def traced_steps(inputs: dict):
    """Engine steps (t0, t1, decoded, kv_live, prefilled) that ran inside
    the traced interval."""
    tr = inputs.get("traced", {})
    if "start" not in tr or "stop" not in tr:
        return []
    return [s for s in inputs["steps"]
            if s[0] >= tr["start"] and s[1] <= tr["stop"]]


def kv_bytes_per_token(inputs: dict) -> float:
    """Bytes of one cached token over all layers: packed 8-bit keys and
    values of every kv head (the e4m3 cache stores one byte a value)."""
    cfg = inputs["cfg"]
    return (2.0 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * cfg["head_dim"])
