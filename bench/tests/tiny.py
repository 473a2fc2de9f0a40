"""Tiny configurations and jobs for running the drivers on the CPU.

The cells' own files set the published widths and the chip's sizes; the
tests keep every key and shrink the sizes so that a CPU run in Pallas
interpret mode ends in seconds.

The cells' limits were set from readings at their own sizes on the chip.
At these sizes sound runs read more (a leaf of a few thousand elements
averages its rounding noise less), so the tests hold the drivers to
limits of their own, set the same way from readings at this size: above
what sound runs read here, below what the control and the faults read.
"""
from __future__ import annotations

import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def config(name: str = "smollm-360m", **over) -> dict:
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    cfg.update(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, intermediate_size=128,
               vocab_size=256)
    cfg.update(over)
    return cfg


# readings at this size (CPU): sound train-b8sr 5.4e-4 / 0.063 / 0.056
# (loss / grad / change gap), its int4 control 3.3e-3 / 0.71 / 0.12;
# sound train-xla 7.9e-5 / 0.0023 / 0.030, its fp8 control 6.7e-4 /
# 0.029 / 0.038; a state left unchanged reads 1; sound serving 0.058,
# a served token altered 0.80
TEST_LIMITS = {
    "train-b8sr": {"loss_gap": 0.002, "grad_norm_gap": 0.25,
                   "change_norm_gap": 0.1},
    "train-xla": {"loss_gap": 4e-4, "grad_norm_gap": 0.012,
                  "change_norm_gap": 0.1},
    "serve-chat": {"served_logit_gap": 0.3},
}


def traffic(name: str = "train-b8sr", **over) -> dict:
    job = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    job["limits"] = dict(TEST_LIMITS[name])
    if job["driver"] == "train":
        job.update(batch=2, seq=64, trace_steps=2)
    if job["driver"] == "serve":
        job.update(rate_per_s=8.0, drain_seconds=20, trace_seconds=1,
                   check_sample=3,
                   prompt=dict(job["prompt"], median=40, min=16, max=96,
                               multiple=16),
                   output=dict(job["output"], median=6, min=2, max=12),
                   engine=dict(job["engine"], n_slots=4, page_size=16,
                               max_pages_per_request=8, prefill_chunk=32,
                               token_budget=64))
    job.update(over)
    return job
