"""A model family is files: its reference module, loaded by the name in
the configuration, maps the configuration to the program and counts the
model's work, with no edit of a driver or a metric."""
import json
import time

import jax
import numpy as np
import pytest

from bench import flops, harness, run
from bench.drivers import train as train_drv
from bench.reference import dense_llama
from bench.tests import tiny

HERE = run.BENCH / "tests"


@pytest.fixture(autouse=True)
def no_chip(monkeypatch):
    monkeypatch.setattr(harness, "load_peaks", lambda kind: tiny.PEAKS)


def dense_flops_per_token(cfg, seq):
    """The count ``flops.train_flops_per_token(cfg, seq)`` made for a
    dense configuration before a family's reference module made it."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, n = cfg["intermediate_size"], cfg["num_hidden_layers"]
    per_layer = d * nh * hd + 2 * d * nkv * hd + nh * hd * d + 3 * d * f
    mm = n * per_layer + d * cfg["vocab_size"]
    mean_ctx = flops.causal_pairs(seq, seq, 0, cfg["sliding_window"]) / seq
    return 6.0 * mm + 3 * 2 * 2 * nh * hd * mean_ctx * n


@pytest.mark.parametrize("config,traffic", [
    ("smollm-360m", "train-b8sr"), ("phi3-medium", "train-b8sr-lr1e-4"),
    ("smollm-360m", "train-wire4")])
def test_dense_llama_by_name_counts_as_before(config, traffic):
    cfg = json.loads((run.BENCH / "configs" / f"{config}.json").read_text())
    seq = json.loads((run.BENCH / "traffic" / f"{traffic}.json")
                     .read_text())["seq"]
    ref = run.load_reference(cfg)
    assert ref.train_flops_per_token(cfg, seq) == \
        dense_flops_per_token(cfg, seq)
    assert ref.train_flops_per_token(cfg, seq) == \
        dense_llama.train_flops_per_token(cfg, seq)


def test_dense_llama_by_name_follows_the_same_steps():
    cfg, job = tiny.config(), tiny.traffic("train-b8sr")
    by_name = run.load_reference(cfg)
    assert by_name.__file__ == dense_llama.__file__
    feed = train_drv.TokenFeed(cfg["vocab_size"], job["batch"], job["seq"],
                               2 ** 33 + 5)
    batches = feed.reference_batches()
    a = by_name.train_steps(cfg, job, by_name.init_params(cfg, 7), batches,
                            7)
    b = dense_llama.train_steps(cfg, job, dense_llama.init_params(cfg, 7),
                                batches, 7)
    assert a["losses"] == b["losses"]
    np.testing.assert_array_equal(a["grad_norms"], b["grad_norms"])
    np.testing.assert_array_equal(a["change_norms"], b["change_norms"])
    c = dense_llama.train_steps(cfg, job, dense_llama.init_params(cfg, 7),
                                batches, 8, control="int4")
    assert train_drv.gap_numbers(c, a) == train_drv.gap_numbers(c, b)


@pytest.fixture
def stub_family(monkeypatch):
    """A configuration of the stub family: the tiny dense model under a
    registry entry that carries an (unused) SSM config."""
    import dataclasses

    from repro import configs
    from repro.configs.base import SSMConfig
    monkeypatch.setattr(run, "REFERENCES", HERE)
    monkeypatch.setitem(configs.REGISTRY, "stub-dense", dataclasses.replace(
        configs.get_config("smollm-360m"), name="stub-dense",
        ssm=SSMConfig()))
    return dict(tiny.config(), registry="stub-dense",
                reference="stub_reference", ssm_chunk=32)


def test_dotted_field_replaces_a_nested_config(stub_family):
    from repro.configs.base import SSMConfig
    ref = run.load_reference(stub_family)
    mc = train_drv.model_config(stub_family, ref, None)
    assert mc.ssm == SSMConfig(chunk=32) != SSMConfig()
    assert (mc.d_model, mc.n_layers, mc.vocab_size) == (64, 2, 256)
    with pytest.raises(ValueError, match="no 'ssm' config"):
        train_drv.model_config(dict(stub_family, registry="smollm-360m"),
                               ref, None)


def test_new_family_runs_with_no_driver_edit(stub_family, tmp_path):
    ref = run.load_reference(stub_family)
    ref.calls.clear()
    out = train_drv.run({"name": "test"}, stub_family,
                        tiny.traffic("train-b8sr"), 2 ** 31 + 3, 0.5, False,
                        jax.devices(), time.perf_counter(), tmp_path)
    assert out.correct, [(c.name, c.value, c.limit) for c in out.checks]
    assert {"init_params", "train_steps"} <= set(ref.calls)
