"""The correctness check catches the control and the faults.

The drivers run here on the CPU (kernels in interpret mode) at a size a
test can hold, skipping the harness's look for a chip: a sound run is
correct, and each fault planted under the timed path makes ``correct``
false against the cell's own limits.  The control, the plain reference
computed in the next lower precision in the program's place, reads above
a limit too.
"""
import time

import jax
import pytest

from bench import control, harness
from bench.drivers import serve as serve_drv, train as train_drv
from bench.tests import tiny
from bench.tests.faults import half_batch, state_unchanged, wrap


@pytest.fixture(autouse=True)
def no_chip(monkeypatch):
    monkeypatch.setattr(harness, "load_peaks", lambda kind: tiny.PEAKS)


def run_train(job_name, tmp_path, **cfg_over):
    cfg = tiny.config(**cfg_over)
    job = tiny.traffic(job_name)
    return train_drv.run({"name": "test"}, cfg, job, 2 ** 31 + 7, 1.0,
                         False, jax.devices(), time.perf_counter(), tmp_path)


def wrap_train_step(monkeypatch, fault):
    from repro.launch import steps as steps_lib
    monkeypatch.setattr(steps_lib, "make_train_step",
                        wrap(steps_lib.make_train_step, fault))


@pytest.mark.parametrize("job", ["train-b8sr", "train-xla"])
def test_train_sound_run_is_correct(job, tmp_path):
    out = run_train(job, tmp_path)
    assert out.correct, [(c.name, c.value, c.limit) for c in out.checks]
    assert out.attempted >= 1 and out.failed == 0


@pytest.mark.parametrize("fault", [state_unchanged, half_batch],
                         ids=["state_unchanged", "half_batch"])
@pytest.mark.parametrize("job", ["train-b8sr", "train-xla"])
def test_train_fault_is_not_correct(job, fault, monkeypatch, tmp_path):
    wrap_train_step(monkeypatch, fault)
    out = run_train(job, tmp_path)
    assert not out.correct, [(c.name, c.value, c.limit) for c in out.checks]


@pytest.mark.parametrize("job", ["train-b8sr", "train-xla"])
def test_train_control_is_not_correct(job):
    cfg, traffic = tiny.config(), tiny.traffic(job)
    nums = control.variant_numbers(cfg, traffic, 11, "control")
    assert any(nums[k] > v for k, v in traffic["limits"].items()), nums


def run_serve(tmp_path):
    cfg, job = tiny.config(), tiny.traffic("serve-chat")
    return serve_drv.run({"name": "test"}, cfg, job, 2 ** 31 + 9, 2.0,
                         False, jax.devices(), time.perf_counter(), tmp_path)


def test_serve_sound_run_is_correct(tmp_path):
    out = run_serve(tmp_path)
    assert out.correct, [(c.name, c.value, c.limit) for c in out.checks]
    assert out.attempted >= 5 and out.failed == 0


def test_serve_altered_token_is_not_correct(monkeypatch, tmp_path):
    from repro.serving import engine as eng
    real = eng.ContinuousBatchingEngine._emit

    def emit(self, i, tok):
        return real(self, i, (tok + 1) % self.model.cfg.vocab_size)
    monkeypatch.setattr(eng.ContinuousBatchingEngine, "_emit", emit)
    out = run_serve(tmp_path)
    assert not out.correct, [(c.name, c.value, c.limit) for c in out.checks]


def test_serve_control_is_not_correct():
    cfg, job = tiny.config(), tiny.traffic("serve-chat")
    gap, prog = serve_drv.control_gap(cfg, job, 13)
    assert gap > job["limits"]["served_logit_gap"] >= prog, (gap, prog)


def four_device_runs(seed, *faults):
    """The tiny ``train-wire4`` job on four virtual CPU devices, in a
    process of its own (``four_devices.py``); one record per run."""
    import json
    import subprocess
    import sys
    from pathlib import Path
    here = Path(__file__).resolve().parent
    proc = subprocess.run(
        [sys.executable, str(here / "four_devices.py"), str(seed), *faults],
        capture_output=True, text=True, timeout=900, cwd=here.parents[1])
    assert proc.returncode == 0, proc.stderr[-4000:]
    return [json.loads(line) for line in proc.stdout.splitlines()
            if line.startswith("{")]


def test_wire_job_on_four_devices_is_correct_and_faults_are_not():
    runs = four_device_runs(2 ** 31 + 11, "sound", "state_unchanged",
                            "half_batch", "no_exchange")
    assert [r["fault"] for r in runs] == ["sound", "state_unchanged",
                                          "half_batch", "no_exchange"]
    sound, *faulty = runs
    assert sound["correct"] and sound["chips"] == 4, sound
    assert sound["attempted"] >= 3 and sound["failed"] == 0, sound
    for r in faulty:
        assert not r["correct"], r


def test_wire_control_is_not_correct():
    cfg, traffic = tiny.config(), tiny.traffic("train-wire4")
    nums = control.variant_numbers(cfg, traffic, 11, "control", chips=4)
    assert any(nums[k] > v for k, v in traffic["limits"].items()), nums
