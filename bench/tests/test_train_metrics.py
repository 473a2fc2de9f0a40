"""The training readers on hand-made reductions: model FLOP utilization
is a share of one chip's peak whatever the chip count, and exposed
collective time is what no other operation covers."""
import pytest

from bench import run
from bench.tests import tiny
from bench.trace import DeviceOp, Reduced


def reader(name):
    return run.load_module(run.BENCH / "metrics" / f"{name}.py")


def reduced(ops, window=(0.0, 1.0), n_devices=1):
    return Reduced(ops=ops, spans=[], window=window, n_devices=n_devices,
                   busy_s=0.0, gaps=[])


def train_inputs(red, **over):
    cfg = run.load_cell("smollm-train-b8sr")[2]
    job = dict(tiny.traffic("train-b8sr"), batch=16, seq=1024)
    return dict({"reduced": red, "cfg": cfg, "job": job, "steps": 3,
                 "tokens_per_step": 16 * 1024, "chips": 1,
                 "peaks": tiny.PEAKS}, **over)


def test_step_mfu_train_divides_by_the_chips():
    mfu = reader("step_mfu.train")
    red = reduced([], window=(0.0, 2.0))
    one = mfu.read(train_inputs(red))
    four = mfu.read(train_inputs(red, chips=4))
    cfg = run.load_cell("smollm-train-b8sr")[2]
    per_token = run.load_reference(cfg).train_flops_per_token(cfg, 1024)
    assert one == pytest.approx(100.0 * per_token * 3 * 16 * 1024 / 2.0
                                / 197e12)
    assert four == pytest.approx(one / 4)


def op(name, device, start, dur, text=""):
    return DeviceOp(name, device, start, dur, text)


def test_exposed_collective_counts_only_bare_collective_time():
    read = reader("exposed_collective_ms.train").read
    # chip 0: a reduce-scatter [0.10, 0.30) half under a fusion [0.20,
    # 0.40), an all-gather [0.50, 0.60) bare, a while container over all
    # of it (busy time only); chip 1: an async all-reduce pair whose done
    # half [0.70, 0.75) is bare and whose start half [0.60, 0.62) lies
    # under a convolution [0.55, 0.65); a fusion named after nothing
    ops = [
        op("reduce-scatter.1", "/device:TPU:0", 0.10, 0.20),
        op("fusion.3", "/device:TPU:0", 0.20, 0.20),
        op("all-gather.2", "/device:TPU:0", 0.50, 0.10,
           "%all-gather.2 = f32[64]{0} all-gather(f32[16]{0} %x), "
           "dimensions={0}"),
        op("while.1", "/device:TPU:0", 0.0, 1.0),
        op("all-reduce-start.4", "/device:TPU:1", 0.60, 0.02,
           "%all-reduce-start.4 = (f32[8]{0}, f32[8]{0}) "
           "all-reduce-start(f32[8]{0} %g), to_apply=%add"),
        op("convolution.5", "/device:TPU:1", 0.55, 0.10),
        op("all-reduce-done.4", "/device:TPU:1", 0.70, 0.05),
        op("fusion.6", "/device:TPU:1", 0.80, 0.10,
           "%fusion.6 = f32[8]{0} fusion(f32[8]{0} %all-gather.2), "
           "kind=kLoop"),
    ]
    red = reduced(ops, n_devices=2)
    # chip 0: 0.10 + 0.10 s bare; chip 1: 0.05 s; over 2 chips, 3 steps
    want = 1e3 * (0.10 + 0.10 + 0.05) / 2 / 3
    assert read(train_inputs(red)) == pytest.approx(want)
    # a window that cuts the bare all-gather in half
    red = reduced(ops, window=(0.0, 0.55), n_devices=2)
    assert read(train_inputs(red)) == pytest.approx(
        1e3 * (0.10 + 0.05) / 2 / 3)


def test_exposed_collective_absent_without_collectives():
    read = reader("exposed_collective_ms.train").read
    red = reduced([op("fusion.1", "/device:TPU:0", 0.0, 0.5)])
    assert read(train_inputs(red)) is None
    assert read(train_inputs(reduced([]), job={"driver": "serve"})) is None
