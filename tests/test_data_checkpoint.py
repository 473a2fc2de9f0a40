"""Data-pipeline determinism/skip-ahead + checkpoint atomicity/elasticity +
train-loop fault injection."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.data import (ShardedPipeline, make_token_pipeline,
                        synthetic_binary_mnist, synthetic_mnist)
from repro.launch.mesh import make_mesh
from repro.train import TrainLoop, TrainLoopConfig


# ----------------------------------------------------------------- data ---
def test_token_pipeline_deterministic_and_skippable():
    src = make_token_pipeline(vocab_size=1000, seq_len=16, global_batch=4,
                              seed=7)
    b1 = src.batch_at(10)
    b2 = src.batch_at(10)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    b3 = src.batch_at(11)
    assert not np.array_equal(np.asarray(b1["tokens"]), np.asarray(b3["tokens"]))
    # labels are next-token shifted
    assert b1["tokens"].shape == (4, 16) and b1["labels"].shape == (4, 16)
    assert int(b1["tokens"].max()) < 1000 and int(b1["tokens"].min()) >= 0


def test_token_pipeline_zipf_skew():
    src = make_token_pipeline(vocab_size=5000, seq_len=256, global_batch=16)
    toks = np.asarray(src.batch_at(0)["tokens"]).ravel()
    # low ids should be much more frequent than high ids
    assert (toks < 50).mean() > 5 * (toks > 2500).mean()


def test_pipeline_state_roundtrip():
    src = make_token_pipeline(vocab_size=100, seq_len=8, global_batch=2)
    p = ShardedPipeline(src)
    a = p.next(); b = p.next()
    state = p.state_dict()
    c = p.next()
    p2 = ShardedPipeline(src)
    p2.load_state_dict(state)
    c2 = p2.next()
    np.testing.assert_array_equal(np.asarray(c["tokens"]),
                                  np.asarray(c2["tokens"]))


def test_pipeline_prefetch():
    src = make_token_pipeline(vocab_size=100, seq_len=8, global_batch=2)
    p = ShardedPipeline(src)
    ref = [p.peek(i)["tokens"] for i in range(4)]
    p.start_prefetch()
    got = [p.next_prefetched()["tokens"] for _ in range(4)]
    p.stop()
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g))


def test_synthetic_mnist_shapes_and_separability():
    xtr, ytr, xte, yte = synthetic_mnist(n_train=2000, n_test=400, seed=0)
    assert xtr.shape == (2000, 784) and xte.shape == (400, 784)
    assert xtr.min() >= 0 and xtr.max() <= 1
    assert set(np.unique(ytr)) <= set(range(10))
    # a nearest-class-mean classifier must beat chance by a wide margin
    means = np.stack([xtr[ytr == c].mean(0) for c in range(10)])
    pred = np.argmin(((xte[:, None] - means[None]) ** 2).sum(-1), axis=1)
    assert (pred == yte).mean() > 0.6


def test_synthetic_binary_mnist():
    xtr, ytr, xte, yte = synthetic_binary_mnist(n_train=500, n_test=100)
    assert xtr.shape[0] == 500 and set(np.unique(ytr)) == {0.0, 1.0}


# ------------------------------------------------------------ checkpoint --
def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "nested": {"b": jnp.ones(4), "k": jax.random.PRNGKey(3)},
            "step": 17}
    mgr.save(100, tree, blocking=True, extra={"note": "hi"})
    step, got, extra = mgr.restore()
    assert step == 100 and extra["note"] == "hi"
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(tree["w"]))
    np.testing.assert_array_equal(np.asarray(got["nested"]["k"]),
                                  np.asarray(tree["nested"]["k"]))


def test_checkpoint_gc_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, {"x": jnp.ones(1) * s}, blocking=True)
    assert mgr.all_steps() == [3, 4]
    assert mgr.latest_step() == 4
    _, tree, _ = mgr.restore(3)
    assert float(tree["x"][0]) == 3.0


def test_checkpoint_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(5, {"x": jnp.zeros(1000)})
    mgr.wait()
    assert mgr.latest_step() == 5


def test_checkpoint_atomicity_no_partial_dirs(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"x": jnp.ones(8)}, blocking=True)
    names = os.listdir(tmp_path)
    assert "step_1" in names and not any(n.endswith(".tmp") for n in names)


def test_checkpoint_resharding_restore(tmp_path):
    """Elastic restore: re-place leaves under an explicit sharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_mesh((1,), ("data",))
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"x": jnp.arange(8.0)}, blocking=True)
    sh = {"x": NamedSharding(mesh, P("data"))}
    _, tree, _ = mgr.restore(1, shardings=sh)
    assert tree["x"].sharding == sh["x"]


# -------------------------------------------------------------- trainloop --
def _toy_setup(tmp_path, total=20, ckpt_every=5):
    src = make_token_pipeline(vocab_size=50, seq_len=4, global_batch=2)
    pipe = ShardedPipeline(src)
    w0 = jnp.ones((4,), jnp.float32)

    @jax.jit
    def step_fn(state, batch):
        w, n = state
        tgt = batch["tokens"][0, :4].astype(jnp.float32) / 50.0
        g = w - tgt
        w = w - 0.1 * g
        return (w, n + 1), {"loss": jnp.sum(g * g)}

    cfg = TrainLoopConfig(total_steps=total, checkpoint_every=ckpt_every,
                          checkpoint_dir=str(tmp_path / "ck"), log_every=5)
    return step_fn, pipe, (w0, jnp.zeros((), jnp.int32)), cfg


def test_trainloop_runs_and_checkpoints(tmp_path):
    step_fn, pipe, state, cfg = _toy_setup(tmp_path)
    loop = TrainLoop(step_fn, pipe, state, cfg)
    out = loop.run()
    assert out["final_step"] == 20 and out["restarts"] == 0
    assert CheckpointManager(cfg.checkpoint_dir).latest_step() == 20


def test_trainloop_survives_injected_fault(tmp_path):
    step_fn, pipe, state, cfg = _toy_setup(tmp_path)
    fired = {"done": False}

    def fault(step):
        if step == 12 and not fired["done"]:
            fired["done"] = True
            raise RuntimeError("simulated preemption")

    loop = TrainLoop(step_fn, pipe, state, cfg, fault_hook=fault)
    out = loop.run()
    assert out["final_step"] == 20 and out["restarts"] == 1

    # the resumed run must match an uninterrupted one bit-for-bit
    step_fn2, pipe2, state2, cfg2 = _toy_setup(tmp_path)
    cfg2.checkpoint_dir = str(tmp_path / "ck2")
    clean = TrainLoop(step_fn2, pipe2, state2, cfg2).run()
    assert clean["history"][-1]["loss"] == out["history"][-1]["loss"]


def test_trainloop_checkpointless_restart_restores_init_state(tmp_path):
    """A failure before the first checkpoint must roll back to the pristine
    initial state (the in-flight state is a corrupted half-step), not keep
    training from the corrupted tree."""
    step_fn, pipe, state, cfg = _toy_setup(tmp_path, total=4, ckpt_every=100)
    calls = {"n": 0}

    def poisoned_step(s, batch):
        calls["n"] += 1
        if calls["n"] == 1:
            # corrupt the in-flight state AND fail the step
            return (s[0] + 1e6, s[1] + 100), {"loss": float("nan")}
        return step_fn(s, batch)

    loop = TrainLoop(poisoned_step, pipe, state, cfg)
    out = loop.run()
    assert out["restarts"] == 1 and out["final_step"] == 4
    # the corrupted +1e6 weights must NOT survive the restart: final
    # weights match a clean run from the same initial state bit-for-bit
    step_fn2, pipe2, state2, cfg2 = _toy_setup(tmp_path, total=4,
                                               ckpt_every=100)
    cfg2.checkpoint_dir = str(tmp_path / "ck_clean")
    clean_loop = TrainLoop(step_fn2, pipe2, state2, cfg2)
    clean_loop.run()
    np.testing.assert_array_equal(np.asarray(loop.state[0]),
                                  np.asarray(clean_loop.state[0]))
    assert int(loop.state[1]) == int(clean_loop.state[1]) == 4


def test_trainloop_gives_up_after_max_restarts(tmp_path):
    step_fn, pipe, state, cfg = _toy_setup(tmp_path)
    cfg.max_restarts = 2

    def always_fail(step):
        raise RuntimeError("permafail")

    loop = TrainLoop(step_fn, pipe, state, cfg, fault_hook=always_fail)
    with pytest.raises(RuntimeError):
        loop.run()
