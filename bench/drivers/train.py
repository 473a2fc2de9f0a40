"""Training driver: one training job on the program's jitted train step.

Set-up builds ONE compiled step with its state, as ``launch/train.run``
builds it (model from the registry with the cell's sizes, the paper's
rounded SGD with momentum, ``make_train_step`` with the dynamic loss
scale, jitted on the one-device mesh under the program's mesh axes), with
the state donated.  It drives that object through the job's first three
steps, on the same feed the window uses, and records what the check
compares: each step's loss, the per-leaf norms of the first gradient as
the optimizer received it (its momentum after one step, which starts at
zero and is kept in float32) and the per-leaf norms of the parameters'
change after the three.  The window then continues the same object for
``--seconds``.  Once the window has closed and the state is freed, the
plain reference follows the same three steps from the same weights.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import math
import shutil
import time

import numpy as np

from bench import harness
from bench.reference import dense_llama as ref

FIRST_STEPS = 3
# steps dispatched ahead of the one the host waits for: a host stall
# shorter than this many steps leaves the device busy
IN_FLIGHT = 3

# configuration keys -> the program's ModelConfig fields
_FIELDS = {"hidden_size": "d_model", "num_hidden_layers": "n_layers",
           "num_attention_heads": "n_heads",
           "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
           "intermediate_size": "d_ff", "vocab_size": "vocab_size",
           "tie_word_embeddings": "tie_embeddings",
           "rope_theta": "rope_theta", "sliding_window": "sliding_window"}


class TokenFeed:
    """Seeded synthetic token batches: a pure function of (seed, step).

    Zipf-like unigram tokens (the exponential transform of uniforms that
    ``data/synthetic.py`` uses), made on the host and placed on the
    device; every row of every step differs."""

    def __init__(self, vocab: int, batch: int, seq: int, seed: int):
        self.vocab, self.batch, self.seq, self.seed = vocab, batch, seq, seed

    def host_batch(self, step: int) -> dict:
        rng = np.random.default_rng([self.seed, step])
        u = rng.uniform(1e-6, 1.0, (self.batch, self.seq + 1))
        toks = np.floor(self.vocab ** (1.0 - u) - 1.0).astype(np.int32)
        toks = np.clip(toks, 0, self.vocab - 1)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    def device_batch(self, step: int) -> dict:
        import jax
        return jax.device_put(self.host_batch(step))


def model_config(cfg: dict, policy):
    """The program's ModelConfig for this configuration file."""
    from repro.configs import get_config
    base = get_config(cfg["registry"])
    changes = {f: cfg[k] for k, f in _FIELDS.items() if k in cfg}
    return dataclasses.replace(base, gemm_policy=policy, **changes)


@dataclasses.dataclass
class Job:
    batch: int
    seq: int
    gemm_policy: object
    lr: float
    momentum: float
    rounding: str
    fmt: str
    eps: float
    update_path: str
    loss_scale: float
    trace_steps: int
    limits: dict

    @classmethod
    def from_file(cls, d: dict) -> "Job":
        return cls(**{f.name: d[f.name] for f in dataclasses.fields(cls)})


def build(cfg: dict, job: Job):
    """(model, optimizer, jitted step, mesh, axes) as the trainer builds
    them, with the state donated."""
    import jax

    from repro.launch import steps as steps_lib, train as train_lib
    from repro.launch.mesh import make_local_mesh, mesh_axes_for
    from repro.models import build_model

    model = build_model(model_config(cfg, job.gemm_policy))
    opt = train_lib.build_optimizer(
        "sgd", lr=job.lr, momentum=job.momentum,
        cfg=train_lib.rounding_config(job.rounding, job.fmt, job.eps),
        update_path=job.update_path)
    step = steps_lib.make_train_step(model, opt, loss_scale=job.loss_scale)
    mesh = make_local_mesh()
    ax = mesh_axes_for(mesh, batch_size=job.batch)
    return model, opt, jax.jit(step, donate_argnums=(0, 1, 2)), mesh, ax


def _check_layout(model, params):
    """The weights the benchmark made fill the program's parameter tree."""
    import jax
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    want = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if got != want:
        raise ValueError(f"weight layout differs from the program's: "
                         f"{got} vs {want}")


def gap_numbers(prog: dict, refr: dict) -> dict:
    """The compared numbers: the worst step's relative loss gap, and the
    worst leaf's gap of norms (first gradient, parameters' change), each
    over the larger of that leaf's reference norm and the median leaf's.
    Leaves whose reference gradient is under a thousandth of the median
    leaf's move by round-off alone and are left out of the change."""
    lp, lr_ = np.asarray(prog["losses"]), np.asarray(refr["losses"])
    loss = float(np.max(np.abs(lp - lr_) / np.abs(lr_)))
    gr = np.asarray(refr["grad_norms"], np.float64)
    gp = np.asarray(prog["grad_norms"], np.float64)
    grad = float(np.max(np.abs(gp - gr) / np.maximum(gr, np.median(gr))))
    keep = gr >= 1e-3 * np.median(gr)
    cr = np.asarray(refr["change_norms"], np.float64)[keep]
    cp = np.asarray(prog["change_norms"], np.float64)[keep]
    change = float(np.max(np.abs(cp - cr) / np.maximum(cr, np.median(cr))))
    return {"loss_gap": loss, "grad_norm_gap": grad,
            "change_norm_gap": change}


def first_steps(compiled, state, feed, params_again, norms):
    """Drive the compiled step through the first steps; returns the state
    and the program's side of the comparison."""
    import jax
    losses, grad_norms = [], None
    for i in range(FIRST_STEPS):
        *state, m = compiled(*state, feed.device_batch(i))
        losses.append(float(m["loss"]))
        if i == 0:
            grad_norms = np.asarray(norms(state[1].momentum))
    p0 = params_again()
    change = np.asarray(norms(jax.tree.map(lambda a, b: a - b,
                                           state[0], p0)))
    del p0
    return tuple(state), {"losses": losses, "grad_norms": grad_norms,
                          "change_norms": change}


def run(cell: dict, cfg: dict, traffic: dict, seed: int, seconds: float,
        trace: bool, devs, t_start: float, out_dir) -> harness.Outcome:
    import jax

    from repro.dist.sharding import set_mesh_axes
    from repro.launch import steps as steps_lib

    job = Job.from_file(traffic)
    phases = harness.Phases(t_start)
    phases.mark("start and imports")
    counter = harness.CompileCounter()
    span = jax.profiler.TraceAnnotation
    model, opt, jitted, mesh, ax = build(cfg, job)
    feed = TokenFeed(cfg["vocab_size"], job.batch, job.seq, seed)
    norms = jax.jit(ref.leaf_norms)

    params = ref.init_params(cfg, seed)
    _check_layout(model, params)
    opt_state = jax.jit(opt.init)(
        params, jax.random.fold_in(ref.seed_key(seed), 1))
    carry = steps_lib.init_step_carry(loss_scale=job.loss_scale)
    jax.block_until_ready((params, opt_state))
    phases.mark("weights and state")
    with set_mesh_axes(ax), mesh:
        lowered = jitted.lower(params, opt_state, carry,
                               feed.device_batch(0))
        phases.mark("trace and lower")
        compiled = lowered.compile()
        phases.mark("compile")
        state, prog = first_steps(compiled, (params, opt_state, carry), feed,
                                  lambda: ref.init_params(cfg, seed), norms)
        del params, opt_state, carry
        phases.mark("first steps")
        tokens_per_step = job.batch * job.seq
        setup_s = time.perf_counter() - t_start

        steps = failed = 0
        trace_dir = out_dir / "trace" if trace else None
        n_window = job.trace_steps if trace else None
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir))
        with counter.window():
            t0 = time.perf_counter()
            pending = collections.deque()
            with span("bench.window"):
                while True:
                    with span("bench.data"):
                        batch = feed.device_batch(FIRST_STEPS + steps)
                    with span("bench.step"):
                        *state, m = compiled(*state, batch)
                        pending.append(m["loss"])
                        if len(pending) > IN_FLIGHT:
                            failed += not math.isfinite(
                                float(pending.popleft()))
                    steps += 1
                    if n_window is not None:
                        if steps >= n_window:
                            break
                    elif time.perf_counter() - t0 >= seconds:
                        break
                while pending:
                    failed += not math.isfinite(float(pending.popleft()))
            window_s = time.perf_counter() - t0
        if trace:
            jax.profiler.stop_trace()
    device = harness.device_record(devs)
    del state, compiled, m, pending
    gc.collect()

    batches = [feed.device_batch(i) for i in range(FIRST_STEPS)]
    refr = ref.train_steps(cfg, traffic, ref.init_params(cfg, seed),
                           batches, seed)
    gaps = gap_numbers(prog, refr)
    checks = [harness.Check(k, gaps[k], job.limits[k]) for k in
              ("loss_gap", "grad_norm_gap", "change_norm_gap")]
    checks.append(harness.Check("compiles_in_window", counter.count, 0))
    notes = [phases.line(),
             f"program losses {prog['losses']} reference {refr['losses']}"]
    e2e = {"train_tokens_per_s": steps * tokens_per_step / window_s,
           "setup_s": setup_s}
    layer = {"trace_dir": trace_dir, "steps": steps,
             "tokens_per_step": tokens_per_step, "cfg": cfg, "job": traffic,
             "peaks": harness.load_peaks(devs[0].device_kind)}
    return harness.Outcome(attempted=steps, failed=failed, checks=checks,
                           end_to_end=e2e, device=device, layer_inputs=layer,
                           notes=notes)
