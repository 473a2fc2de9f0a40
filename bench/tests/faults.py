"""Faults planted under a training cell's timed path: each wraps the
program's extended train step ``(params, opt_state, carry, batch)``."""
import jax


def wrap(make_train_step, fault):
    """``make_train_step`` whose steps run through ``fault``."""
    def make(*a, **kw):
        step = make_train_step(*a, **kw)

        def faulty(params, opt_state, carry, batch):
            return fault(step, params, opt_state, carry, batch)
        return faulty
    return make


def state_unchanged(step, params, opt_state, carry, batch):
    _, _, carry2, m = step(params, opt_state, carry, batch)
    return params, opt_state, carry2, m


def half_batch(step, params, opt_state, carry, batch):
    half = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
    return step(params, opt_state, carry, half)
