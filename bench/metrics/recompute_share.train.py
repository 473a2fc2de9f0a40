"""Share of the traced train steps' device time spent recomputing the
forward pass under ``jax.checkpoint``, in percent: device time of the
operations whose ``op_name`` holds ``/rematted_computation/`` (and not
``step.update``) over that of all operations (``scopes.phase``).  On a
device whose operations never overlap, as on a TPU, that is the share of
busy time.  None where the step carries no ``step.forward`` scope."""
from bench import scopes
from bench.metrics import _common


def read(inputs):
    sc = scopes.of(inputs) if _common.is_train(inputs) else None
    return scopes.phase_share(sc, "recompute") if sc is not None else None
