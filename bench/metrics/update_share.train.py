"""Share of the traced train steps' device time spent in the update, in
percent: device time of the operations under the ``step.update`` scope
(``launch/steps.make_train_step``: the optimizer update with its unscale,
finiteness check and skip-step select) over that of all operations
(``scopes.phase``).  On a device whose operations never overlap, as on a
TPU, that is the share of busy time.  None where the step carries no
``step.forward`` scope."""
from bench import scopes
from bench.metrics import _common


def read(inputs):
    sc = scopes.of(inputs) if _common.is_train(inputs) else None
    return scopes.phase_share(sc, "update") if sc is not None else None
