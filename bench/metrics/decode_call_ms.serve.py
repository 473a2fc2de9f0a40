"""Device time of one batched decode call of the serving engine, in ms:
device-busy time inside the traced executions of its ``engine_decode``
program (module ``jit_engine_decode``), over their number."""
from bench import scopes
from bench.metrics import _common

MODULE = "jit_engine_decode"


def read(inputs):
    sc = scopes.of(inputs) if _common.is_serve(inputs) else None
    if sc is None:
        return None
    busy, n = sc.module_busy(MODULE)
    return 1e3 * busy / sc.n_devices / n if n else None
