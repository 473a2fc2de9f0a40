"""Device time of the serving engine's prefill, in ms per thousand prompt
tokens: device-busy time inside the traced executions of its
``engine_prefill`` program (module ``jit_engine_prefill``), over the
prompt tokens of the traced ``engine.prefill`` spans (their ``tokens``
argument)."""
from bench import scopes
from bench.metrics import _common

MODULE = "jit_engine_prefill"


def read(inputs):
    sc = scopes.of(inputs) if _common.is_serve(inputs) else None
    if sc is None:
        return None
    busy, n = sc.module_busy(MODULE)
    tokens = sum(int(s.args.get("tokens", 0)) for s in sc.spans
                 if s.name == "engine.prefill")
    if not n or not tokens:
        return None
    return 1e3 * busy / sc.n_devices / tokens * 1000
