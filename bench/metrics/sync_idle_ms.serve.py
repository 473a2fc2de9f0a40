"""Device-idle time while the serving engine's host waits on the device,
in ms per traced engine step: idle intervals of the traced window whose
midpoint lies in ``engine.decode.wait`` or ``engine.prefill.wait``
(``serving/engine.py``), over the number of ``engine.step`` spans
traced."""
from bench import scopes
from bench.metrics import _common


def read(inputs):
    sc = scopes.of(inputs) if _common.is_serve(inputs) else None
    if sc is None or not sc.count("engine.step"):
        return None
    idle = sc.idle_s(lambda n: n.startswith(scopes.SPAN_PREFIX)
                     and n.endswith(scopes.WAIT_SUFFIX))
    return 1e3 * idle / sc.count("engine.step")
