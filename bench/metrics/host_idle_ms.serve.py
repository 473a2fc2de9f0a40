"""Device-idle time that the serving engine's host work leaves, in ms per
traced engine step: idle intervals of the traced window whose midpoint
lies in an ``engine.*`` span (``serving/engine.py``) other than a
``.wait`` span, over the number of ``engine.step`` spans traced."""
from bench import scopes
from bench.metrics import _common


def read(inputs):
    sc = scopes.of(inputs) if _common.is_serve(inputs) else None
    if sc is None or not sc.count("engine.step"):
        return None
    idle = sc.idle_s(lambda n: n.startswith(scopes.SPAN_PREFIX)
                     and not n.endswith(scopes.WAIT_SUFFIX))
    return 1e3 * idle / sc.count("engine.step")
