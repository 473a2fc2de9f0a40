"""Records ``cpu_window.xplane.pb``: two jitted steps on the CPU inside the
harness's spans (``bench.window``, ``bench.data``, ``bench.step``).

    JAX_PLATFORMS=cpu python3 bench/tests/data/record_cpu_trace.py
"""
import glob
import shutil
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp

HERE = Path(__file__).resolve().parent


def main():
    # no source locations in the compiled program's metadata either
    jax.config.update("jax_traceback_in_locations_limit", 0)
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    # host spans only: no Python call stacks (they would carry the paths
    # of the machine that recorded the file)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        with jax.profiler.trace(tmp, profiler_options=opts):
            with jax.profiler.TraceAnnotation("bench.window"):
                for _ in range(2):
                    with jax.profiler.TraceAnnotation("bench.data"):
                        y = jnp.asarray(x) + 0.0
                    with jax.profiler.TraceAnnotation("bench.step"):
                        f(y).block_until_ready()
        src = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
        shutil.copy(src, HERE / "cpu_window.xplane.pb")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
