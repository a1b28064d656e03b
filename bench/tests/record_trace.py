"""Record the small chip trace that ``test_trace_reduce.py`` reads.

    python3 bench/tests/record_trace.py <out.xplane.pb>

Runs a few tiny jitted products under ``jax.profiler.trace`` with the
harness's host annotations (a window holding rounds of round_batch,
dispatch and metrics_fetch, with a host-only pause in round_batch), and
copies the ``.xplane.pb`` to the path given.  It was run once on a TPU v5e.
"""
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
from bench import trace_reduce  # noqa: E402


def main(out: str) -> None:
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: needs a TPU")
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    ann = jax.profiler.TraceAnnotation
    with jax.profiler.trace(tmp, profiler_options=opts):
        with ann(trace_reduce.WINDOW):
            for _ in range(3):
                with ann("bench/round_batch"):
                    time.sleep(0.02)
                with ann("bench/dispatch"):
                    y = f(x)
                with ann("bench/metrics_fetch"):
                    float(y[0, 0])
    shutil.copy(trace_reduce.find_xplane(tmp), out)
    shutil.rmtree(tmp)
    print(out, os.path.getsize(out))


if __name__ == "__main__":
    main(sys.argv[1])
