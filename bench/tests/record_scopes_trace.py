"""Record the chip trace that ``test_scopes.py`` reads.

    python3 bench/tests/record_scopes_trace.py <out.xplane.pb> <out.hlo.txt>

Runs three rounds of a small jitted round step under ``jax.profiler.trace``
inside the harness's window annotation.  The step carries the program's
named scopes (a K-step ``lax.scan`` under ``local_step`` holding ``lm_head``
and ``local_apply``, then ``client_delta``, ``accumulate``,
``server_update``, and one op under none); each round's host side runs the
program's ``data/*`` spans (``repro.obs.trace.span``) around host pauses and
the batch's transfer, then a second jitted module whose instruction names
repeat the step's.  Writes the ``.xplane.pb`` and the step's compiled HLO
text, less the stack-frame tables that name the recording machine's files.
It was run once on a TPU v5e.
"""
import os
import re
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
from bench import trace_reduce  # noqa: E402  (puts src/ on the path)
from repro.obs import trace  # noqa: E402

K, B, D = 4, 256, 1024
ROUNDS = 3
PAUSE_S = 0.01


def round_step(w, xs):
    def body(y, x):
        with jax.named_scope("lm_head"):
            loss, g = jax.value_and_grad(lambda v: jnp.mean(jnp.tanh(x @ v) ** 2))(y)
        with jax.named_scope("local_apply"):
            y = (y.astype(jnp.float32) - 0.01 * g.astype(jnp.float32)).astype(y.dtype)
        return y, loss

    with jax.named_scope("local_step"):
        y, losses = jax.lax.scan(body, w, xs)
    with jax.named_scope("client_delta"):
        delta = y - w
    with jax.named_scope("accumulate"):
        acc = 0.5 * delta.astype(jnp.float32)
    with jax.named_scope("server_update"):
        w = (w.astype(jnp.float32) + acc).astype(w.dtype)
    return w, losses.mean() * 2.0


def hlo_text(compiled) -> str:
    text = re.sub(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n(.+\n)*\n", "",
                  compiled.as_text(), flags=re.M)
    return re.sub(r" stack_frame_id=\d+", "", text)


def main(out: str, out_hlo: str) -> None:
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_scopes_trace: needs a TPU")
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(0, 0.02, (D, D)), jnp.bfloat16)
    host = [rng.normal(0, 1, (K, B, D)).astype(np.float32) for _ in range(ROUNDS)]
    step = jax.jit(round_step)
    other = jax.jit(lambda v: jnp.sum(jnp.tanh(v) ** 2))
    xs = jnp.asarray(host[0])
    compiled = step.lower(w, xs).compile()
    w2, loss = step(w, xs)
    float(loss), float(other(w2))
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    ann = jax.profiler.TraceAnnotation
    with jax.profiler.trace(tmp, profiler_options=opts):
        with ann(trace_reduce.WINDOW):
            for r in range(ROUNDS):
                with trace.span("data/index_plan", round=r):
                    time.sleep(PAUSE_S / 2)
                with trace.span("data/materialize", round=r):
                    time.sleep(PAUSE_S)
                with trace.span("data/to_device", bytes=host[r].nbytes):
                    xs = jnp.asarray(host[r])
                with ann("bench/dispatch"):
                    w, loss = step(w, xs)
                with ann("bench/metrics_fetch"):
                    float(loss)
                float(other(w))
    shutil.copy(trace_reduce.find_xplane(tmp), out)
    shutil.rmtree(tmp)
    with open(out_hlo, "w") as f:
        f.write(hlo_text(compiled))
    print(out, os.path.getsize(out), out_hlo, os.path.getsize(out_hlo))


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
