"""The routed experts' grouped matmuls against their roofline, in %: the
least time the chip could take for them in one computed local step over
``experts_ms`` (``bench/metrics/experts_ms.py``).

Per MoE layer and step, with D the model width, F the expert width, ``held``
the experts on this chip and R = B T k held / E the (token, expert) rows
they compute, in expectation under uniform routing (B the local batch, T
the sequence length, k experts per token of E):

* operations 6 R 3 D F (gate, up and down projections, forward and the two
  products of the backward);
* bytes 3 held 3 D F 2 (the bf16 weights read forward, read again backward,
  and their gradients written) + R (2 D + 2 F) 2 3 (each row's input and
  hidden activations in and out, forward and backward);

roofline time = max(operations / bf16 peak, bytes / HBM bandwidth), both of
``bench/peaks.json`` for the device's kind.  Nothing where the program names
no ``experts`` scope or the configuration has no routed experts."""
import jax

from bench import spec


def read(run):
    if run.scopes is None or "experts" not in run.scopes["scopes"]:
        return None
    s = run.cell.shape
    if not hasattr(s, "held"):
        return None
    D, F = s.d_model, s.expert_ff
    R = run.tokens_per_step * s.top_k * s.held / s.experts
    layers = s.layers - s.first_dense
    flops = layers * 6 * R * 3 * D * F
    nbytes = layers * (3 * s.held * 3 * D * F * 2 + R * (2 * D + 2 * F) * 2 * 3)
    peak = spec.peaks(jax.devices()[0].device_kind)
    t_roof = max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    t = run.scopes["scopes"]["experts"] / run.computed_steps
    return 100.0 * t_roof / t
