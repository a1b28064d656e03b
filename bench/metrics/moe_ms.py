"""Device self time under the ``moe`` scope (each MoE layer's whole expert
FFN: router, dispatch, routed and shared experts, combine, and their
backward) per computed local step, summed over the layers, in ms, from a
traced window (``bench/scopes.py``); nothing where the program names no
such scope."""


def read(run):
    if run.scopes is None or "moe" not in run.scopes["scopes"]:
        return None
    return 1e3 * run.scopes["scopes"]["moe"] / run.computed_steps
