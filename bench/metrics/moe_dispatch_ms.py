"""Device self time under the ``moe_dispatch`` (sorting the routed (token,
expert) pairs by expert and gathering their rows) and ``moe_combine``
(weighting the expert outputs by their gates back into token order) scopes,
forward and backward, per computed local step, summed over the MoE layers,
in ms, from a traced window (``bench/scopes.py``); nothing where the program
names no such scope."""


def read(run):
    if run.scopes is None:
        return None
    sc = run.scopes["scopes"]
    if "moe_dispatch" not in sc:
        return None
    return 1e3 * (sc["moe_dispatch"] + sc.get("moe_combine", 0.0)) / run.computed_steps
