"""Device self time under the ``experts`` scope (the grouped matmuls of the
routed experts this chip holds, forward and backward) per computed local
step, summed over the MoE layers, in ms, from a traced window
(``bench/scopes.py``); nothing where the program names no such scope."""


def read(run):
    if run.scopes is None or "experts" not in run.scopes["scopes"]:
        return None
    return 1e3 * run.scopes["scopes"]["experts"] / run.computed_steps
