"""Device self time under the ``mla`` scope (multi-head latent attention of
every layer, its projections and its backward) per computed local step,
summed over the layers, in ms, from a traced window (``bench/scopes.py``);
nothing where the program names no such scope."""


def read(run):
    if run.scopes is None or "mla" not in run.scopes["scopes"]:
        return None
    return 1e3 * run.scopes["scopes"]["mla"] / run.computed_steps
