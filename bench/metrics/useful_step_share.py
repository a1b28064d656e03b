"""Unmasked local steps over the C * K_max steps the padded cohort scan
computes, summed over the window's rounds, from the batches' step masks."""


def read(run):
    return sum(r.useful_steps for r in run.rounds) / sum(r.padded_steps for r in run.rounds)
