"""Unmasked local steps over the C * K_max steps the cohort layout holds,
summed over the window's rounds, from the batches' step masks: a share of the
layout, not of the compute (a sequential cohort computes each client only to
its last unmasked step)."""


def read(run):
    return sum(r.useful_steps for r in run.rounds) / sum(r.padded_steps for r in run.rounds)
