"""Model FLOP/s utilisation of the whole round, in %: useful tokens per
second times training FLOPs per token (PaLM's count, ``bench/flops.py``)
over the chips' bf16 peak (``bench/peaks.json``).  Padded steps count as
nothing."""


def read(run):
    return 100.0 * run.useful_tokens / run.window_s * run.flops_per_token / (
        run.chips * run.peak_flops)
