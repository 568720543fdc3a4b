"""Model FLOPs utilisation of the steady window, in % of the chips'
bf16 peak: model FLOPs per token x tokens/s / (chips x peak)."""
from bench.metrics._common import mfu


def read(ctx):
    return mfu(ctx)
