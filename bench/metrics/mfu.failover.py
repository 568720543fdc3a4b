"""Model FLOPs utilisation over a window that includes every failure,
recovery and join, in % of the chips' bf16 peak."""
from bench.metrics._common import mfu


def read(ctx):
    return mfu(ctx)
