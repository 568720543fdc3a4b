"""Device time per step of the bucketed sync and AdamW update programs
(runtime/sync_exec.py: pack, scale, add, sumsq, update), from the trace."""
from bench.metrics._common import SYNC_KINDS, per_step_ms


def read(ctx):
    return per_step_ms(ctx, SYNC_KINDS)
