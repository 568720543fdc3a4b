"""Device time per step of the per-template grads programs
(runtime/pipeline.py make_grads_fn), from the trace."""
from bench.metrics._common import per_step_ms


def read(ctx):
    return per_step_ms(ctx, ("grads",))
