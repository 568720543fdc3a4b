"""Mean over the window's failures of the benchmark's span around
trainer.recover, up to block_until_ready of every replica's state, less
the replan time: the layer copies and the rebinding, in ms."""
from bench.metrics._common import failures


def read(ctx):
    fails = failures(ctx)
    if not fails:
        return None
    return 1e3 * sum(e["t_bound"] - e["t_call"] - e["replan_s"]
                     for e in fails) / len(fails)
