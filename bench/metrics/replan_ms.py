"""Mean over the window's failures of the engine's replan time
(trainer.recover's info["breakdown"]["replan"], a host clock around
host-only planning), in ms."""
from bench.metrics._common import failures


def read(ctx):
    fails = failures(ctx)
    if not fails:
        return None
    return 1e3 * sum(e["replan_s"] for e in fails) / len(fails)
