"""Compiled-program executions per step: the executions, on the first
device inside the window (its ``XLA Modules`` events), of the trainer's
ProgramCache programs of every kind (grads, bucket pack, scale, add,
sumsq, update ...), over the window's steps.  The bucket plan fixes it:
grads x R + (pack + scale) x R x B + add x (R - 1) x B + sumsq x B +
update x R x B for R replicas and B buckets."""
from bench.trace import module_name


def read(ctx):
    red, steps = ctx["trace"], ctx["window"]["steps"]
    if not steps or not red.devices:
        return None
    names = red.modules_of(red.programs)
    runs = sum(1 for e in red.modules
               if e.plane == red.devices[0] and module_name(e) in names)
    return runs / steps if runs else None
