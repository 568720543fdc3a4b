"""Share of the time inside the bench.recover spans in which no
operation ran on the device, in %: whether recovery is host-bound."""


def read(ctx):
    red = ctx["trace"]
    spans = red.span_intervals("bench.recover")
    total = sum(b - a for a, b in spans)
    if not red.devices or not spans or not total:
        return None
    busy = sum(red.busy_ns(a, b) for a, b in spans)
    return 100.0 * (1.0 - busy / total)
