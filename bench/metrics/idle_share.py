"""Share of the traced window in which no operation ran on the device,
in %: 1 - union of device op intervals / window."""


def read(ctx):
    share = ctx["trace"].idle_share()
    return None if share is None else 100.0 * share
