"""Peak device memory in use, in GB: memory_stats()["peak_bytes_in_use"]
after the window."""


def read(ctx):
    peak = ctx["memory_peak_bytes"]
    return peak / 1e9 if peak else None
