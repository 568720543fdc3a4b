"""Share of its roofline that the flash attention kernels reach, in %:
the least time of causal attention fwd + bwd at the cell's shapes
(bench/work/flash_attention.py) over the summed device time of the
custom calls written in kernels/flash_attention.py."""
from bench.metrics._common import roofline


def read(ctx):
    return roofline(ctx, "flash_attention")
