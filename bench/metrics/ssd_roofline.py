"""Share of its roofline that the SSD kernels reach, in %: the least
time of the chunked scan fwd + bwd at the configuration's chunk size
(bench/work/ssd.py) over the summed device time of the custom calls
written in kernels/ssd.py."""
from bench.metrics._common import roofline


def read(ctx):
    return roofline(ctx, "ssd")
