"""Median host time of a reduce_local call in the device combiner's
launch: the eager dispatch of ``op.fn`` on the device operands, the
``op.launch:<op>`` span."""
from benchmark import libspans


def read(ctx):
    return libspans.median_us(ctx, "reduce_local", "bw", "launch")
