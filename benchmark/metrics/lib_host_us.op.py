"""Median host time of a reduce_local call in the op framework's own
Python: the ``op.reduce_local`` span less the combiner's launch inside
it."""
from benchmark import libspans


def read(ctx):
    return libspans.median_us(ctx, "reduce_local", "bw", "lib")
