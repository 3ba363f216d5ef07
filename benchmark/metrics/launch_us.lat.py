"""Median host time of an 8 B call in coll/xla's launch: the compiled
executable's call (PjRt execute on every rank's device), the
``coll.xla.launch:allreduce/<algorithm>`` span."""
from benchmark import libspans


def read(ctx):
    return libspans.median_us(ctx, "allreduce", "lat", "launch")
