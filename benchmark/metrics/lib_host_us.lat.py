"""Median host time of an 8 B call in the library's own Python: the
``comm.allreduce`` span (api -> communicator -> coll/tuned -> coll/xla
memo) less the coll/xla launch inside it."""
from benchmark import libspans


def read(ctx):
    return libspans.median_us(ctx, "allreduce", "lat", "lib")
