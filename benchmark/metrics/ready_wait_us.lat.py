"""Median completion wait of an 8 B call: from the return of
``comm.allreduce`` to the end of the call's ``block_until_ready``."""
from benchmark import libspans


def read(ctx):
    return libspans.median_us(ctx, "allreduce", "lat", "wait")
