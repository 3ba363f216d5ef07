"""Median completion wait of a reduce_local call: from the return of
``op.reduce_local`` to the end of the call's ``block_until_ready``."""
from benchmark import libspans


def read(ctx):
    return libspans.median_us(ctx, "reduce_local", "bw", "wait")
