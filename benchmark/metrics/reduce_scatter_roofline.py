"""Least ICI time of the mix's 256 MiB reduce_scatter_blocks, (n-1)/n
of the send buffer over the chip's ICI peak, in the device time of the
reduce_scatter_block phase's blocks."""
from benchmark import phasespans


def read(ctx):
    return phasespans.roofline(ctx, "coll_mix", "reduce_scatter_block")
