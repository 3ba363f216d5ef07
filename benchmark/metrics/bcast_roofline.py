"""Least ICI time of the mix's 256 MiB bcasts (``calls/coll_mix.py``
``roofline_bytes`` over the chip's ICI peak) in the device time of the
bcast phase's blocks."""
from benchmark import phasespans


def read(ctx):
    return phasespans.roofline(ctx, "coll_mix", "bcast")
