"""Least ICI time of a 256 MiB allreduce, 2(n-1)/n of the buffer over
the chip's ICI peak, in its device time."""
from benchmark import measures


def read(ctx):
    return measures.roofline(ctx, "allreduce", "bw")
