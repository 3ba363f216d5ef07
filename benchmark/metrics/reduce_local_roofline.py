"""Least HBM time of reduce_local, two operand reads and one result
write over the chip's HBM peak, in its device time."""
from benchmark import measures


def read(ctx):
    return measures.roofline(ctx, "reduce_local", "bw")
