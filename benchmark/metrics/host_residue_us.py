"""Median host time of an 8 B call that the device does not cover:
api -> communicator -> coll/tuned -> coll/xla dispatch."""
from benchmark import measures


def read(ctx):
    return measures.host_residue_us(ctx, "lat")
