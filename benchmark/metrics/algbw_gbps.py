"""Payload bytes per rank per second over the bandwidth phase (host
clock)."""
from benchmark import measures


def read(ctx):
    return measures.algbw_gbps(ctx, "bw")
