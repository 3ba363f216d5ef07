"""Device idle percent over the bandwidth phase's blocks."""
from benchmark import measures


def read(ctx):
    return measures.idle_share(ctx, "bw")
