"""Device idle percent over the latency phase's blocks."""
from benchmark import measures


def read(ctx):
    return measures.idle_share(ctx, "lat")
