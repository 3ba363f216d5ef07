"""Device idle percent over the bandwidth phase of a local-op cell."""
from benchmark import measures


def read(ctx):
    return measures.idle_share(ctx, "bw")
