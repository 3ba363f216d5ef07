"""OSU average latency of the cell's latency phase (host clock)."""
from benchmark import measures


def read(ctx):
    return measures.avg_latency_us(ctx, "lat")
