"""Operand bytes per second through a local reduction op over the
bandwidth phase (host clock). Kept apart from ``algbw_gbps``: a
dispatch-bound local op spreads far wider from run to run than a
collective, and each needs its own bound."""
from benchmark import measures


def read(ctx):
    return measures.algbw_gbps(ctx, "bw")
