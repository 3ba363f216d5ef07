"""Median host time of a mix call in coll/xla's launch: the compiled
executable's call, the ``coll.xla.launch:<coll>/<algorithm>`` span,
over the traced calls of all four collectives."""
from benchmark import phasespans


def read(ctx):
    return phasespans.median_us(ctx, "coll_mix", "launch")
