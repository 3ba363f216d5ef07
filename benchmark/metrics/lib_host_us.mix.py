"""Median host time of a mix call in the library's own Python: the
``comm.<coll>`` span (api -> communicator -> coll/tuned -> coll/xla
memo) less the coll/xla launch inside it, over the traced calls of all
four collectives."""
from benchmark import phasespans


def read(ctx):
    return phasespans.median_us(ctx, "coll_mix", "lib")
