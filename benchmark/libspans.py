"""The library's own spans in a traced window: each call split, on the
trace's host clock, at the library's layer boundaries.

While a profiler session records, ompi_tpu writes a span for the layer
a call enters (``comm.allreduce``, ``op.reduce_local``) and, nested in
it, one for the launch of the device work
(``coll.xla.launch:allreduce/<algorithm>``, ``op.launch:<op>``). Inside
each traced ``bench.call:<phase>`` span that holds exactly one outer
span, the call's time is

    before  bench.call start to the outer span's start (the window's
            own loop)
    lib     the outer span less the launch spans inside it
    launch  the launch spans inside the outer span
    wait    the outer span's end to bench.call's end: the completion
            wait (``block_until_ready``)

and the four add up to the call. A trace without the spans, as of a
program that writes none, gives None."""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

# the call a cell drives -> (its outer span, its launch spans' prefix)
SPANS = {"allreduce": ("comm.allreduce", "coll.xla.launch:allreduce/"),
         "reduce_local": ("op.reduce_local", "op.launch:")}
PARTS = ("before", "lib", "launch", "wait")


def _named(trace, keep):
    """(starts, ends) ns, by start, of the host events ``keep`` takes."""
    hs, he, hn = trace.host
    idx = np.fromiter((i for i, n in enumerate(hn) if keep(n)), int)
    order = np.argsort(hs[idx], kind="stable")
    return hs[idx][order], he[idx][order]


def split(ctx, call: str, role: str) -> Optional[Dict[str, np.ndarray]]:
    """The ``PARTS`` and the whole ``call`` span, in ns, of each traced
    call of the role's phases; None off the chip (a trace with no TPU
    plane), where the cell drives another call, or where no traced call
    holds exactly one outer span."""
    if (ctx.trace is None or not ctx.trace.chips
            or ctx.cell.traffic["call"] != call):
        return None
    cs, ce = ctx.traced_calls(role)
    if not len(cs):
        return None
    outer, launch = SPANS[call]
    os_, oe = _named(ctx.trace, outer.__eq__)
    if not len(os_):
        return None
    ls, le = _named(ctx.trace, lambda n: n.startswith(launch))
    # the outer spans that start inside each call: [first, last)
    first = np.searchsorted(os_, cs, side="left")
    last = np.searchsorted(os_, ce, side="right")
    j = np.minimum(first, len(os_) - 1)
    one = (last - first == 1) & (oe[j] <= ce)
    if not one.any():
        return None
    cs, ce, o0, o1 = cs[one], ce[one], os_[j[one]], oe[j[one]]
    cum = np.concatenate([[0.0], np.cumsum(le - ls)])
    inside = (cum[np.searchsorted(ls, o1, side="right")]
              - cum[np.searchsorted(ls, o0, side="left")])
    return {"before": o0 - cs, "lib": (o1 - o0) - inside, "launch": inside,
            "wait": ce - o1, "call": ce - cs}


def median_us(ctx, call: str, role: str, part: str) -> Optional[float]:
    """Median over the role's traced calls of one of ``PARTS``, in us."""
    parts = split(ctx, call, role)
    if parts is None:
        return None
    return float(np.median(parts[part])) / 1e3
