"""Readings of one phase of a traced window, for a cell whose phases
drive different MPI calls under one role (``coll_mix``: allgather,
alltoall, bcast and reduce_scatter_block, all ``bw``).

- ``roofline``: the least device time of one phase's calls in the
  device busy time of that phase's own traced blocks.
- ``split``: each traced call cut at the library's layer boundaries, as
  ``libspans.py`` cuts an allreduce, with the outer and launch spans
  that the call's phase names (``comm.<coll>``, nested in it
  ``coll.xla.launch:<coll>/<algorithm>``).

Each returns None where the run has nothing to read: off the chip, in a
cell that drives another call, or in a trace without the spans, as of a
program that writes none."""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from benchmark.libspans import PARTS, _named

# the call a cell drives -> its phase -> (outer span, launch spans' prefix)
SPANS = {"coll_mix": lambda coll: (f"comm.{coll}",
                                   f"coll.xla.launch:{coll}/")}


def traced_blocks(ctx, phase: str) -> list:
    """(block, t0 ns, t1 ns) of the phase's blocks in the trace."""
    if ctx.trace is None or not ctx.trace.chips:
        return []
    return [(b, t0, t1) for b, (t0, t1) in
            zip(ctx.win.blocks, ctx.trace_blocks)
            if ctx.phase(b)["name"] == phase and b.calls]


def roofline(ctx, call: str, phase: str) -> Optional[float]:
    """Percent of the least device time of the phase's calls (their
    bytes over the chip's peak, ``calls/<call>.py``'s
    ``roofline_bytes``) in the busy time of the phase's traced
    blocks."""
    if ctx.cell.traffic["call"] != call:
        return None
    tb = traced_blocks(ctx, phase)
    if not tb:
        return None
    least = 0.0
    for blk, _, _ in tb:
        nbytes, peak = ctx.cell.call.roofline_bytes(ctx.phase(blk),
                                                     ctx.size)
        least += blk.calls * nbytes / ctx.peak(peak) * 1e9
    busy = ctx.trace.busy(np.array([t0 for _, t0, _ in tb]),
                          np.array([t1 for _, _, t1 in tb])).sum()
    if busy <= 0:
        return None
    return 100.0 * least / busy


def split_calls(trace, cs, ce, outer: str, launch: str
                ) -> Optional[Dict[str, np.ndarray]]:
    """``libspans.PARTS`` and the whole call, in ns, of each call
    [cs, ce] that holds exactly one ``outer`` span, the launch spans
    being the host events whose name starts with ``launch``; None
    where no call holds one."""
    if not len(cs):
        return None
    os_, oe = _named(trace, outer.__eq__)
    if not len(os_):
        return None
    ls, le = _named(trace, lambda n: n.startswith(launch))
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


def split(ctx, call: str) -> Optional[Dict[str, np.ndarray]]:
    """The parts of every traced call of every phase, each phase's
    calls cut at the spans it names; None where no phase's calls hold
    them."""
    if (ctx.trace is None or not ctx.trace.chips
            or ctx.cell.traffic["call"] != call):
        return None
    parts = []
    for ph in ctx.cell.traffic["phases"]:
        cs, ce = ctx.trace.span(f"bench.call:{ph['name']}")
        p = split_calls(ctx.trace, cs, ce, *SPANS[call](ph["name"]))
        if p is not None:
            parts.append(p)
    if not parts:
        return None
    return {k: np.concatenate([p[k] for p in parts])
            for k in PARTS + ("call",)}


def median_us(ctx, call: str, part: str) -> Optional[float]:
    """Median over the traced calls of every phase of one of
    ``libspans.PARTS``, in us."""
    parts = split(ctx, call)
    if parts is None:
        return None
    return float(np.median(parts[part])) / 1e3
