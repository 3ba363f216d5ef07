"""The arithmetic behind the metric readers in ``metrics/``: rates on
the host clock over whole blocks, and device shares over the traced
blocks. Each returns None where the run has nothing to read."""
from __future__ import annotations

from typing import Optional

import numpy as np


def avg_latency_us(ctx, role: str) -> Optional[float]:
    """Wall time of the role's blocks over the calls they ran: OSU's
    average latency of a closed loop."""
    blocks = ctx.blocks(role)
    calls = sum(b.calls for b in blocks)
    if not calls:
        return None
    return sum(b.t1 - b.t0 for b in blocks) / calls * 1e6


def algbw_gbps(ctx, role: str) -> Optional[float]:
    """Payload bytes per rank of every call of the role's blocks over
    their wall time."""
    blocks = ctx.blocks(role)
    if not blocks:
        return None
    moved = sum(ctx.phase(b)["bytes_per_rank"] * b.calls for b in blocks)
    return moved / sum(b.t1 - b.t0 for b in blocks) / 1e9


def idle_share(ctx, role: str) -> Optional[float]:
    """Percent of the role's traced blocks in which no op ran on the
    device, averaged over the chips."""
    tb = ctx.traced_blocks(role)
    if not tb:
        return None
    a = np.array([t0 for _, t0, _ in tb])
    b = np.array([t1 for _, _, t1 in tb])
    busy = ctx.trace.busy(a, b).sum()
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / (b - a).sum())


def roofline(ctx, call: str, role: str) -> Optional[float]:
    """Percent of the least device time the calls could take (their
    bytes over the chip's peak, ``calls/<call>.py``'s
    ``roofline_bytes``) in the device time they took: the busy time of
    the role's traced blocks."""
    if ctx.cell.traffic["call"] != call:
        return None
    tb = ctx.traced_blocks(role)
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


def host_residue_us(ctx, role: str) -> Optional[float]:
    """Median over the role's traced calls of the call's span less the
    device busy time inside it (mean over the chips)."""
    if ctx.trace is None or not ctx.trace.chips:
        return None
    s, e = ctx.traced_calls(role)
    if not len(s):
        return None
    return float(np.median((e - s) - ctx.trace.busy(s, e))) / 1e3
