"""Reduce a profiler trace (``.xplane.pb``) of the window to device
busy time, idle gaps and host spans, all on the trace's one clock.

Device events are the ops on each TPU's ``XLA Ops`` line, each named
by its HLO text. Their kind is the HLO opcode read from that text, not
a name scope (``coll/xla`` sets none); an asynchronous pair (``all-gather-start`` ...
``all-gather-done``) counts as busy from the start op to the end of the
done op. Host spans are the ``bench.*`` annotations the window writes.
"""
from __future__ import annotations

import collections
import dataclasses
import re
from typing import Dict, List, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
_SUFFIX = re.compile(r"(\.\d+)+$")
_ASYNC = re.compile(r"-(start|done|update)$")
_HLO = re.compile(r"^%\S+ = .*?\s([a-z][a-z0-9-]*)\(")


def opcode(name: str) -> str:
    """The HLO opcode of an op event, named either by its HLO text
    (``%all-reduce.1 = f32[4,2]{1,0} all-reduce(...)``, as the TPU
    trace names it) or by its op name (``all-reduce-start.2``)."""
    m = _HLO.match(name)
    return m.group(1) if m else _SUFFIX.sub("", name)


def op_kind(name: str) -> str:
    """The opcode less an asynchronous suffix: ``all-reduce-start`` ->
    ``all-reduce``, ``fusion.14`` -> ``fusion``."""
    return _ASYNC.sub("", opcode(name))


def short_name(name: str) -> str:
    """An op event's HLO text up to its opcode: name, shape, opcode."""
    m = _HLO.match(name)
    return name[:m.end() - 1] if m else name


def merge(starts: np.ndarray, ends: np.ndarray) -> Tuple[np.ndarray,
                                                          np.ndarray]:
    """The union of intervals, as sorted disjoint (starts, ends)."""
    if len(starts) == 0:
        return np.zeros(0), np.zeros(0)
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    run_end = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > run_end[:-1]
    idx = np.flatnonzero(new)
    return s[idx], np.maximum.reduceat(e, idx)


class Busy:
    """One chip's busy union, with the busy time inside any spans."""

    def __init__(self, starts, ends):
        self.s, self.e = merge(np.asarray(starts, float),
                               np.asarray(ends, float))
        self._cum = np.concatenate([[0.0], np.cumsum(self.e - self.s)])

    def upto(self, t) -> np.ndarray:
        """Busy ns before each time in ``t``."""
        t = np.asarray(t, float)
        if len(self.s) == 0:
            return np.zeros_like(t)
        i = np.searchsorted(self.s, t, side="right")
        j = np.maximum(i - 1, 0)
        part = np.clip(t - self.s[j], 0.0, self.e[j] - self.s[j])
        return self._cum[j] + np.where(i > 0, part, 0.0)

    def within(self, a, b) -> np.ndarray:
        return self.upto(b) - self.upto(a)


@dataclasses.dataclass
class Trace:
    chips: List[Busy]                       # one per TPU plane
    ops: List[Tuple[str, float]]            # (op name, ns), all chips
    spans: Dict[str, Tuple[np.ndarray, np.ndarray]]   # bench.* spans
    host: Tuple[np.ndarray, np.ndarray, List[str]]    # all host events

    def busy(self, a, b) -> np.ndarray:
        """Mean over chips of the busy ns inside each span [a, b]."""
        return np.mean([c.within(a, b) for c in self.chips], axis=0)

    def span(self, name: str):
        z = np.zeros(0)
        return self.spans.get(name, (z, z))


def _by_start(starts, ends):
    s, e = np.asarray(starts, float), np.asarray(ends, float)
    order = np.argsort(s, kind="stable")
    return s[order], e[order]


def _device_intervals(line) -> Tuple[list, list, list]:
    starts, ends, ops = [], [], []
    pending = {}
    for ev in line.events:
        name, t0 = ev.name, ev.start_ns
        t1 = t0 + ev.duration_ns
        ops.append((name, ev.duration_ns))
        base = opcode(name)
        if base.endswith("-start"):
            pending[op_kind(name)] = t0
            continue
        if base.endswith("-done"):
            t0 = pending.pop(op_kind(name), t0)
        starts.append(t0)
        ends.append(t1)
    return starts, ends, ops


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    prof = ProfileData.from_file(path)
    chips, ops = [], []
    spans = collections.defaultdict(lambda: ([], []))
    hs, he, hn = [], [], []
    for plane in prof.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    s, e, o = _device_intervals(line)
                    chips.append(Busy(s, e))
                    ops += o
            continue
        for line in plane.lines:
            for ev in line.events:
                t0, t1 = ev.start_ns, ev.start_ns + ev.duration_ns
                if ev.name.startswith("bench."):
                    spans[ev.name][0].append(t0)
                    spans[ev.name][1].append(t1)
                hs.append(t0)
                he.append(t1)
                hn.append(ev.name)
    spans = {k: _by_start(*se) for k, se in spans.items()}
    return Trace(chips, ops, spans,
                 (np.asarray(hs, float), np.asarray(he, float), hn))


def device_ops(trace: Trace, top: int = 10) -> List[list]:
    """The ops that took the most device time: [name, seconds per
    chip], summed over the traced run."""
    tot = collections.Counter()
    for name, ns in trace.ops:
        tot[short_name(name)] += ns
    n = max(len(trace.chips), 1)
    return [[k, v / n / 1e9] for k, v in tot.most_common(top)]


def idle_gaps(trace: Trace, a: float, b: float, top: int = 10,
              label_gaps: int = 200) -> List[list]:
    """Chip 0's idle time inside [a, b], by what the host was doing:
    the longest ``label_gaps`` gaps, each named after the shortest host
    event that spans its middle, summed by that name."""
    if not trace.chips:
        return []
    c = trace.chips[0]
    keep = (c.e > a) & (c.s < b)
    s, e = np.clip(c.s[keep], a, b), np.clip(c.e[keep], a, b)
    gs = np.concatenate([[a], e])
    ge = np.concatenate([s, [b]])
    dur = ge - gs
    order = np.argsort(-dur)[:label_gaps]
    hs, he, hn = trace.host
    hd = he - hs
    tot = collections.Counter()
    for g in order:
        if dur[g] <= 0:
            break
        m = (gs[g] + ge[g]) / 2
        inside = np.flatnonzero((hs <= m) & (he >= m))
        name = (hn[inside[np.argmin(hd[inside])]] if len(inside)
                else "no host event")
        tot[name] += dur[g]
    return [[k, v / 1e9] for k, v in tot.most_common(top)]
