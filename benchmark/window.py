"""The measured window: a closed loop of blocking calls, one
outstanding at a time, in blocks that take the traffic's phases in
turn."""
from __future__ import annotations

import dataclasses
import time
import traceback
from typing import Dict, List, Optional


@dataclasses.dataclass
class Block:
    phase: int            # index into the traffic's phases
    t0: float             # host perf_counter seconds
    t1: float
    calls: int


@dataclasses.dataclass
class Window:
    blocks: List[Block]
    kept: list            # (phase, entry, output) of the sampled calls
    error: Optional[str]  # traceback of a call that raised

    @property
    def calls(self) -> int:
        return sum(b.calls for b in self.blocks)


class CompileCounter:
    """JAX's backend compiles (persistent-cache loads included) and
    persistent-cache hits, from its monitoring events."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.compiles = self.hits = 0
        self.seconds = 0.0
        event = dispatch.BACKEND_COMPILE_EVENT

        def on_duration(name, secs, **_kw):
            if name == event:
                self.compiles += 1
                self.seconds += secs

        def on_event(name, **_kw):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def n_blocks(seconds: float, block_seconds: float, phases: int) -> int:
    return max(phases, round(seconds / block_seconds))


def _block(fn, complete, entries, keep: Dict[int, int], deadline: float,
           kept, phase: int, label: Optional[str]) -> int:
    """Calls until the deadline; returns how many ran. ``complete``
    waits for a call's result and returns it finished; that value is
    what a sampled call keeps. The call's return is bound to ``y``
    before the wait, so the previous output is released while the
    device runs and not after the wait. Two loops, so that the
    untraced window pays nothing for the annotations."""
    clock = time.perf_counter
    n = len(entries)
    i = 0
    if label is None:
        while True:
            y = fn(*entries[i % n])
            y = complete(y)
            if i in keep:
                kept.append((phase, keep[i], y))
            i += 1
            if clock() >= deadline:
                return i
    from jax.profiler import TraceAnnotation
    while True:
        with TraceAnnotation(label):
            y = fn(*entries[i % n])
            y = complete(y)
        if i in keep:
            kept.append((phase, keep[i], y))
        i += 1
        if clock() >= deadline:
            return i


def run(fn, complete, phases, entries, plans, seconds: float,
        block_seconds: float, annotate: bool) -> Window:
    """``entries[p]`` are the args of phase p's calls, ``plans[p][k]``
    the sampled calls of its k-th block. With ``annotate`` every block
    and every call is a profiler span named ``bench.block:<phase>`` /
    ``bench.call:<phase>``."""
    from jax.profiler import TraceAnnotation
    nb = n_blocks(seconds, block_seconds, len(phases))
    length = seconds / nb
    win = Window([], [], None)
    t = time.perf_counter()
    for b in range(nb):
        p = b % len(phases)
        name = phases[p]["name"]
        keep = plans[p][b // len(phases)]
        t0, deadline = t, t + length
        try:
            if annotate:
                with TraceAnnotation(f"bench.block:{name}"):
                    calls = _block(fn, complete, entries[p], keep,
                                   deadline, win.kept, p,
                                   f"bench.call:{name}")
            else:
                calls = _block(fn, complete, entries[p], keep, deadline,
                               win.kept, p, None)
        except Exception:                 # noqa: BLE001 — reported as a
            win.error = traceback.format_exc()   # failed call, not raised
            win.blocks.append(Block(p, t0, time.perf_counter(), 0))
            break
        t = time.perf_counter()
        win.blocks.append(Block(p, t0, t, calls))
    return win
