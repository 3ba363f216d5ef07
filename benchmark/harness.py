"""One run of one cell: set-up, the window, the check against the
reference, and the metrics read from the window and its trace."""
from __future__ import annotations

import dataclasses
import glob
import json
import operator
import os
import shutil
import tempfile
import time
from typing import Callable, List, Optional

import numpy as np

from benchmark import data, tracereduce, window
from benchmark.spec import HERE, Cell, SpecError


@dataclasses.dataclass
class Context:
    """What a metric reader sees."""
    cell: Cell
    win: window.Window
    setup_s: float
    size: int                        # ranks of the call
    device_kind: str
    trace: Optional[tracereduce.Trace] = None
    trace_blocks: Optional[list] = None   # (t0 ns, t1 ns) per block

    def phase(self, block) -> dict:
        return self.cell.traffic["phases"][block.phase]

    def blocks(self, role: str) -> List[window.Block]:
        return [b for b in self.win.blocks
                if self.phase(b)["role"] == role and b.calls]

    def traced_blocks(self, role: str) -> list:
        """(block, t0 ns, t1 ns) of the role's blocks in the trace."""
        if self.trace is None or not self.trace.chips:
            return []
        return [(b, t0, t1) for b, (t0, t1) in
                zip(self.win.blocks, self.trace_blocks)
                if self.phase(b)["role"] == role and b.calls]

    def traced_calls(self, role: str):
        """(starts, ends) ns of every traced call of the role."""
        names = {f"bench.call:{p['name']}"
                 for p in self.cell.traffic["phases"] if p["role"] == role}
        parts = [self.trace.span(n) for n in sorted(names)]
        return (np.concatenate([np.zeros(0)] + [p[0] for p in parts]),
                np.concatenate([np.zeros(0)] + [p[1] for p in parts]))

    def peak(self, name: str) -> float:
        with open(os.path.join(HERE, "peaks.json")) as f:
            peaks = json.load(f)["devices"]
        if self.device_kind not in peaks:
            raise SpecError(f"no peaks for device kind {self.device_kind!r}"
                            " in peaks.json")
        return float(peaks[self.device_kind][name])


def log_checks(result: dict, log: Callable[[str], None]) -> None:
    for name, c in result["checks"].items():
        lim = (f"<= {c['max']}" if "max" in c else f">= {c['min']}")
        log(f"check {name}: {c['value']} (limit {lim})")


def _check(cell, comm, entries, win, log):
    """Compare each sampled output with the reference on the host
    copies of its inputs: every element of every rank's result, in the
    case's type (the traffic's data makes the result exact there).
    Returns the checks and the number of outputs that were wrong."""
    call = cell.call
    phases = cell.traffic["phases"]
    want = {}
    mismatched = wrong = 0
    compared = {p["name"]: 0 for p in phases}
    for _, _, y in win.kept:
        start = getattr(y, "copy_to_host_async", None)
        if start is not None:          # a host array is there already
            start()
    for p, e, y in win.kept:
        case = phases[p]["cases"][e % len(phases[p]["cases"])]
        if (p, e) not in want:
            want[p, e] = call.reference(call.inputs(entries[p][e]),
                                        case).astype(data.dtype(
                                            case["dtype"]))
        w = want[p, e]
        try:
            got = call.output(comm, y)
        except AssertionError as err:
            log(f"output of phase {p} entry {e}: {err}")
            bad = w.size * comm.size
        else:
            bad = int(np.count_nonzero(got != w))
        mismatched += bad
        wrong += bad > 0
        compared[phases[p]["name"]] += 1
    checks = {"mismatched_elements": {"value": mismatched, "max": 0},
              "calls_raised": {"value": int(win.error is not None),
                               "max": 0}}
    for name, n in compared.items():
        checks[f"outputs_compared.{name}"] = {"value": n, "min": 1}
    return checks, wrong


def _ok(checks: dict) -> bool:
    return all(c["value"] <= c["max"] if "max" in c else c["value"] >= c["min"]
               for c in checks.values())


def completer(call) -> Callable:
    """The call file's ``complete(y)``, or ``y.block_until_ready()``
    where it has none."""
    return getattr(call, "complete", None) or operator.methodcaller(
        "block_until_ready")


def _warm(fn, complete, entries, calls: int) -> None:
    for args in entries:
        complete(fn(*args))
    for i in range(calls):
        complete(fn(*entries[i % len(entries)]))


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))


def _read(readers, entries, ctx) -> dict:
    out = {}
    for m in entries:
        v = readers[m["name"]].read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run_cell(cell: Cell, MPI, seed: int, seconds: float, trace: bool,
             t_start: float, log: Callable[[str], None]) -> dict:
    """Run ``cell`` once; returns the result line as a dict."""
    import jax
    stats = window.CompileCounter()
    call, phases = cell.call, cell.traffic["phases"]
    split = {}
    t = time.perf_counter()
    target = call.setup(MPI, cell.config)
    split["device_init"] = time.perf_counter() - t

    from ompi_tpu.native import loader
    t = time.perf_counter()
    loader.get_lib()
    split["native_library"] = time.perf_counter() - t

    t = time.perf_counter()
    entries = [call.make_entries(MPI, target, ph, seed, p)
               for p, ph in enumerate(phases)]
    jax.block_until_ready(entries)
    split["data"] = time.perf_counter() - t

    fn, complete = call.function(MPI, target), completer(call)
    t = time.perf_counter()
    c0 = (stats.compiles, stats.hits, stats.seconds)
    for ph, ent in zip(phases, entries):
        _warm(fn, complete, ent, ph["warmup_calls"])
    split["warmup"] = time.perf_counter() - t
    log("setup: " + ", ".join(f"{k} {v:.3f} s" for k, v in split.items())
        + f"; warmup compiles {stats.compiles - c0[0]} "
        f"({stats.seconds - c0[2]:.3f} s), persistent-cache hits "
        f"{stats.hits - c0[1]}; all compiles {stats.compiles}, hits "
        f"{stats.hits}")
    log(f"served: {call.served(MPI, target)}")

    nb = window.n_blocks(seconds, cell.traffic["block_seconds"],
                         len(phases))
    plans = [data.sample_plan(seed, p, len(range(p, nb, len(phases))),
                              ph["sample_per_block"], ph["sample_within"],
                              len(entries[p]), ph.get("sample_blocks"))
             for p, ph in enumerate(phases)]
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    c0 = stats.compiles
    setup_s = time.perf_counter() - t_start
    win = window.run(fn, complete, phases, entries, plans, seconds,
                     cell.traffic["block_seconds"], annotate=trace)
    in_window = stats.compiles - c0
    if trace:
        jax.profiler.stop_trace()
    log(f"window: {win.calls} calls in {len(win.blocks)} blocks "
        f"({', '.join(str(b.calls) for b in win.blocks)}), "
        f"{win.blocks[-1].t1 - win.blocks[0].t0:.3f} s, compiles in the "
        f"window {in_window}")
    if win.error:
        log(f"a call raised in the window:\n{win.error}")
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips,
              "memory_peak_bytes": _memory_peak(target.devices)}

    t = time.perf_counter()
    checks, wrong = _check(cell, target, entries, win, log)
    log(f"reference: {time.perf_counter() - t:.3f} s, "
        f"{len(win.kept)} outputs")
    del entries, win.kept[:]

    ctx = Context(cell, win, setup_s, target.size, dev.device_kind)
    result = {"correct": _ok(checks), "attempted": win.calls,
              "failed": wrong + checks["calls_raised"]["value"],
              "metrics": {}, "device": device}
    if trace:
        t = time.perf_counter()
        path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                         recursive=True)[0]
        ctx.trace = tracereduce.load(path)
        shutil.rmtree(tdir, ignore_errors=True)
        ctx.trace_blocks = _trace_blocks(ctx.trace, phases, win)
        a, b = ctx.trace_blocks[0][0], ctx.trace_blocks[-1][1]
        busy = (float(ctx.trace.busy([a], [b])[0])
                if ctx.trace.chips else 0.0)
        device["busy_s"] = busy / 1e9
        device["window_s"] = (b - a) / 1e9
        result["metrics"] = _read(cell.readers(cell.per_layer),
                                  cell.per_layer, ctx)
        result["breakdown"] = {
            "device_ops": tracereduce.device_ops(ctx.trace),
            "idle_gaps": tracereduce.idle_gaps(ctx.trace, a, b)}
        log(f"trace: {len(ctx.trace.chips)} device planes, "
            f"{len(ctx.trace.ops)} device ops, read in "
            f"{time.perf_counter() - t:.3f} s")
    else:
        result["metrics"] = _read(cell.readers(cell.end_to_end),
                                  cell.end_to_end, ctx)
    result["checks"] = checks
    return result


def _trace_blocks(trace, phases, win) -> list:
    """(t0, t1) ns of each window block, from its annotation."""
    seen = {}
    out = []
    for b in win.blocks:
        name = "bench.block:" + phases[b.phase]["name"]
        s, e = trace.span(name)
        k = seen.get(name, 0)
        seen[name] = k + 1
        out.append((float(s[k]), float(e[k])))
    return out
