"""coll/tuned — the decision layer.

Mirrors two reference components at once, because on TPU they collapse
into one decision: (a) coll/tuned's per-collective decision functions
choosing an algorithm from message size (``coll_tuned_decision_fixed.c``),
and (b) coll/accelerator's device-buffer staging shim
(``coll_accelerator_allreduce.c:55-80``) — except *inverted*: the
reference stages device buffers to host to run CPU algorithms; here the
native path IS the device path, and the decision is whether a
*host*-resident buffer is large enough to be worth staging to HBM to ride
ICI, or small enough to run with host NumPy.

The switch point is an MCA var (``coll_tuned_stage_min_bytes``) with an
optional JSON dynamic-rules file (``coll_tuned_dynamic_rules``) that can
override it per collective — the re-design of tuned's dynamic rule file
(``coll_tuned_component.c:187-191``).
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, Optional, Tuple

import numpy as np

from ompi_tpu.accelerator import (LOCUS_DEVICE, check_addr, to_device,
                                  to_host)
from ompi_tpu.coll.basic import BasicCollModule
from ompi_tpu.coll.framework import coll_framework
from ompi_tpu.coll.xla import XlaCollModule
from ompi_tpu.mca import var
from ompi_tpu.mca.base import Component


_rules_cache: Dict[str, Tuple[float, Dict]] = {}


def _load_rules(path: str) -> Dict[str, Dict]:
    """mtime-memoized: the decision layer consults this per collective
    call, so re-parsing the JSON every time would put file IO on the
    hot path."""
    if not path:
        return {}
    try:
        mtime = os.path.getmtime(path)
    except OSError:
        return {}
    cached = _rules_cache.get(path)
    if cached is not None and cached[0] == mtime:
        return cached[1]
    try:
        with open(path) as f:
            data = json.load(f)
        rules = data if isinstance(data, dict) else {}
    except (OSError, ValueError):
        rules = {}
    _rules_cache[path] = (mtime, rules)
    # A reload changes decision inputs that the hot-path epoch memo
    # (coll/xla allreduce _fast) otherwise can't see: bump the var
    # epoch so warm (shape, dtype, op) entries re-decide. Without this,
    # editing the rules file on disk would never take effect on warm
    # entries — a regression vs the per-call lookup.
    var.bump_epoch()
    return rules


# -- probe-earned staging threshold ---------------
# The r4 run of record staged 8 MB allreduces onto a tier its own A/B
# showed 1.6x slower, because the switch point was a data-blind 1 MB
# constant. Like the bml's bulk routing, the threshold now earns its
# value from a measurement: a local micro-probe times the staged path's
# mechanics (H2D + compiled dispatch + D2H round trip) against the host
# fold's (NumPy reduce + transport crossing when one is in play), fits
# per-byte cost models, and solves for the crossover. A user-set
# coll_tuned_stage_min_bytes (env/file/CLI/MPI_T write) overrides the
# probe, exactly as btl_sm_min_bytes overrides the bml's.
_NEVER_STAGE = 1 << 62
_probe_state: Dict[str, object] = {"ran": False}


def staging_probe(transport_bps: Optional[float] = None,
                  nranks: int = 1) -> Tuple[int, Dict[str, object]]:
    """Measure the staged-vs-host crossover on THIS platform.

    Two sizes bound a linear cost model per path; the staged side runs
    the actual mechanics (device_put + jitted op + host fetch), the
    host side runs the NumPy fold plus — in a per-rank world — the
    measured transport's per-byte cost for the log-round byte shuffle
    (``transport_bps`` from the bml probe). Returns
    (crossover_bytes, basis)."""
    import jax
    sizes = (256 << 10, 2 << 20)
    fn = jax.jit(lambda a: a * 1.0)

    def _med(f, reps=3):
        f()                              # warm (compile / first touch)
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            f()
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    staged, host = [], []
    for nb in sizes:
        buf = np.ones(nb // 4, np.float32)
        other = buf.copy()
        out = np.empty_like(buf)
        staged.append(_med(lambda: np.asarray(fn(jax.device_put(buf)))))
        host.append(_med(lambda: np.add(buf, other, out=out)))
    n1, n2 = sizes
    b_s = (staged[1] - staged[0]) / (n2 - n1)
    a_s = staged[0] - b_s * n1
    b_h = (host[1] - host[0]) / (n2 - n1)
    a_h = host[0] - b_h * n1
    # host-tier wire volume per member: 2 serial payloads for the
    # reduce+bcast schedule, 2(n-1)/n with full-duplex overlap once
    # the segment-pipelined ring handles the large sizes this probe
    # is deciding for (core/rankcomm, docs/LARGEMSG.md)
    from ompi_tpu.pml import pipeline as _pl
    wire_factor = (2.0 * (nranks - 1) / nranks
                   if nranks > 1 and _pl.enabled() else 2.0)
    if transport_bps and transport_bps > 0 and nranks > 1:
        # host-tier collectives shuffle the payload volume above
        # through the byte transport; the staged tier's device
        # dispatch replaces that entirely
        b_h += wire_factor / transport_bps
    basis: Dict[str, object] = {
        "ran": True,
        "staged_per_mb_ms": round(b_s * (1 << 20) * 1e3, 3),
        "host_per_mb_ms": round(b_h * (1 << 20) * 1e3, 3),
        "staged_fixed_us": round(a_s * 1e6, 1),
        "host_fixed_us": round(a_h * 1e6, 1),
        **({"transport_gbps": round(transport_bps / 1e9, 3)}
           if transport_bps else {}),
    }
    if b_h <= b_s:
        # the host side scales at least as well as staging: staging can
        # only win on fixed cost, which it never does (a_s > a_h on
        # every platform measured) — never stage
        cross = _NEVER_STAGE
    else:
        n_star = (a_s - a_h) / (b_h - b_s)
        cross = int(min(max(n_star, 64 << 10), _NEVER_STAGE))
    # Close the staging contract: the two-point
    # fit EXTRAPOLATES, and the round-5 record routed 8 MB to a tier
    # its own A/B measured 1.3x slower because the fitted crossover
    # landed just under the payload. Confirm by MEASUREMENT at the
    # first size the fit would route to staging: if the host path
    # still wins there, walk the candidate up (x2) until the staged
    # side actually wins or staging is ruled out entirely. The
    # adopted winner then gets a 1.5x hysteresis band — payloads near
    # the boundary, where all the fit error lives, keep the host path.
    if cross < _NEVER_STAGE:
        tx_per_byte = (wire_factor / transport_bps
                       if transport_bps and transport_bps > 0
                       and nranks > 1 else 0.0)
        confirm: Dict[str, object] = {}
        candidate = int(min(max(cross, 64 << 10), 16 << 20))
        adopted = _NEVER_STAGE
        for _ in range(3):
            nb = candidate - (candidate % 4) or 4
            buf = np.ones(nb // 4, np.float32)
            other = buf.copy()
            out = np.empty_like(buf)
            staged_t = _med(lambda: np.asarray(fn(jax.device_put(buf))),
                            reps=2)
            host_t = _med(lambda: np.add(buf, other, out=out),
                          reps=2) + tx_per_byte * nb
            confirm = {"confirm_bytes": nb,
                       "confirm_staged_ms": round(staged_t * 1e3, 3),
                       "confirm_host_ms": round(host_t * 1e3, 3)}
            if staged_t < host_t:
                adopted = candidate
                break
            if candidate >= 16 << 20:   # staging never won in range
                break
            candidate = min(candidate * 2, 16 << 20)
        basis.update(confirm)
        if adopted < _NEVER_STAGE:
            cross = int(min(adopted * 1.5, _NEVER_STAGE))
            basis["hysteresis"] = 1.5
        else:
            cross = _NEVER_STAGE
            basis["confirm_rejected_staging"] = True
    basis["stage_min_bytes"] = cross if cross < _NEVER_STAGE else -1
    return cross, basis


def adopt_probed_stage_min(value: int, basis: Dict[str, object]) -> None:
    """Install a probe result (rank 0 measures, every rank adopts the
    SAME value through the modex — the staging decision must stay
    rank-symmetric, and timing probes are not)."""
    _probe_state.update(basis)
    _probe_state["ran"] = True
    _probe_state["value"] = int(value)


def probed_stage_basis() -> Dict[str, object]:
    """The measured basis of the staging decision (comm_method row)."""
    return dict(_probe_state)


def _probed_stage_min() -> Optional[int]:
    if not _probe_state.get("ran"):
        try:
            value, basis = staging_probe()
            adopt_probed_stage_min(value, basis)
        except Exception:                # noqa: BLE001 — probe is
            _probe_state["ran"] = True   # advisory, never fatal
            _probe_state["error"] = True
    v = _probe_state.get("value")
    return int(v) if v is not None else None


def small_allreduce_limits() -> Tuple[int, int]:
    """(max_bytes, max_ranks) for the combined small-message allreduce
    (the inline-combining gossip path, ``core/rankcomm.py``)."""
    return (int(var.var_get("coll_tuned_small_allreduce_max_bytes",
                            4096)),
            int(var.var_get("coll_tuned_small_allreduce_max_ranks", 32)))


def stage_min_for(func: str) -> int:
    """The staging switch point for one collective: the dynamic-rules
    per-collective override when present, else the user-set MCA var,
    else the probe-earned platform value. One decision plane shared by
    the single-controller TunedCollModule and the per-rank staged
    device tier."""
    rules = _load_rules(var.var_get("coll_tuned_dynamic_rules", ""))
    override = rules.get(func, {}).get("stage_min_bytes")
    if override is not None:
        return int(override)
    if var.var_overridden("coll_tuned_stage_min_bytes"):
        return int(var.var_get("coll_tuned_stage_min_bytes", 1 << 20))
    probed = _probed_stage_min()
    if probed is not None:
        return probed
    return int(var.var_get("coll_tuned_stage_min_bytes", 1 << 20))


class TunedCollModule:
    def __init__(self, comm, rules: Dict[str, Dict]):
        self.comm = comm
        self.device = XlaCollModule(comm)
        self.host = BasicCollModule(comm)
        self.rules = rules

    def _decide(self, func: str, buf):
        """Return (module, stage_back: bool) for this call."""
        if check_addr(buf) == LOCUS_DEVICE:
            return self.device, False
        nbytes = getattr(buf, "nbytes", 0)
        if nbytes >= stage_min_for(func):
            return self.device, True      # stage host->HBM, ride ICI
        return self.host, False

    def _run(self, func: str, buf, *args):
        mod, stage = self._decide(func, buf)
        if stage:
            y = getattr(mod, func)(to_device(buf, self.comm.sharding), *args)
            return to_host(y)
        return getattr(mod, func)(buf, *args)

    # Per-function entry points (the vtable winners).
    def allreduce(self, x, op):
        return self._run("allreduce", x, op)

    def allreduce_dtype(self, x, op, dt, count: int,
                        preserve_gaps: bool):
        """Fused derived-datatype path: device buffers only (the
        communicator gates on locus), so the decision is always the
        device module's."""
        return self.device.allreduce_dtype(x, op, dt, count,
                                           preserve_gaps)

    def reduce(self, x, op, root):
        return self._run("reduce", x, op, root)

    def bcast(self, x, root):
        return self._run("bcast", x, root)

    def allgather(self, x):
        return self._run("allgather", x)

    def gather(self, x, root):
        return self._run("gather", x, root)

    def scatter(self, x, root):
        return self._run("scatter", x, root)

    def alltoall(self, x):
        return self._run("alltoall", x)

    def reduce_scatter_block(self, x, op):
        return self._run("reduce_scatter_block", x, op)

    def scan(self, x, op):
        return self._run("scan", x, op)

    def exscan(self, x, op):
        return self._run("exscan", x, op)

    def barrier(self) -> None:
        self.device.barrier()

    def _ibarrier_arrays(self):
        return self.device._ibarrier_arrays()


class TunedCollComponent(Component):
    name = "tuned"

    def register_params(self):
        var.var_register(
            "coll", "tuned", "priority", vtype="int", default=60,
            help="Selection priority of the tuned decision component")
        var.var_register(
            "coll", "tuned", "stage_min_bytes", vtype="int", default=1 << 20,
            help="Host buffers at least this large are staged to HBM and "
                 "run on the ICI-native path; smaller ones run host-side")
        var.var_register(
            "coll", "tuned", "dynamic_rules", vtype="str", default="",
            help="Path to a JSON per-collective decision-rule override "
                 "file (re-design of coll/tuned dynamic rules)")
        var.var_register(
            "coll", "tuned", "small_allreduce_max_bytes", vtype="int",
            default=4096,
            help="Per-rank host payloads at or below this take the "
                 "combined small-message allreduce (one eager send per "
                 "peer, inline reader-thread combining, one wakeup)")
        var.var_register(
            "coll", "tuned", "small_allreduce_max_ranks", vtype="int",
            default=32,
            help="The combined small-message allreduce sends rank-count "
                 "squared messages total; larger worlds use the tree "
                 "algorithms")

    def comm_query(self, comm):
        if comm is None or not getattr(comm, "mesh", None):
            return None
        rules = _load_rules(var.var_get("coll_tuned_dynamic_rules", ""))
        prio = var.var_get("coll_tuned_priority", 60)
        return (prio, TunedCollModule(comm, rules))


coll_framework.register(TunedCollComponent())
