"""Benchmark driver — OSU-style collective latency on the native path.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "us", "vs_baseline": N, ...}

Headline metric: **osu_allreduce p50 latency @ 8 B** — dispatch-to-
completion of the cached compiled XLA collective, amortized OSU-style
(N back-to-back calls, one completion observation, minus the observation
round-trip). ``vs_baseline`` is the speedup over the reference
architecture's device-buffer strategy for the same call:
coll/accelerator-style staging (D2H -> host reduce -> H2D,
``coll_accelerator_allreduce.c:55-80``) on the same hardware.

Methodology notes:
- Completion is observed by fetching ONE element via a device-side
  slice, never the whole buffer (round 1 pulled the full 256 MB result
  across the host link every iteration — that transfer, not the
  collective, was 942 ms).
- ``observe_rtt_ms`` is the measured cost of observing *any* fresh
  device result (a 4-byte fetch with zero compute). It is the floor
  for any single blocking call, and is subtracted once per amortized
  loop. ``osu_barrier_blocking_us``
  reports the un-amortized single-shot barrier, which inherits it.
- ``dispatch_only_8B_us`` is the framework's own per-call cost
  (validation + decision + cached-executable dispatch) with no
  completion wait — the part this framework controls.
- A run finds a TPU or fails, unless ``JAX_PLATFORMS=cpu`` asks for
  the host platform: no CPU number is ever reported as a chip's.
- When the world is size 1 (the single-chip run), algorithm
  A/B numbers and >1-rank collective rows come from a subprocess on an
  8-virtual-device CPU mesh (``ab_matrix``) so the run of record is
  still one command.
- The per-rank 8 B rows carry the small-message control-plane
  breakdown (marshal / btl RTT / rounds / measured wakeups-per-call /
  frames-per-wakeup / combine hits); the mechanisms behind those
  counters — the ctl flush window, wakeup coalescing, and the
  sub-eager dispatch cache — are documented in ``docs/SMALLMSG.md``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

# ---- child mode must configure the platform BEFORE jax import -------
if "--ab-child" in sys.argv or "--perrank-child" in sys.argv \
        or "--compress-child" in sys.argv \
        or "--compress-device-child" in sys.argv \
        or "--pcoll-child" in sys.argv \
        or "--largemsg-child" in sys.argv \
        or "--shm-child" in sys.argv \
        or "--rma-child" in sys.argv \
        or "--ft-child" in sys.argv \
        or "--telemetry-child" in sys.argv:
    os.environ["JAX_PLATFORMS"] = "cpu"
if "--ab-child" in sys.argv or "--compress-device-child" in sys.argv \
        or "--telemetry-child" in sys.argv:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8")

import numpy as np

# Measure the real compiled XLA collective, not coll/self's identity
# shortcut (which wins selection on a size-1 world and returns the input
# buffer untouched — a meaningless 0-cost "collective").
os.environ.setdefault("OMPI_TPU_MCA_coll_self_priority", "1")


def _fetch(y):
    """Observe completion: fetch ONE element through a device-side
    slice — the cheapest completion observation available, and one
    that never pulls the whole result across the host link."""
    if isinstance(y, (list, tuple)):
        y = y[0]
    if isinstance(y, np.ndarray):
        return y.ravel()[:1]
    return np.asarray(y.ravel()[0:1])


def _measure_rtt(iters: int = 5) -> float:
    """Round-trip of observing a FRESH device value (no compute). This
    is the completion-observation floor; round 1 measured a cached
    (already-fetched) array, which returns from a host-side cache in
    ~5 us and under-stated the baseline by 4 orders of magnitude."""
    import jax
    ts = []
    jax.device_put(np.float32(0))            # connection warm-up
    for i in range(iters):
        z = jax.device_put(np.float32(i))
        t0 = time.perf_counter()
        np.asarray(z)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _blocking(fn, reps: int = 3) -> float:
    """Un-amortized single-shot latency in us: one call + full
    completion observation per rep (inherits the transport RTT by
    definition — the honest row next to every amortized one)."""
    _fetch(fn())
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        _fetch(fn())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e6


def _osu(fn, iters: int, rtt_s: float, chunk: int = 0) -> float:
    """OSU methodology: ``iters`` back-to-back dispatches (the device
    executes them serially), one completion observation, amortize, and
    charge the observation round-trips. ``chunk`` bounds the unsynced
    batch depth (the forced-host CPU backend can overflow XLA's
    in-process collective rendezvous on very deep unsynced queues —
    observed in round 1); each chunk boundary adds one observation,
    accounted in the subtraction."""
    _fetch(fn())                             # warm: compile + drain
    step = chunk if chunk else iters
    t0 = time.perf_counter()
    syncs = 0
    done = 0
    r = None
    while done < iters:
        for _ in range(min(step, iters - done)):
            r = fn()
        _fetch(r)
        syncs += 1
        done += step
    total = time.perf_counter() - t0
    return max((total - rtt_s * syncs) / iters, 1e-9)


def _overlap_pct(world, MPI, elems: int = 1 << 20) -> dict:
    """osu_iallreduce-style overlap: compute/communication overlap of
    the schedule-driven nonblocking allreduce (coll/nbc + the progress
    engine), under the weak-progress model (MPI_Test calls sliced into
    the host compute, as osu_iallreduce does). Observes the final
    result (one-element fetch) so the timing covers true completion."""
    import numpy as _np
    ox = world.alloc((elems,), _np.float32, fill=1.0)

    # instrumented pure run: wall time split into
    # dispatch (the i-call itself: schedule build + first enqueue) and
    # wait (rounds progressing to completion), plus PROCESS CPU time —
    # on a shared-core host the virtual mesh's compute burns this
    # process's CPU, and (wall - cpu)/wall is the EXACT fraction of
    # the collective during which the core is free for overlap.
    disp_l, wait_l, cpu_l, wall_l = [], [], [], []

    def pure(record=True):
        w0 = time.perf_counter()
        c0 = time.process_time()
        req = world.iallreduce(ox, MPI.SUM)
        d = time.perf_counter() - w0
        req.wait()
        _fetch(req.get())
        wall = time.perf_counter() - w0
        if record:
            disp_l.append(d)
            wait_l.append(wall - d)
            cpu_l.append(time.process_time() - c0)
            wall_l.append(wall)
        return wall

    pure(record=False)                               # warm
    t_pure = float(np.median([pure() for _ in range(3)]))
    t_pure_cpu = float(np.median(cpu_l))
    t_both_l, t_cpu_l = [], []
    for _ in range(3):
        t0 = time.perf_counter()
        req = world.iallreduce(ox, MPI.SUM)
        cpu = 0.0
        for _ in range(4):
            cpu += _calibrated_busy(t_pure / 4)
            req.test()
        req.wait()
        _fetch(req.get())
        t_both_l.append(time.perf_counter() - t0)
        t_cpu_l.append(cpu)
    t_both = float(np.median(t_both_l))
    t_cpu = float(np.median(t_cpu_l))
    overlap = (t_pure + t_cpu - t_both) / t_pure * 100.0
    # the measured ceiling: only the core-free part of the pure run can
    # hide injected host compute; everything else is contention by
    # construction on a shared core
    bound = max(0.0, (t_pure - t_pure_cpu) / t_pure * 100.0)
    out = {"iallreduce_overlap_pct": round(min(max(overlap, 0.0),
                                               100.0), 1),
           "iallreduce_4MB_us": round(t_pure * 1e6, 2),
           "iallreduce_dispatch_us": round(
               float(np.median(disp_l)) * 1e6, 1),
           "iallreduce_wait_us": round(
               float(np.median(wait_l)) * 1e6, 1),
           "iallreduce_pure_cpu_ratio": round(t_pure_cpu / t_pure, 2),
           "iallreduce_overlap_bound_pct": round(bound, 1),
           "iallreduce_busy_inflation_x": round(
               t_cpu / max(t_pure, 1e-9), 2)}
    cores = os.cpu_count() or 1
    if cores <= 2:
        # the "device" here is the virtual CPU mesh: its compute and
        # the injected host busy-loop share the same core(s), so the
        # measured overlap is scheduler interleaving bounded by
        # iallreduce_overlap_bound_pct above — on real TPU the comm
        # runs on the chip while the host computes and the bound rises
        # toward 100%. Record the ceiling so the number is read
        # honestly.
        out["iallreduce_overlap_capped_by_host_cores"] = cores
        if overlap > bound:              # raw value: rounding must not
            # flip the classification at the boundary
            # the core-free ceiling assumes COOPERATIVE overlap (comm
            # offloaded while the host computes); process_time counts
            # CPU across ALL threads, so with the CPU backend's own
            # compute threads saturating the core the ceiling reads
            # ~0 while the OS still timeslices the busy-loop against
            # the mesh's backend threads — measured overlap above the
            # ceiling is preemptive interleaving credit, not offload
            out["iallreduce_overlap_model"] = "timeslice_interleaving"
    return out


def _calibrated_busy(seconds: float) -> float:
    """Host-side compute of ~``seconds``; returns actual elapsed."""
    t0 = time.perf_counter()
    x = np.random.default_rng(0).random(4096)
    while time.perf_counter() - t0 < seconds:
        x = np.sqrt(x * x + 1e-9)
    return time.perf_counter() - t0


def _perrank_child() -> None:
    """One rank of a 2-process per-rank job (launched by the parent
    via ``mpirun --per-rank``): pt2pt ping-pong latency, one-way
    stream bandwidth, an 8 B allreduce over the btl algorithms, and
    the bml transport counters. Rank 0 prints one JSON line."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import ompi_tpu as MPI
    MPI.Init()
    w = MPI.get_comm_world()
    r, peer = w.rank(), 1 - w.rank()

    token = np.zeros(1)
    w.barrier()
    t0 = time.perf_counter()
    iters = 100
    for _ in range(iters):
        if r == 0:
            w.send(token, peer, tag=9)
            token, _ = w.recv(peer, tag=9)
        else:
            token, _ = w.recv(peer, tag=9)
            w.send(token, peer, tag=9)
    rtt_us = (time.perf_counter() - t0) / iters * 1e6

    chunk = np.zeros((256 << 10) // 8, dtype=np.int64)
    reps = 16
    w.barrier()
    t0 = time.perf_counter()
    if r == 0:
        for _ in range(reps):
            w.send(chunk, peer, tag=11)
        w.recv(peer, tag=12)
        stream_gbps = reps * chunk.nbytes / (time.perf_counter()
                                             - t0) / 1e9
    else:
        for _ in range(reps):
            w.recv(0, tag=11)
        w.send(np.array([1]), 0, tag=12)
        stream_gbps = 0.0

    # BOTH 8 B rows carry the full control-plane breakdown (the
    # scalar and ndarray rows once disagreed by 8x on the record with
    # only one instrumented): marshal cost, btl wire RTT
    # (the pingpong row above), combine hits, and the MEASURED wakeup
    # schedule from the coalescing counters (docs/SMALLMSG.md) — not
    # the hardcoded rounds/wakeups claim the r5 record shipped.
    from ompi_tpu.btl.tcp import decode_payload as _dec
    from ompi_tpu.btl.tcp import encode_payload as _enc
    from ompi_tpu.runtime import progress as _prog
    from ompi_tpu.runtime import spc as _spc0

    def _marshal_us(payload, reps=300):
        if isinstance(payload, np.generic):
            # mirror send_small: numpy scalars ride the raw 0-d nd
            # encoding, not the pickle path
            payload = np.asarray(payload)
        t0 = time.perf_counter()
        for _ in range(reps):
            dsc, rw = _enc(payload)
            _dec(dsc, rw)
        return (time.perf_counter() - t0) / reps * 1e6

    def _row8(payload, iters=50):
        """One instrumented 8 B allreduce row: (us/call, breakdown)."""
        w.allreduce(payload, MPI.SUM)            # warm the caches
        ws0 = _prog.wake_stats()
        ch0 = _spc0.read("coll_small_combine")
        w.barrier()
        t0 = time.perf_counter()
        for _ in range(iters):
            w.allreduce(payload, MPI.SUM)
        us = (time.perf_counter() - t0) / iters * 1e6
        ws1 = _prog.wake_stats()
        wakes = ws1["wakeups"] - ws0["wakeups"]
        frames = ws1["frames"] - ws0["frames"]
        return us, {
            "marshal_us": round(_marshal_us(payload), 1),
            "btl_rtt_us": round(rtt_us, 1),
            "rounds": 1,
            "wakeups_per_call": round(wakes / iters, 2),
            "frames_per_wakeup": round(frames / max(wakes, 1), 2),
            "combine_hits": int(_spc0.read("coll_small_combine") - ch0),
        }

    allred_us, bd_scalar = _row8(np.float64(r))       # the 8x row
    small8 = np.full(2, float(r + 1), np.float32)     # 8 B payload
    allred8_nd_us, bd_nd = _row8(small8)

    # staged-device vs host-tier A/B at 8 MB: the
    # same numpy allreduce, once riding the staged XLA tier (default
    # threshold stages >=1 MB) and once forced onto the host p2p
    # algorithms — the row that proves C/host buffers reach the fabric.
    from ompi_tpu.mca import var as _var
    from ompi_tpu.runtime import spc as _spc

    def _timed(fn, reps=3):
        fn()                         # warm (compile on the staged leg)
        ts = []
        for _ in range(reps):
            w.barrier()
            t1 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t1)
        return float(np.median(ts))

    big = np.full((8 << 20) // 4, float(r + 1), np.float32)
    # the route the decision layer picks on its own (probe-earned
    # threshold) — measured BEFORE the forced legs
    # so the A/B var writes cannot contaminate it
    hits0 = _spc.read("coll_staged_device")
    routed_s = _timed(lambda: w.allreduce(big, MPI.SUM))
    routed_hits = _spc.read("coll_staged_device") - hits0
    from ompi_tpu.coll.tuned import probed_stage_basis as _psb
    stage_probe = dict(_psb())
    # forced legs for the A/B itself
    _var.var_set("coll_tuned_stage_min_bytes", 1 << 20)
    staged_s = _timed(lambda: w.allreduce(big, MPI.SUM))
    staged_hits = _spc.read("coll_staged_device") - hits0 - routed_hits
    _var.var_set("coll_tuned_stage_min_bytes", 1 << 62)
    host_s = _timed(lambda: w.allreduce(big, MPI.SUM))
    _var.var_set("coll_tuned_stage_min_bytes", 1 << 20)
    # the contract the round-4 record broke: the chosen route must be
    # the measurably faster side of its own A/B
    routed_to_staged = routed_hits > 0
    faster_is_staged = staged_s < host_s
    route_agrees = routed_to_staged == faster_is_staged

    # device pt2pt A/B at 16 MB: the same
    # jax.Array round-trip over the PJRT transfer plane (D2D
    # rendezvous pull) vs forced onto the host byte path. 16 MB: large
    # enough that transfer amortization dominates this 1-core box's
    # scheduler noise (4 MB results flip run-to-run here).
    import jax.numpy as jnp
    xdev = jnp.full((16 << 20) // 4, float(r), jnp.float32)

    def _pingpong_dev():
        if r == 0:
            w.send(xdev, 1, tag=21)
            y, _ = w.recv(1, tag=22)
        else:
            y, _ = w.recv(0, tag=21)
            w.send(xdev, 0, tag=22)
        np.asarray(y[:1])                # observe completion

    # host leg FIRST (so the transfer-plane connection warm-up can
    # never leak into the host number), 5 reps each: this box is
    # 1-core and scheduler noise at 3 reps flipped the comparison
    _var.var_set("btl_devxfer_min_bytes", 1 << 62)
    hostp_s = _timed(_pingpong_dev, reps=5)
    _var.var_set("btl_devxfer_min_bytes", 1 << 20)
    d2d_s = _timed(_pingpong_dev, reps=5)

    from ompi_tpu.runtime.init import _state
    stats = dict(_state["router"].endpoint.stats)
    ctl = dict(_state["router"].endpoint.tcp.ctl_stats)
    probe = dict(getattr(_state["router"].endpoint, "probe_basis", {}))
    w.barrier()
    MPI.Finalize()
    if r == 0:
        print(json.dumps({
            "pingpong_8B_rtt_us": round(rtt_us, 1),
            "stream_256KB_gbps": round(stream_gbps, 2),
            "allreduce_8B_us": round(allred_us, 1),
            "allreduce_8B_nd_us": round(allred8_nd_us, 1),
            "allreduce_8B_breakdown": bd_scalar,
            "allreduce_8B_nd_breakdown": bd_nd,
            "ctl_batching": ctl,
            "allreduce_8MB_staged_ms": round(staged_s * 1e3, 2),
            "allreduce_8MB_host_ms": round(host_s * 1e3, 2),
            "allreduce_8MB_routed_ms": round(routed_s * 1e3, 2),
            "routed_to_staged": bool(routed_to_staged),
            "route_agrees_with_ab": bool(route_agrees),
            "stage_probe": stage_probe,
            "staged_device_hits": int(staged_hits),
            "pt2pt_16MB_rtt_d2d_ms": round(d2d_s * 1e3, 2),
            "pt2pt_16MB_rtt_host_ms": round(hostp_s * 1e3, 2),
            "transports": stats,
            "btl_probe": probe,
        }), flush=True)


def _child_env() -> dict:
    """Environment for benchmark children: the parent's platform pins
    must not leak (children pick their own backend)."""
    return {k: v for k, v in os.environ.items()
            if not k.startswith(("JAX_", "XLA_"))}


def _child_json(cmd, timeout: int, env: dict) -> dict:
    """Run a child benchmark process and scrape its one JSON line
    (shared by the ab-matrix and per-rank children)."""
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=timeout,
            env=env, cwd=os.path.dirname(os.path.abspath(__file__)))
        last = [ln for ln in proc.stdout.splitlines()
                if ln.startswith("{")]
        return (json.loads(last[-1]) if last
                else {"error": (proc.stderr or "no output")[-300:]})
    except Exception as e:              # noqa: BLE001
        return {"error": f"{type(e).__name__}: {e}"}


def _perrank_rows() -> dict:
    """Launch two 2-process per-rank jobs — btl/sm enabled and
    disabled — and report both (the same-host transport A/B; real OS
    processes, so the numbers include genuine IPC)."""
    out = {}
    mpirun = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "ompi_tpu", "tools", "mpirun.py")
    for label, extra in (("sm", []), ("tcp_only",
                                      ["--mca", "btl_sm_enable", "0"])):
        out[label] = _child_json(
            [sys.executable, mpirun, "--per-rank", "-n", "2",
             "--timeout", "120", *extra,
             sys.executable, os.path.abspath(__file__),
             "--perrank-child"], 180, _child_env())
    return out


def _ab_matrix_child() -> None:
    """8-rank CPU-mesh A/B: per-algorithm allreduce timing at three
    sizes, plus the >1-rank OSU rows the single-chip parent cannot
    measure. Prints one JSON line."""
    import jax
    # the host mesh, never the chip the parent holds (same pin as
    # tests/conftest.py)
    jax.config.update("jax_platforms", "cpu")
    import ompi_tpu as MPI
    from ompi_tpu.mca import var

    MPI.Init()
    world = MPI.get_comm_world()
    n = world.size
    rtt = _measure_rtt()
    chunk = 10                  # bound unsynced depth on the host backend
    # (50 was still enough for 8-participant all_to_all rendezvous
    # threads to starve the shared CPU thread pool intermittently)
    out = {"ranks": n}

    sizes = {"1MB": 1 << 20, "8MB": 8 << 20, "32MB": 32 << 20}
    algs = ("direct", "ring", "ring_segmented", "rabenseifner")
    ab = {}
    for label, nbytes in sizes.items():
        x = world.alloc((nbytes // 4,), np.float32, fill=1.0)
        row = {}
        for alg in algs:
            var.var_set("coll_xla_allreduce_algorithm", alg)
            try:
                row[alg + "_ms"] = round(_osu(
                    lambda: world.allreduce(x, MPI.SUM), 5, rtt,
                    chunk) * 1e3, 3)
            except Exception as e:      # noqa: BLE001
                row[alg + "_error"] = f"{type(e).__name__}"
        ab[label] = row
    var.var_set("coll_xla_allreduce_algorithm", "auto")
    out["allreduce_ab"] = ab

    # Root-targeted vs symmetric alias (measure the delta):
    # reduce-to-root should beat allreduce on wire bytes at size.
    rx = world.alloc(((8 << 20) // 4,), np.float32, fill=1.0)
    rr = {}
    for alg in ("alias", "rabenseifner_root"):
        var.var_set("coll_xla_reduce_algorithm", alg)
        rr[alg + "_ms"] = round(_osu(
            lambda: world.reduce(rx, MPI.SUM, 0), 5, rtt, chunk) * 1e3, 3)
    var.var_set("coll_xla_reduce_algorithm", "auto")
    out["reduce_8MB_ab"] = rr

    # Round-3 registry breadth: each new
    # algorithm gets a measured row so the decision tables stay honest.
    bx = world.alloc(((1 << 20) // 4,), np.float32, fill=1.0)
    bsmall = world.alloc((2,), np.float32, fill=1.0)
    bc = {}
    for alg in ("direct", "binomial", "knomial", "chain", "pipeline",
                "scatter_allgather"):
        var.var_set("coll_xla_bcast_algorithm", alg)
        try:
            bc[alg + "_1MB_us"] = round(_osu(
                lambda: world.bcast(bx, 0), 10, rtt, chunk) * 1e6, 1)
            bc[alg + "_8B_us"] = round(_osu(
                lambda: world.bcast(bsmall, 0), 50, rtt, chunk) * 1e6, 1)
        except Exception as e:          # noqa: BLE001
            bc[alg + "_error"] = f"{type(e).__name__}"
    var.var_set("coll_xla_bcast_algorithm", "auto")
    out["bcast_ab"] = bc

    ag = {}
    for alg in ("direct", "ring", "bruck", "neighborexchange"):
        var.var_set("coll_xla_allgather_algorithm", alg)
        try:
            ag[alg + "_8B_us"] = round(_osu(
                lambda: world.allgather(bsmall), 50, rtt,
                chunk) * 1e6, 1)
        except Exception as e:          # noqa: BLE001
            ag[alg + "_error"] = f"{type(e).__name__}"
    var.var_set("coll_xla_allgather_algorithm", "auto")
    out["allgather_ab"] = ag

    br = {}
    for alg in ("direct", "dissemination", "tree"):
        var.var_set("coll_xla_barrier_algorithm", alg)
        try:
            bmod = world.c_coll["barrier"]
            bmod.device._barrier_tokens.clear()
            br[alg + "_us"] = round(_osu(
                lambda: bmod._ibarrier_arrays(), 50, rtt,
                chunk) * 1e6, 1)
        except Exception as e:          # noqa: BLE001
            br[alg + "_error"] = f"{type(e).__name__}"
    var.var_set("coll_xla_barrier_algorithm", "auto")
    out["barrier_ab"] = br

    kr = {}
    for alg in ("alias", "knomial", "in_order_binary"):
        var.var_set("coll_xla_reduce_algorithm", alg)
        try:
            kr[alg + "_8B_us"] = round(_osu(
                lambda: world.reduce(bsmall, MPI.SUM, 0), 50, rtt,
                chunk) * 1e6, 1)
        except Exception as e:          # noqa: BLE001
            kr[alg + "_error"] = f"{type(e).__name__}"
    var.var_set("coll_xla_reduce_algorithm", "auto")
    out["reduce_8B_ab"] = kr

    # Round-4 registry breadth: sparbit
    # allgather and butterfly reduce_scatter A/B rows.
    ag2 = {}
    for alg in ("direct", "bruck", "sparbit"):
        var.var_set("coll_xla_allgather_algorithm", alg)
        try:
            ag2[alg + "_64KB_us"] = round(_osu(
                lambda: world.allgather(world.alloc(
                    ((64 << 10) // 4,), np.float32, fill=1.0)),
                10, rtt, chunk) * 1e6, 1)
        except Exception as e:          # noqa: BLE001
            ag2[alg + "_error"] = f"{type(e).__name__}"
    var.var_set("coll_xla_allgather_algorithm", "auto")
    out["allgather_64KB_ab"] = ag2

    rsb = {}
    rsx = world.alloc((n, (1 << 20) // 4 // n), np.float32, fill=1.0)
    for alg in ("direct", "ring", "recursive_halving", "butterfly"):
        var.var_set("coll_xla_reduce_scatter_block_algorithm", alg)
        try:
            rsb[alg + "_1MB_us"] = round(_osu(
                lambda: world.reduce_scatter_block(rsx, MPI.SUM),
                10, rtt, chunk) * 1e6, 1)
        except Exception as e:          # noqa: BLE001
            rsb[alg + "_error"] = f"{type(e).__name__}"
    var.var_set("coll_xla_reduce_scatter_block_algorithm", "auto")
    out["reduce_scatter_1MB_ab"] = rsb

    # Segsize tuned from DATA: the sweep that set
    # the acoll cpu hint (segmented must beat plain ring somewhere)
    segs = {}
    var.var_set("coll_xla_allreduce_algorithm", "ring")
    x32 = world.alloc(((32 << 20) // 4,), np.float32, fill=1.0)
    try:
        segs["ring_ms"] = round(_osu(
            lambda: world.allreduce(x32, MPI.SUM), 3, rtt,
            chunk) * 1e3, 1)
        var.var_set("coll_xla_allreduce_algorithm", "ring_segmented")
        for seg in (1 << 20, 4 << 20):
            var.var_set("coll_xla_segsize", seg)
            segs[f"seg_{seg >> 20}MB_ms"] = round(_osu(
                lambda: world.allreduce(x32, MPI.SUM), 3, rtt,
                chunk) * 1e3, 1)
    except Exception as e:              # noqa: BLE001
        segs["error"] = f"{type(e).__name__}"
    var.var_set("coll_xla_allreduce_algorithm", "auto")
    var.var_set("coll_xla_segsize", 4 << 20)
    out["segsize_sweep_32MB"] = segs

    # NBC vs blocking measured the SAME way (an earlier
    # record compared apples to oranges): iallreduce@4MB next to blocking
    # direct@4MB under identical amortization.
    nbc = {}
    x4 = world.alloc(((4 << 20) // 4,), np.float32, fill=1.0)
    try:
        var.var_set("coll_xla_allreduce_algorithm", "direct")
        nbc["allreduce_direct_4MB_ms"] = round(_osu(
            lambda: world.allreduce(x4, MPI.SUM), 5, rtt,
            chunk) * 1e3, 2)
        var.var_set("coll_xla_allreduce_algorithm", "auto")

        def _iall():
            r = world.iallreduce(x4, MPI.SUM)
            r.wait()
            return r.get()
        nbc["iallreduce_4MB_ms"] = round(_osu(
            _iall, 5, rtt, chunk) * 1e3, 2)
    except Exception as e:              # noqa: BLE001
        nbc["error"] = f"{type(e).__name__}"
    var.var_set("coll_xla_allreduce_algorithm", "auto")
    out["nbc_vs_blocking_4MB"] = nbc

    # round-3 additions: bruck alltoall, recursive-halving
    # reduce_scatter, recursive-doubling scan
    a2a_s = world.alloc((n, 2), np.float32, fill=1.0)
    at = {}
    for alg in ("direct", "pairwise", "bruck"):
        var.var_set("coll_xla_alltoall_algorithm", alg)
        try:
            at[alg + "_8B_us"] = round(_osu(
                lambda: world.alltoall(a2a_s), 50, rtt, chunk) * 1e6, 1)
        except Exception as e:          # noqa: BLE001
            at[alg + "_error"] = f"{type(e).__name__}"
    var.var_set("coll_xla_alltoall_algorithm", "auto")
    out["alltoall_ab"] = at

    rs = {}
    for alg in ("direct", "ring", "recursive_halving"):
        var.var_set("coll_xla_reduce_scatter_block_algorithm", alg)
        try:
            rs[alg + "_8B_us"] = round(_osu(
                lambda: world.reduce_scatter_block(a2a_s, MPI.SUM), 50,
                rtt, chunk) * 1e6, 1)
        except Exception as e:          # noqa: BLE001
            rs[alg + "_error"] = f"{type(e).__name__}"
    var.var_set("coll_xla_reduce_scatter_block_algorithm", "auto")
    out["reduce_scatter_8B_ab"] = rs

    sc = {}
    for alg in ("direct", "recursive_doubling"):
        var.var_set("coll_xla_scan_algorithm", alg)
        try:
            sc[alg + "_8B_us"] = round(_osu(
                lambda: world.scan(bsmall, MPI.SUM), 50, rtt,
                chunk) * 1e6, 1)
        except Exception as e:          # noqa: BLE001
            sc[alg + "_error"] = f"{type(e).__name__}"
    var.var_set("coll_xla_scan_algorithm", "auto")
    out["scan_ab"] = sc

    # single-shot blocking rows next to the amortized ones —
    # un-amortized dispatch-to-completion, RTT included
    out["allreduce_8B_blocking_single_shot_us"] = round(
        _blocking(lambda: world.allreduce(bsmall, MPI.SUM)), 1)
    out["bcast_8B_blocking_single_shot_us"] = round(
        _blocking(lambda: world.bcast(bsmall, 0)), 1)

    small = world.alloc((2,), np.float32, fill=1.0)
    a2a = world.alloc((n, 2), np.float32, fill=1.0)
    out["osu_alltoall_8B_us"] = round(_osu(
        lambda: world.alltoall(a2a), 50, rtt, chunk) * 1e6, 2)
    out["osu_reduce_scatter_8B_us"] = round(_osu(
        lambda: world.reduce_scatter_block(a2a, MPI.SUM), 50, rtt,
        chunk) * 1e6, 2)
    sub = world.split([0] * (n // 2) + [1] * (n - n // 2))[0]
    if sub is not None:
        ssmall = sub.alloc((2,), np.float32, fill=1.0)
        out["osu_subcomm_allreduce_8B_us"] = round(_osu(
            lambda: sub.allreduce(ssmall, MPI.SUM), 50, rtt,
            chunk) * 1e6, 2)
    out["osu_allreduce_8B_us"] = round(_osu(
        lambda: world.allreduce(small, MPI.SUM), 100, rtt,
        chunk) * 1e6, 2)

    # BASELINE plan item 5: MPI_IN_PLACE and derived-datatype variants
    out["osu_allreduce_inplace_8B_us"] = round(_osu(
        lambda: world.allreduce(MPI.IN_PLACE, MPI.SUM, recvbuf=small),
        50, rtt, chunk) * 1e6, 2)
    vec = MPI.FLOAT.create_vector(count=4, blocklength=2, stride=4)
    # exact-fit buffer (last dim == count*extent = 14): the fused
    # gather->collective->scatter program serves it; other shapes keep
    # the convertor path (core/communicator.py shape contract)
    vbuf = world.alloc((14,), np.float32, fill=1.0)
    out["osu_allreduce_vector_dtype_us"] = round(_osu(
        lambda: world.allreduce(vbuf, MPI.SUM, datatype=vec, count=1),
        20, rtt, chunk) * 1e6, 2)
    try:
        out.update(_overlap_pct(world, MPI))
    except Exception as e:              # noqa: BLE001
        out["overlap_error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(out))
    MPI.Finalize()


def _compress_device_child() -> None:
    """8-rank CPU-mesh compressed-collective rows: >= 4 MB fp32
    allreduce, baseline (auto: fused psum) vs the compressed component
    per codec — wall time, pvar-accounted wire ratio, and measured max
    relative error vs the float64 reference. Prints one JSON line.

    Honest expectation on THIS transport: the host mesh moves bytes at
    memcpy speed, so the quantization arithmetic usually loses on wall
    time here — the row exists to pin the accuracy/ratio contract; the
    bandwidth win is measured where bytes are expensive (the per-rank
    wire child) and on real ICI/DCN fabrics."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import ompi_tpu as MPI
    from ompi_tpu.compress import codecs
    from ompi_tpu.mca import pvar, var

    MPI.Init()
    world = MPI.get_comm_world()
    n = world.size
    rtt = _measure_rtt()
    elems = 1 << 20                        # 4 MB fp32 per rank
    rng = np.random.default_rng(11)
    host = rng.normal(size=(n, elems)).astype(np.float32)
    ref = host.sum(axis=0, dtype=np.float64)
    scale = float(np.abs(ref).max())
    x = world.put(host)

    out = {"ranks": n, "payload_mb": elems * 4 / (1 << 20)}
    out["fp32_ms"] = round(_osu(
        lambda: world.allreduce(x, MPI.SUM), 5, rtt, 10) * 1e3, 3)

    var.var_set("mpi_base_compress", True)
    comp = world.dup()                     # selection sees the var
    try:
        for codec in codecs.codec_names():
            var.var_set("mpi_base_compress_codec", codec)
            row = {}
            bi0 = pvar.pvar_read("compress_bytes_in")
            bo0 = pvar.pvar_read("compress_bytes_out")
            y = np.asarray(comp.allreduce(x, MPI.SUM))   # compile+run
            row["ms"] = round(_osu(
                lambda: comp.allreduce(x, MPI.SUM), 5, rtt, 10)
                * 1e3, 3)
            bi = pvar.pvar_read("compress_bytes_in") - bi0
            bo = pvar.pvar_read("compress_bytes_out") - bo0
            row["wire_ratio"] = round(bo / bi, 4) if bi else None
            row["max_rel_err"] = round(
                float(np.abs(y[0].astype(np.float64) - ref).max())
                / scale, 6)
            out[codec] = row
    finally:
        var.var_set("mpi_base_compress_codec", "int8_block")
        var.var_set("mpi_base_compress", False)
        comp.free()
    MPI.Finalize()
    print(json.dumps(out), flush=True)


def _compress_perrank_child() -> None:
    """One rank of the 2-process wire A/B: a 4 MB fp32 allreduce over
    the host-tier binomial chains (staged device tier forced off), the
    SAME transport with compression off vs on. Effective bandwidth is
    logical payload bytes over wall time — the EQuARX metric: the
    quantized hops move ~0.25x the bytes, so on a byte-bound transport
    the effective bandwidth multiplies. Rank 0 prints one JSON line."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import ompi_tpu as MPI
    from ompi_tpu.mca import pvar, var

    MPI.Init()
    w = MPI.get_comm_world()
    r, n = w.rank(), w.size
    var.var_set("coll_tuned_stage_min_bytes", 1 << 62)  # host tier only

    elems = 1 << 20                        # 4 MB fp32 per rank
    rng = np.random.default_rng(13)        # same stream on every rank
    full = rng.normal(size=(n, elems)).astype(np.float32)
    mine = full[r].copy()
    ref = full.sum(axis=0, dtype=np.float64)
    scale = float(np.abs(ref).max())

    def _timed(reps=5):
        w.allreduce(mine, MPI.SUM)         # warm
        ts = []
        for _ in range(reps):
            w.barrier()
            t0 = time.perf_counter()
            w.allreduce(mine, MPI.SUM)
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    fp32_s = _timed()

    var.var_set("mpi_base_compress", True)
    var.var_set("mpi_base_compress_min_bytes", 1 << 20)
    bi0 = pvar.pvar_read("compress_bytes_in")
    bo0 = pvar.pvar_read("compress_bytes_out")
    y = w.allreduce(mine, MPI.SUM)
    err = float(np.abs(y.astype(np.float64) - ref).max())
    int8_s = _timed()
    bi = pvar.pvar_read("compress_bytes_in") - bi0
    bo = pvar.pvar_read("compress_bytes_out") - bo0
    var.var_set("mpi_base_compress", False)

    from ompi_tpu.runtime.init import _state
    transports = dict(_state["router"].endpoint.stats)
    w.barrier()
    MPI.Finalize()
    if r == 0:
        nbytes = elems * 4
        print(json.dumps({
            "payload_mb": nbytes / (1 << 20),
            "fp32_ms": round(fp32_s * 1e3, 2),
            "int8_ms": round(int8_s * 1e3, 2),
            "fp32_effective_gbps": round(nbytes / fp32_s / 1e9, 3),
            "int8_effective_gbps": round(nbytes / int8_s / 1e9, 3),
            "effective_bw_ratio": round(fp32_s / int8_s, 2),
            "wire_ratio": round(bo / bi, 4) if bi else None,
            "max_rel_err": round(err / scale, 6),
            "transports": transports,
        }), flush=True)


def _compress_rows() -> dict:
    """The --compress section: the 8-rank device-path rows plus the
    2-process wire A/B on three transports — sm rings and raw tcp
    (this host's loopback, honest even where compression only breaks
    even: loopback moves bytes at near-memcpy speed), and tcp paced to
    0.2 GB/s (``btl_tcp_sim_gbps`` — the DCN-like tier every real
    multi-host fabric presents, where the >= 1.5x effective-bandwidth
    contract is asserted)."""
    here = os.path.dirname(os.path.abspath(__file__))
    mpirun = os.path.join(here, "ompi_tpu", "tools", "mpirun.py")
    out = {"device_8rank": _child_json(
        [sys.executable, os.path.abspath(__file__),
         "--compress-device-child"], 600, _child_env())}
    for label, extra in (
            ("wire_sm", []),
            ("wire_tcp", ["--mca", "btl_sm_enable", "0"]),
            ("wire_dcn_sim", ["--mca", "btl_sm_enable", "0",
                              "--mca", "btl_tcp_sim_gbps", "0.2"])):
        out[label] = _child_json(
            [sys.executable, mpirun, "--per-rank", "-n", "2",
             "--timeout", "240", *extra,
             sys.executable, os.path.abspath(__file__),
             "--compress-child"], 300, _child_env())
    return out


def _pcoll_child() -> None:
    """One rank of the 2-process persistent/bucketed A/B job
    (docs/PERSISTENT.md): the 256 x 4 KiB many-small-allreduce
    workload — one-shot loop vs persistent plans vs bucketed
    persistent (``mpi_base_bucket``, Startall-fused) — with the
    bucketed leg's results byte-compared to the one-shot references
    and its wire-collective budget pvar-asserted. Rank 0 prints one
    JSON line."""
    import math

    import jax
    jax.config.update("jax_platforms", "cpu")
    import ompi_tpu as MPI
    from ompi_tpu.mca import pvar as _pvar
    from ompi_tpu.mca import var as _var

    MPI.Init()
    w = MPI.get_comm_world()
    r = w.rank()
    K, elems = 256, 1024                 # 256 x 4 KiB per rank
    bucket_bytes = 1 << 20
    bufs = [np.full(elems, float(r + i + 1), np.float32)
            for i in range(K)]
    refs = [np.asarray(w.allreduce(b, MPI.SUM)) for b in bufs]

    def timed(fn, reps=3):
        fn()                             # warm
        ts = []
        for _ in range(reps):
            w.barrier()
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    def oneshot():
        for b in bufs:
            w.allreduce(b, MPI.SUM)

    t_one = timed(oneshot)

    preqs = [w.allreduce_init(b, MPI.SUM) for b in bufs]

    def persist():
        for q in preqs:
            q.start()
        for q in preqs:
            q.wait()

    t_pers = timed(persist)

    _var.var_set("mpi_base_bucket", True)
    _var.var_set("mpi_base_bucket_bytes", bucket_bytes)
    breqs = [w.allreduce_init(b, MPI.SUM) for b in bufs]

    def bucketed():
        MPI.Startall(breqs)
        for q in breqs:
            q.wait()

    # correctness: the fused leg is byte-identical on integer-valued
    # f32 (elementwise combine is exact)
    bucketed()
    correct = all(np.asarray(q.get()).tobytes() == e.tobytes()
                  for q, e in zip(breqs, refs))
    f0 = _pvar.pvar_read("coll_bucket_flushes")
    reps = 3
    t_buck = timed(bucketed, reps)
    flushes = _pvar.pvar_read("coll_bucket_flushes") - f0
    _var.var_set("mpi_base_bucket", False)
    per_call = flushes / (reps + 1)      # warm + reps timed runs
    budget = math.ceil(K * elems * 4 / bucket_bytes)

    w.barrier()
    MPI.Finalize()
    if r == 0:
        print(json.dumps({
            "workload": f"{K}x{elems * 4 // 1024}KiB_allreduce",
            "oneshot_ms": round(t_one * 1e3, 2),
            "persistent_ms": round(t_pers * 1e3, 2),
            "bucketed_ms": round(t_buck * 1e3, 2),
            "speedup_persistent": round(t_one / t_pers, 2),
            "speedup_bucketed": round(t_one / t_buck, 2),
            "bucketed_correct": bool(correct),
            "wire_colls_per_call": round(per_call, 2),
            "wire_coll_budget": budget,
            "wire_budget_ok": bool(per_call <= budget),
        }), flush=True)


def _pcoll_rows() -> dict:
    """The --pcoll section: the many-small-allreduce A/B on both
    same-host transports (sm rings on, and tcp only) — real OS
    processes, genuine IPC."""
    here = os.path.dirname(os.path.abspath(__file__))
    mpirun = os.path.join(here, "ompi_tpu", "tools", "mpirun.py")
    out = {}
    for label, extra in (("sm", []), ("tcp_only",
                                      ["--mca", "btl_sm_enable", "0"])):
        out[label] = _child_json(
            [sys.executable, mpirun, "--per-rank", "-n", "2",
             "--timeout", "240", *extra,
             sys.executable, os.path.abspath(__file__),
             "--pcoll-child"], 300, _child_env())
    return out


def _largemsg_child() -> None:
    """One rank of the 2-process large-message A/B job
    (docs/LARGEMSG.md): a 64 MB f32 allreduce riding the segment-
    pipelined ring (chunk hops through the pml's pipelined rendezvous,
    striped over ``mpi_base_btl_rails``) against the serial
    reduce+bcast schedule, plus the chain-vs-binomial bcast pair —
    with the pipeline pvars read so the speedup row is EVIDENCED
    (segments actually flowed, overlap actually measured, rail bytes
    actually balanced), not inferred. Rank 0 prints one JSON line."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import ompi_tpu as MPI
    from ompi_tpu.mca import pvar as _pvar
    from ompi_tpu.mca import var as _var

    MPI.Init()
    w = MPI.get_comm_world()
    r = w.rank()
    # host tier only: the staging shim would swallow the payload
    _var.var_set("coll_tuned_stage_min_bytes", 1 << 62)
    mb = int(os.environ.get("OMPI_TPU_BENCH_LARGEMSG_MB", "64"))
    x = np.full((mb << 20) // 4, float(r + 1), np.float32)

    def timed(fn, reps=3):
        fn()                             # warm
        ts = []
        for _ in range(reps):
            w.barrier()
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    s0 = _pvar.pvar_read("pml_pipeline_segments")
    t_pipe = timed(lambda: w.allreduce(x, MPI.SUM))
    segments = int(_pvar.pvar_read("pml_pipeline_segments") - s0)
    overlap = float(_pvar.pvar_read("pml_overlap_ratio"))
    y = np.asarray(w.allreduce(x, MPI.SUM))
    correct = bool(y[0] == 3.0)          # (r=0)+1 + (r=1)+1
    _var.var_set("mpi_base_pipeline_enable", False)
    t_serial = timed(lambda: w.allreduce(x, MPI.SUM))
    _var.var_set("mpi_base_pipeline_enable", True)

    t_bchain = timed(lambda: w.bcast(x if r == 0 else None, 0))
    _var.var_set("mpi_base_pipeline_enable", False)
    t_bserial = timed(lambda: w.bcast(x if r == 0 else None, 0))
    _var.var_set("mpi_base_pipeline_enable", True)

    rails = int(_var.var_get("mpi_base_btl_rails", 1))
    rail_bytes = [int(_pvar.pvar_read(f"btl_rail_bytes_c{c}"))
                  for c in range(rails)]
    balanced = None
    if rails > 1:
        even = sum(rail_bytes) / rails
        balanced = bool(even > 0 and all(
            abs(b - even) <= 0.2 * even for b in rail_bytes))

    w.barrier()
    MPI.Finalize()
    if r == 0:
        print(json.dumps({
            "payload_mb": mb,
            "rails": rails,
            "allreduce_pipelined_ms": round(t_pipe * 1e3, 1),
            "allreduce_serial_ms": round(t_serial * 1e3, 1),
            "allreduce_speedup": round(t_serial / t_pipe, 2),
            "bcast_chain_ms": round(t_bchain * 1e3, 1),
            "bcast_serial_ms": round(t_bserial * 1e3, 1),
            "bcast_speedup": round(t_bserial / t_bchain, 2),
            "pipeline_segments": segments,
            "overlap_ratio": round(overlap, 3),
            "rail_bytes": rail_bytes,
            "rail_bytes_balanced": balanced,
            "correct": correct,
        }), flush=True)


def _largemsg_rows() -> dict:
    """The --largemsg section: pipelined-vs-serial A/B at 64 MB on
    the three transports (sm rings, raw tcp loopback, and tcp paced
    to 0.2 GB/s — the DCN-like tier where overlap actually pays), and
    rails 1-vs-2 on the tcp tiers (rail count binds at Init, so each
    rail count is its own job). The paced rails=2 job carries the
    acceptance contract: pipeline_speedup_paced >= 1.5 with
    pml_pipeline_segments > 1, and rail bytes within 20% of even."""
    here = os.path.dirname(os.path.abspath(__file__))
    mpirun = os.path.join(here, "ompi_tpu", "tools", "mpirun.py")
    out = {}
    for label, extra in (
            ("sm", []),
            ("tcp", ["--mca", "btl_sm_enable", "0"]),
            ("tcp_rails2", ["--mca", "btl_sm_enable", "0",
                            "--mca", "mpi_base_btl_rails", "2"]),
            ("paced", ["--mca", "btl_sm_enable", "0",
                       "--mca", "btl_tcp_sim_gbps", "0.2"]),
            ("paced_rails2", ["--mca", "btl_sm_enable", "0",
                              "--mca", "btl_tcp_sim_gbps", "0.2",
                              "--mca", "mpi_base_btl_rails", "2"])):
        out[label] = _child_json(
            [sys.executable, mpirun, "--per-rank", "-n", "2",
             "--timeout", "300", *extra,
             sys.executable, os.path.abspath(__file__),
             "--largemsg-child"], 360, _child_env())
    return out


def _shm_child() -> None:
    """One rank of the zero-copy shared-memory A/B job
    (docs/LARGEMSG.md): pt2pt one-way time rank0->rank1 at 1/8/32 MB
    and the 32 MB allreduce, each timed with the segment plane ON
    (single-copy adoption / in-segment fold) and OFF (the unchanged
    ring path) inside the same process — with the adoption and fold
    pvars read so the speedup rows are EVIDENCED (payloads actually
    rode the segments), not inferred. Rank 0 prints one JSON line."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import ompi_tpu as MPI
    from ompi_tpu.mca import pvar as _pvar
    from ompi_tpu.mca import var as _var

    MPI.Init()
    w = MPI.get_comm_world()
    r, n = w.rank(), w.size
    # host tier only: the staging shim would swallow the payload
    _var.var_set("coll_tuned_stage_min_bytes", 1 << 62)

    def pt2pt_ms(mb, zerocopy, reps=7):
        """Median one-way 0->1 transfer: send + 1-byte ack (the ack
        also paces the sender behind the receiver's slot frees)."""
        _var.var_set("mpi_base_shm_zerocopy", zerocopy)
        x = np.full((mb << 20) // 4, 1.0, np.float32)
        ts = []
        for i in range(reps + 1):        # first rep is the warm-up
            w.barrier()
            t0 = time.perf_counter()
            if r == 0:
                w.send(x, 1, 60)
                w.recv(1, 61)
            elif r == 1:
                y = np.asarray(w.recv(0, 60)[0])
                assert y[0] == 1.0 and y.nbytes == x.nbytes
                del y                    # drop the adoption: slot frees
                w.send(b"k", 0, 61)
            if r == 0 and i:
                ts.append(time.perf_counter() - t0)
        _var.var_set("mpi_base_shm_zerocopy", True)
        return float(np.median(ts)) * 1e3 if r == 0 else 0.0

    def allreduce_ms(mb, zerocopy, reps=5):
        _var.var_set("mpi_base_shm_zerocopy", zerocopy)
        x = np.full((mb << 20) // 4, float(r + 1), np.float32)
        y = np.asarray(w.allreduce(x, MPI.SUM))     # warm + verify
        assert y[0] == n * (n + 1) / 2, y[0]
        ts = []
        for _ in range(reps):
            w.barrier()
            t0 = time.perf_counter()
            w.allreduce(x, MPI.SUM)
            ts.append(time.perf_counter() - t0)
        _var.var_set("mpi_base_shm_zerocopy", True)
        return float(np.median(ts)) * 1e3

    a0 = _pvar.pvar_read("btl_shm_adoptions")
    f0 = _pvar.pvar_read("btl_shm_fold_ops")
    pt = {}
    for mb in (1, 8, 32):
        ring = pt2pt_ms(mb, False)
        zc = pt2pt_ms(mb, True)
        if r == 0:
            pt[f"{mb}MB"] = {
                "ring_ms": round(ring, 2),
                "zerocopy_ms": round(zc, 2),
                "speedup": round(ring / zc, 2) if zc else None,
                "zerocopy_gbps": round((mb * (1 << 20)) / (zc / 1e3)
                                       / 1e9, 2) if zc else None}

    ar_ring = allreduce_ms(32, False, reps=3)
    ar_zc = allreduce_ms(32, True, reps=3)

    # adoption evidence lives at the RECEIVER (rank 1); fold evidence
    # on every rank — gather both to the reporting rank
    counts = np.asarray(w.gather(np.array(
        [_pvar.pvar_read("btl_shm_adoptions") - a0,
         _pvar.pvar_read("btl_shm_fold_ops") - f0], np.int64), 0))
    w.barrier()
    MPI.Finalize()
    if r == 0:
        print(json.dumps({
            "ranks": n,
            "pt2pt": pt,
            "allreduce_32MB": {
                "ring_ms": round(ar_ring, 2),
                "zerocopy_ms": round(ar_zc, 2),
                "speedup": round(ar_ring / ar_zc, 2) if ar_zc else None},
            "adoptions": int(counts[:, 0].sum()),
            "fold_ops": int(counts[:, 1].sum()),
        }), flush=True)


def _shm_rows() -> dict:
    """The --shm section: segment plane ON vs OFF at 1/8/32 MB pt2pt
    and the 32 MB allreduce, on 2-rank and 8-rank per-rank jobs
    (docs/LARGEMSG.md). The 2-rank 32 MB pt2pt speedup (>= 3x) and the
    8-rank 32 MB allreduce speedup (>= 2x) carry the acceptance
    contract, evidenced by the adoption/fold pvar deltas."""
    here = os.path.dirname(os.path.abspath(__file__))
    mpirun = os.path.join(here, "ompi_tpu", "tools", "mpirun.py")
    out = {}
    for label, nr, to in (("2rank", 2, 420), ("8rank", 8, 600)):
        out[label] = _child_json(
            [sys.executable, mpirun, "--per-rank", "-n", str(nr),
             "--timeout", str(to - 60),
             sys.executable, os.path.abspath(__file__), "--shm-child"],
            to, _child_env())
    return out


def _rma_child() -> None:
    """One rank of the 4-process one-sided RMA A/B job (docs/RMA.md),
    windows on the osc/shm component: the 32 MB one-way Put against
    the two-sided wire path (Send/Recv with the segment plane OFF —
    the multi-copy ring; the zero-copy Send/Recv rides alongside for
    honesty), Win_fence against MPI_Barrier (the fence is an epoch
    transition plus that very barrier, so the contract bounds it at
    2x), and the 4-rank fenced accumulate fan-in verified against the
    numpy reference. The ``osc_puts`` pvar delta evidences that the
    Puts actually rode the window path. Rank 0 prints one JSON line."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import ompi_tpu as MPI
    from ompi_tpu.api import mpi as api
    from ompi_tpu.mca import pvar as _pvar
    from ompi_tpu.mca import var as _var

    MPI.Init()
    w = MPI.get_comm_world()
    r, n = w.rank(), w.size
    _var.var_set("coll_tuned_stage_min_bytes", 1 << 62)

    mb = 32
    elems = (mb << 20) // 4
    p0 = _pvar.pvar_read("osc_puts")
    win = api.Win_allocate(w, elems, np.float32, name="bench_rma",
                           force="shm")
    assert win.component == "shm", win.component
    win.fence()                          # one open fence epoch

    def put_ms(reps=7):
        """Median one-way 0->1: a Put is ONE memcpy into the target's
        mapped segment, complete on return (no ack leg to pay)."""
        x = np.full(elems, 1.0, np.float32)
        ts = []
        for i in range(reps + 1):        # first rep is the warm-up
            w.barrier()
            t0 = time.perf_counter()
            if r == 0:
                win.put(x, 1)
            if r == 0 and i:
                ts.append(time.perf_counter() - t0)
        if r == 1:
            assert win.local[0] == 1.0
        return float(np.median(ts)) * 1e3 if r == 0 else 0.0

    def sendrecv_ms(zerocopy, reps=7):
        """Median one-way 0->1 over the two-sided path (send + 1-byte
        ack, _shm_child's protocol), segment plane ON or OFF."""
        _var.var_set("mpi_base_shm_zerocopy", zerocopy)
        x = np.full(elems, 1.0, np.float32)
        ts = []
        for i in range(reps + 1):
            w.barrier()
            t0 = time.perf_counter()
            if r == 0:
                w.send(x, 1, 70)
                w.recv(1, 71)
            elif r == 1:
                y = np.asarray(w.recv(0, 70)[0])
                assert y.nbytes == x.nbytes
                del y
                w.send(b"k", 0, 71)
            if r == 0 and i:
                ts.append(time.perf_counter() - t0)
        _var.var_set("mpi_base_shm_zerocopy", True)
        return float(np.median(ts)) * 1e3 if r == 0 else 0.0

    def sync_ms(fn, reps=30):
        w.barrier()
        t0 = time.perf_counter()
        for _ in range(reps):
            fn()
        return (time.perf_counter() - t0) / reps * 1e3

    pm = put_ms()
    ring = sendrecv_ms(False)
    zc = sendrecv_ms(True)
    fence = sync_ms(win.fence)
    barrier = sync_ms(w.barrier)

    # 4-rank accumulate fan-in: everyone folds 4 MB into rank 0
    acc_elems = (4 << 20) // 4
    xr = np.full(acc_elems, float(r + 1), np.float32)
    win.local[:] = 0.0
    win.fence()
    w.barrier()
    t0 = time.perf_counter()
    win.accumulate(xr, 0, op="sum")
    win.fence()
    acc = (time.perf_counter() - t0) * 1e3
    acc_ok = bool(r != 0 or np.allclose(
        win.local[:acc_elems], n * (n + 1) / 2, rtol=1e-5))

    puts = np.asarray(w.gather(np.array(
        [_pvar.pvar_read("osc_puts") - p0], np.int64), 0))
    oks = np.asarray(w.gather(np.array([int(acc_ok)], np.int64), 0))
    win.free()
    w.barrier()
    MPI.Finalize()
    if r == 0:
        print(json.dumps({
            "ranks": n,
            "component": "shm",
            "put_32MB": {
                "put_ms": round(pm, 2),
                "sendrecv_ring_ms": round(ring, 2),
                "sendrecv_zerocopy_ms": round(zc, 2),
                "speedup_vs_ring": round(ring / pm, 2) if pm else None,
                "speedup_vs_zerocopy": round(zc / pm, 2)
                if pm else None,
                "put_gbps": round((mb * (1 << 20)) / (pm / 1e3) / 1e9,
                                  2) if pm else None},
            "sync": {
                "fence_ms": round(fence, 4),
                "barrier_ms": round(barrier, 4),
                "fence_vs_barrier": round(fence / barrier, 2)
                if barrier else None},
            "acc_fanin_4MB": {
                "ms": round(acc, 2),
                "correct": bool(oks.sum() == n)},
            "osc_puts": int(puts.sum()),
        }), flush=True)


def _rma_rows() -> dict:
    """The --rma section: one 4-rank per-rank job on the osc/shm
    component (docs/RMA.md). The 32 MB Put >= 3x the two-sided ring,
    Win_fence <= 2x MPI_Barrier, and the accumulate fan-in's numpy
    parity carry the acceptance contract, evidenced by the osc_puts
    pvar delta."""
    here = os.path.dirname(os.path.abspath(__file__))
    mpirun = os.path.join(here, "ompi_tpu", "tools", "mpirun.py")
    return {"4rank": _child_json(
        [sys.executable, mpirun, "--per-rank", "-n", "4",
         "--timeout", "360",
         sys.executable, os.path.abspath(__file__), "--rma-child"],
        420, _child_env())}


def _ft_child() -> None:
    """One rank of the 4-process resilience drill (docs/RESILIENCE.md):
    the heartbeat detector is on and ft/inject kills rank 2 at its 2nd
    crossing of the ``coll.allreduce`` point (both configured by the
    parent's --mca flags). The survivors measure the BENCH contract:
    detection latency under 2x the configured heartbeat timeout, and a
    post-shrink allreduce that matches the numpy reference — plus the
    revoke round-trip and BucketedGradSync's elastic continuation.
    Rank 0 (a survivor) prints one JSON line; the victim's exit code is
    invisible here because _child_json scrapes stdout, not rc."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import ompi_tpu as MPI
    from ompi_tpu.api import mpi as api
    from ompi_tpu.mca import pvar as _pvar
    from ompi_tpu.mca import var as _var
    from ompi_tpu.models.transformer import BucketedGradSync

    MPI.Init()
    w = MPI.get_comm_world()
    r, n = w.rank(), w.size
    victim = 2
    hb_timeout = float(_var.var_get("mpi_base_ft_hb_timeout", 0.8))
    api.Comm_set_errhandler(w, MPI.ERRORS_RETURN)
    w.barrier()

    grads = {"w": np.full(4, float(r)), "b": np.full(2, float(r))}
    sync = BucketedGradSync(w, grads)
    sync(grads)                          # healthy persistent-path step
    w.allreduce(np.arange(4.0))          # victim's point hit 1

    t_fault = time.monotonic()
    proc_failed = False
    try:
        api.Allreduce(w, np.ones(4))     # victim os._exit(137)s here
    except MPI.MPIError as e:
        proc_failed = e.error_class == MPI.ERR_PROC_FAILED
    # (the victim never reaches past the program point above)

    deadline = time.monotonic() + 15
    while w.get_failed() != [victim] and time.monotonic() < deadline:
        time.sleep(0.05)
    failed_seen = w.get_failed() == [victim]
    t_detect = time.monotonic() - t_fault

    if r == 0:
        MPI.MPIX_Comm_revoke(w)
    deadline = time.monotonic() + 10
    while not MPI.MPIX_Comm_is_revoked(w) \
            and time.monotonic() < deadline:
        time.sleep(0.02)
    revoked = MPI.MPIX_Comm_is_revoked(w)

    shrunk = MPI.MPIX_Comm_shrink(w)
    survivors = [k for k in range(n) if k != victim]
    shrink_size = shrunk.size
    y = np.asarray(shrunk.allreduce(np.full(3, float(r))))
    shrink_ok = (shrink_size == n - 1
                 and bool(np.allclose(y, float(sum(survivors)))))

    sync.shrink(shrunk)
    g2 = sync(grads)
    resume_ok = bool(np.allclose(
        g2["w"], sum(survivors) / len(survivors)))

    lat_us = float(_pvar.pvar_read("ft_detect_latency_us"))
    shrunk.barrier()
    shrunk.free()
    MPI.Finalize()
    if r == 0:
        print(json.dumps({
            "ranks": n,
            "victim": victim,
            "hb_timeout_s": hb_timeout,
            "proc_failed_raised": proc_failed,
            "failure_reported": failed_seen,
            "detect_latency_us": round(lat_us, 1),
            "detect_under_2x_timeout": bool(
                0 <= lat_us < 2 * hb_timeout * 1e6),
            "wall_to_membership_s": round(t_detect, 2),
            "revoke_propagated": revoked,
            "shrink_size": shrink_size,
            "shrink_allreduce_correct": shrink_ok,
            "gradsync_resumed": resume_ok,
        }), flush=True)
    # survivors skip interpreter teardown: once a rank has died jax's
    # coordination service aborts nondeterministically on exit, and the
    # JSON verdict is already on stdout. Rank 0 hosts the coordination
    # service and must outlive the other survivors (exiting first RSTs
    # their error-polling clients, which fatally terminate them).
    if r == 0:
        time.sleep(3)
    os._exit(0)


def _ft_rows() -> dict:
    """The --ft section: the 4-process kill drill under the real
    heartbeat detector (period 0.1 s / timeout 0.8 s / miss 3) with a
    deterministic ft/inject SIGKILL mid-collective. Carries the two
    resilience acceptance rows: ft_detect_under_2x_timeout and
    shrink_allreduce_correct."""
    here = os.path.dirname(os.path.abspath(__file__))
    mpirun = os.path.join(here, "ompi_tpu", "tools", "mpirun.py")
    return {"kill_drill": _child_json(
        [sys.executable, mpirun, "--per-rank", "-n", "4",
         "--timeout", "240",
         "--mca", "mpi_base_ft_hb_period", "0.1",
         "--mca", "mpi_base_ft_hb_timeout", "0.8",
         "--mca", "mpi_base_ft_hb_miss", "3",
         "--mca", "mpi_base_ft_inject", "1",
         "--mca", "mpi_base_ft_inject_kill",
         "rank=2,point=coll.allreduce,hit=2",
         sys.executable, os.path.abspath(__file__),
         "--ft-child"], 300, _child_env())}


def _lint_rows() -> dict:
    """The --lint section: time one full-tree mpilint pass (the static
    gate every tier-1 run pays through tests/test_lint_clean.py) and
    pin the <10 s wall-time contract the analyzer ships under
    (docs/ANALYSIS.md)."""
    from ompi_tpu.analyze import mpilint
    t0 = time.perf_counter()
    rep = mpilint.run_lint()
    dt = time.perf_counter() - t0
    return {
        "seconds": round(dt, 3),
        "under_10s": bool(dt < 10.0),
        "files": rep["files"],
        "rules": len(rep["rules"]),
        "findings": len(rep["findings"]),
        "baselined": len(rep["suppressed"]),
        "stale_baseline": len(rep["stale_baseline"]),
        "clean": bool(rep["ok"]),
    }


def _telemetry_child() -> None:
    """The telemetry overhead probe: 8 B allreduce latency on the
    8-rank stacked CPU mesh, with the plane armed (or not) by the
    parent's OMPI_TPU_MCA_mpi_base_telemetry env. Min-of-batches —
    each batch is an independent OSU loop and the best one is this
    configuration's floor — so host scheduling noise doesn't
    masquerade as plane overhead. Prints one JSON line."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import ompi_tpu as MPI
    from ompi_tpu import telemetry

    MPI.Init()
    world = MPI.get_comm_world()
    n = world.size
    on = bool(telemetry.active)
    rtt = _measure_rtt()
    x = world.alloc((2,), np.float32, fill=1.0)
    batches = [round(_osu(lambda: world.allreduce(x, MPI.SUM), 150,
                          rtt, 10) * 1e6, 3) for _ in range(8)]
    hits = 0
    if on:
        # evidence the histogram shim was actually in the path — an
        # accidentally unwrapped vtable would make the A/B vacuous
        hits = sum(h.snapshot()["count"]
                   for h in telemetry.histograms()
                   if h.name.startswith("tele_coll_allreduce"))
        assert hits > 0, "telemetry on but no coll samples recorded"
    MPI.Finalize()
    print(json.dumps({
        "telemetry": on,
        "ranks": n,
        "allreduce_8B_us": min(batches),
        "batches": batches,
        "coll_samples": hits,
    }), flush=True)


def _telemetry_rows() -> dict:
    """The --telemetry section (docs/OBSERVABILITY.md): (1) the
    overhead A/B — the 8-rank child's min-of-batches 8 B allreduce
    with the telemetry plane off vs on, pinning the <=3% contract row;
    (2) the acceptance drill — the p41 4-process job with a 200 ms
    injected pml delay at rank 1, whose healthy ranks must declare it,
    mpitop must elect it slow_rank, and the merged flight-recorder
    incident must name it critical."""
    import glob as _glob
    import shutil
    import tempfile
    here = os.path.dirname(os.path.abspath(__file__))
    out: dict = {}

    # interleaved off/on child PAIRS, compared pairwise: ambient load
    # on the shared CPU mesh drifts by far more than the plane's real
    # cost (~±100 us on a ~300 us call between children), so min-vs-min
    # across arms is corrupted the moment one arm catches a quiet
    # window the other didn't. Adjacent off/on children see similar
    # load — each pair is a matched A/B — and the MEDIAN pair ratio
    # rejects a pair whose halves ran under different conditions.
    pairs: list = []
    detail: dict = {}
    for _ in range(3):
        vals: dict = {}
        for label, flag in (("off", "0"), ("on", "1")):
            env = _child_env()
            env["OMPI_TPU_MCA_mpi_base_telemetry"] = flag
            job = _child_json(
                [sys.executable, os.path.abspath(__file__),
                 "--telemetry-child"], 300, env)
            detail[label] = job
            vals[label] = (job or {}).get("allreduce_8B_us")
        if vals.get("off") and vals.get("on"):
            pairs.append((vals["off"], vals["on"]))
    row: dict = {"off": detail.get("off"), "on": detail.get("on"),
                 "pairs_us": [[round(o, 1), round(n, 1)]
                              for o, n in pairs]}
    if pairs:
        ratios = sorted(n / o for o, n in pairs)
        med = ratios[len(ratios) // 2]
        row["pair_ratios"] = [round(r, 4) for r in ratios]
        row["overhead_pct"] = round((med - 1.0) * 100, 2)
        row["le_3pct"] = bool(med <= 1.03)
    out["overhead"] = row

    mpirun = os.path.join(here, "ompi_tpu", "tools", "mpirun.py")
    prog = os.path.join(here, "tests", "perrank_programs",
                        "p41_straggler.py")
    tmp = tempfile.mkdtemp(prefix="bench_telemetry_")
    try:
        env = _child_env()
        env["P41_OUT"] = tmp
        proc = subprocess.run(
            [sys.executable, mpirun, "--per-rank", "-n", "4",
             "--timeout", "150", prog],
            capture_output=True, text=True, timeout=200, env=env,
            cwd=here)
        drill: dict = {"rc": proc.returncode,
                       "ok_ranks":
                       proc.stdout.count("OK p41_straggler")}
        if proc.returncode == 0:
            from ompi_tpu.telemetry import flightrec
            from ompi_tpu.tools import mpitop
            snaps, _skipped = mpitop.load_snapshots(sorted(_glob.glob(
                os.path.join(tmp, "telemetry_*.json"))))
            summary = mpitop.summarize(snaps)
            row1 = next((r for r in summary["rows"]
                         if r["rank"] == 1), {})
            payloads = []
            for f in sorted(_glob.glob(
                    os.path.join(tmp, "flightrec_*.json"))):
                with open(f) as fh:
                    payloads.append(json.load(fh))
            report = flightrec.merge(payloads)
            drill.update({
                "slow_rank": summary["slow_rank"],
                "declared": summary["declared"],
                "rank1_p99_us": max(row1.get("send_p99_us") or 0,
                                    row1.get("coll_p99_us") or 0),
                "mpitop_names_rank1": summary["slow_rank"] == 1,
                "flightrec_critical_rank": report["critical_rank"],
                "flightrec_names_rank1": report["critical_rank"] == 1,
            })
        else:
            drill["error"] = (proc.stderr or "no output")[-300:]
        out["straggler_drill"] = drill
    except Exception as e:              # noqa: BLE001
        out["straggler_drill"] = {"error": f"{type(e).__name__}: {e}"}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def _trace_summary() -> dict:
    """Trace summary for the committed BENCH record, proven
    machine-readable: the summary must round-trip through JSON
    bit-identically (the archive's consumers parse these records —
    a float NaN or tuple key here would silently rot the record)."""
    from ompi_tpu import trace
    from ompi_tpu.trace import attribution
    summary = attribution.summarize(trace.spans(), trace.stats())
    rt = json.loads(json.dumps(summary))
    assert rt == summary, "trace summary does not round-trip JSON"
    return summary


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--size-mb", type=float, default=256.0)
    ap.add_argument("--iters", type=int, default=20,
                    help="large-message amortization count")
    ap.add_argument("--lat-iters", type=int, default=1000,
                    help="small-message amortization count")
    ap.add_argument("--no-ab", action="store_true",
                    help="skip the benchmark child processes (the 8-rank "
                         "CPU-mesh A/B matrix AND the 2-process per-rank "
                         "transport rows)")
    ap.add_argument("--ab-child", action="store_true")
    ap.add_argument("--perrank-child", action="store_true")
    ap.add_argument("--compress", action="store_true",
                    help="measure the compressed-collective rows "
                         "(8-rank device path + 2-process wire A/B; "
                         "docs/COMPRESSION.md)")
    ap.add_argument("--compress-child", action="store_true")
    ap.add_argument("--compress-device-child", action="store_true")
    ap.add_argument("--pcoll", action="store_true",
                    help="measure the persistent/bucketed-collective "
                         "rows: the 256 x 4 KiB many-small-allreduce "
                         "A/B on sm and tcp per-rank jobs "
                         "(docs/PERSISTENT.md)")
    ap.add_argument("--pcoll-child", action="store_true")
    ap.add_argument("--largemsg", action="store_true",
                    help="measure the large-message data-plane rows: "
                         "the 64 MB pipelined-vs-serial allreduce/"
                         "bcast A/B with rails 1 vs 2 on sm, tcp, and "
                         "the paced tier (docs/LARGEMSG.md)")
    ap.add_argument("--largemsg-child", action="store_true")
    ap.add_argument("--shm", action="store_true",
                    help="measure the zero-copy shared-memory rows: "
                         "segment plane vs ring A/B at 1/8/32 MB "
                         "pt2pt + the 32 MB allreduce fold on 2- and "
                         "8-rank per-rank jobs (docs/LARGEMSG.md)")
    ap.add_argument("--shm-child", action="store_true")
    ap.add_argument("--rma", action="store_true",
                    help="measure the one-sided RMA rows: 32 MB Put "
                         "vs Send/Recv, Win_fence vs MPI_Barrier, and "
                         "the 4-rank accumulate fan-in on an osc/shm "
                         "per-rank job (docs/RMA.md)")
    ap.add_argument("--rma-child", action="store_true")
    ap.add_argument("--ft", action="store_true",
                    help="run the resilience drill: 4-process kill "
                         "drill under the heartbeat detector — "
                         "detection latency, revoke, shrink, elastic "
                         "continuation (docs/RESILIENCE.md)")
    ap.add_argument("--ft-child", action="store_true")
    ap.add_argument("--lint", action="store_true",
                    help="time one full-tree mpilint pass and record "
                         "the <10 s static-gate contract row "
                         "(docs/ANALYSIS.md)")
    ap.add_argument("--trace", action="store_true",
                    help="record collective/pt2pt spans "
                         "(ompi_tpu.trace) and attach the trace "
                         "summary to the committed BENCH record")
    ap.add_argument("--telemetry", action="store_true",
                    help="measure the telemetry-plane rows: the "
                         "on-vs-off 8 B allreduce overhead A/B "
                         "(<=3%% contract) and the 4-process "
                         "injected-straggler drill "
                         "(docs/OBSERVABILITY.md)")
    ap.add_argument("--telemetry-child", action="store_true")
    args = ap.parse_args()

    if args.perrank_child:
        _perrank_child()
        return
    if args.ab_child:
        _ab_matrix_child()
        return
    if args.compress_child:
        _compress_perrank_child()
        return
    if args.compress_device_child:
        _compress_device_child()
        return
    if args.pcoll_child:
        _pcoll_child()
        return
    if args.largemsg_child:
        _largemsg_child()
        return
    if args.shm_child:
        _shm_child()
        return
    if args.rma_child:
        _rma_child()
        return
    if args.ft_child:
        _ft_child()
        return
    if args.telemetry_child:
        _telemetry_child()
        return

    import jax
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        # a caller's explicit host run: assert the pin through the
        # config too (jax read the env at import)
        jax.config.update("jax_platforms", "cpu")
    elif jax.devices()[0].platform != "tpu":
        sys.exit(f"bench: no TPU found ({jax.devices()[0].platform}); "
                 "set JAX_PLATFORMS=cpu to run on the host on purpose")
    import ompi_tpu as MPI
    from ompi_tpu.accelerator import to_device, to_host
    from ompi_tpu.runtime.init import compile_cache_dir
    compile_cache_dir()

    if args.trace:
        # before Init: the coll composer wraps vtables at communicator
        # construction, so enabling later would miss collective spans
        from ompi_tpu import trace as _trace_mod
        _trace_mod.enable()

    MPI.Init()
    world = MPI.get_comm_world()
    n = world.size
    platform = world.devices[0].platform
    if platform == "cpu" and args.size_mb > 64:
        args.size_mb = 64.0                    # keep CI-host runs sane
    if platform == "cpu":
        args.lat_iters = min(args.lat_iters, 300)
    chunk = 10 if platform == "cpu" else 0   # bound unsynced host depth

    rtt = _measure_rtt()

    def staged_allreduce(buf):
        host = to_host(buf)                          # D2H
        red = host.sum(axis=0, dtype=np.float32)     # host CPU reduction
        out = np.broadcast_to(red, host.shape)
        return to_device(np.ascontiguousarray(out), world.sharding)  # H2D

    def _staged_time(buf, iters):
        _fetch(staged_allreduce(buf))        # warm: exclude first-touch
        ts = []                              # transfer-path setup
        for _ in range(iters):
            t0 = time.perf_counter()
            _fetch(staged_allreduce(buf))
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    # ---- headline: 8 B latency --------------------------------------
    small = world.alloc((2,), np.float32, fill=1.0)  # 8 B per rank
    lat_native_s = _osu(lambda: world.allreduce(small, MPI.SUM),
                        args.lat_iters, rtt, chunk)
    lat_staged_s = _staged_time(small, 5)

    # single-shot blocking latency: one call, full completion
    # observation, NO amortization — what a lone MPI_Allreduce costs on
    # this transport (inherits the observation RTT by definition)
    blocking_us = _blocking(
        lambda: world.allreduce(small, MPI.SUM), reps=5)

    # framework-controlled cost: dispatch with no completion wait
    # (bounded by the same unsynced-depth limit as _osu on the host
    # backend)
    disp_iters = 200 if not chunk else chunk
    world.allreduce(small, MPI.SUM)
    best = None
    for _ in range(4):
        t0 = time.perf_counter()
        for _ in range(disp_iters):
            world.allreduce(small, MPI.SUM)
        dt = (time.perf_counter() - t0) / disp_iters * 1e6
        best = dt if best is None else min(best, dt)
        _fetch(world.allreduce(small, MPI.SUM))      # drain the queue
    dispatch_us = best

    # pre-bound persistent-collective handle (allreduce_bind): the
    # per-call floor — jax compiled dispatch + one sharding identity
    # check; everything else hoisted out
    bound = world.allreduce_bind(small, MPI.SUM)
    bound(small)
    best_b = None
    for _ in range(4):
        t0 = time.perf_counter()
        for _ in range(disp_iters):
            bound(small)
        dt = (time.perf_counter() - t0) / disp_iters * 1e6
        best_b = dt if best_b is None else min(best_b, dt)
        _fetch(bound(small))
    dispatch_bound_us = best_b

    # MPI-4 persistent Start through the pre-bound plan
    # (coll/persistent; the round's tentpole contract: Start-to-
    # dispatch <= 1/3 of the one-shot dispatch path). Methodology
    # mirrors the dispatch_only loop — back-to-back launch-only
    # starts, one completion observation per batch; the request is
    # re-armed between launches by marking the batch's inner
    # dispatches complete (their device results drain at the
    # batch-end fetch, exactly like the unsynced one-shot loop).
    from ompi_tpu.mca import pvar as _pvar_mod
    preq = world.allreduce_init(small, MPI.SUM)
    preq.start()
    preq.wait()
    ps0 = _pvar_mod.pvar_read("coll_persistent_starts")
    best_p = None
    ps_iters = 0
    for _ in range(4):
        t0 = time.perf_counter()
        for _ in range(disp_iters):
            preq.start()
            preq._complete = True        # launch-only re-arm
        dt = (time.perf_counter() - t0) / disp_iters * 1e6
        best_p = dt if best_p is None else min(best_p, dt)
        ps_iters += disp_iters
        _fetch(preq._result)             # drain the batch (direct
        #                                  plans park output here)
    persistent_start_us = best_p
    # pvar-asserted: every loop iteration took the persistent path
    persistent_pvar_ok = (
        _pvar_mod.pvar_read("coll_persistent_starts") - ps0 == ps_iters)

    # ---- OSU small-message matrix -----------------------------------
    lat2 = max(100, args.lat_iters // 2)
    osu = {}

    try:
        osu["osu_bcast_8B_us"] = round(_osu(
            lambda: world.bcast(small, 0), lat2, rtt, chunk) * 1e6, 2)
        osu["osu_bcast_blocking_single_shot_us"] = round(
            _blocking(lambda: world.bcast(small, 0)), 2)
        osu["osu_reduce_blocking_single_shot_us"] = round(
            _blocking(lambda: world.reduce(small, MPI.SUM, 0)), 2)
        osu["osu_allgather_8B_us"] = round(_osu(
            lambda: world.allgather(small), lat2, rtt, chunk) * 1e6, 2)
        osu["osu_reduce_8B_us"] = round(_osu(
            lambda: world.reduce(small, MPI.SUM, 0), lat2, rtt,
            chunk) * 1e6, 2)
        if n > 1:
            a2a = world.alloc((n, 2), np.float32, fill=1.0)
            osu["osu_alltoall_8B_us"] = round(_osu(
                lambda: world.alltoall(a2a), lat2, rtt, chunk) * 1e6, 2)
            osu["osu_reduce_scatter_8B_us"] = round(_osu(
                lambda: world.reduce_scatter_block(a2a, MPI.SUM),
                lat2, rtt, chunk) * 1e6, 2)
            sub = world.split([0] * (n // 2) + [1] * (n - n // 2))[0]
            if sub is not None:
                ss = sub.alloc((2,), np.float32, fill=1.0)
                osu["osu_subcomm_allreduce_8B_us"] = round(_osu(
                    lambda: sub.allreduce(ss, MPI.SUM), lat2, rtt,
                    chunk) * 1e6, 2)

        # Engineered barrier: pre-staged token +
        # pre-compiled executable; amortized dispatch-to-completion on
        # the same methodology as every other row.
        bmod = world.c_coll["barrier"]
        osu["osu_barrier_us"] = round(_osu(
            lambda: bmod._ibarrier_arrays(), lat2, rtt, chunk) * 1e6, 2)
        # single-shot blocking barrier: inherits one full observation
        # round-trip per call by definition (reported, not amortized)
        world.barrier()
        t0 = time.perf_counter()
        for _ in range(3):
            world.barrier()
        osu["osu_barrier_blocking_us"] = round(
            (time.perf_counter() - t0) / 3 * 1e6, 2)
    except Exception as e:              # noqa: BLE001 — report partial
        osu["osu_matrix_error"] = f"{type(e).__name__}: {e}"

    # ---- nonblocking overlap (osu_iallreduce) ------
    # Only meaningful with real schedule rounds (n > 1); on the
    # single-chip run the 8-rank CPU-mesh child reports it.
    if n > 1:
        try:
            osu.update(_overlap_pct(world, MPI))
        except Exception as e:          # noqa: BLE001
            osu["overlap_error"] = f"{type(e).__name__}: {e}"

    # ---- large-message bandwidth ------------------------------------
    elems = int(args.size_mb * (1 << 20) // 4)
    bytes_per_rank = elems * 4
    x = world.alloc((elems,), np.float32, fill=1.0)
    t0 = time.perf_counter()
    y = world.allreduce(x, MPI.SUM)
    _fetch(y)
    warmup_s = time.perf_counter() - t0
    big_native_s = _osu(lambda: world.allreduce(x, MPI.SUM),
                        args.iters, rtt, min(chunk, 10) if chunk else 0)
    big_staged_s = _staged_time(x, 1)

    algbw = bytes_per_rank / big_native_s / 1e9
    busbw = algbw * (2 * (n - 1) / n) if n > 1 else 0.0
    correct = bool(np.asarray(y[0, :1])[0] == float(n))

    # ---- 8-rank CPU-mesh A/B + multi-rank rows (single-chip runs) ---
    ab = None
    if n == 1 and not args.no_ab:
        ab = _child_json(
            [sys.executable, os.path.abspath(__file__), "--ab-child"],
            600, _child_env())

    # ---- per-rank transport rows (2 real OS processes, btl A/B) -----
    perrank = _perrank_rows() if (n == 1 and not args.no_ab) else None

    # ---- compressed-collective rows (--compress) --------------------
    compress_rows = _compress_rows() if args.compress else None

    # ---- persistent/bucketed rows (--pcoll) -------------------------
    pcoll_rows = _pcoll_rows() if (args.pcoll and n == 1
                                   and not args.no_ab) else None

    # ---- large-message pipeline/rail rows (--largemsg) --------------
    largemsg_rows = _largemsg_rows() if (args.largemsg and n == 1
                                         and not args.no_ab) else None

    # ---- zero-copy shared-memory rows (--shm) -----------------------
    # explicit opt-in like --ft: the A/B toggling happens inside the
    # children, not through this process's config
    shm_rows = _shm_rows() if (args.shm and n == 1) else None

    # ---- one-sided RMA rows (--rma) ---------------------------------
    # explicit opt-in like --shm: the A/B lives in the 4-rank child
    rma_rows = _rma_rows() if (args.rma and n == 1) else None

    # ---- resilience-plane drill rows (--ft) -------------------------
    # explicit opt-in flag, so --no-ab (which skips the implicit
    # children) does not gate it
    ft_rows = _ft_rows() if (args.ft and n == 1) else None

    # ---- static-gate timing row (--lint) ----------------------------
    lint_rows = _lint_rows() if args.lint else None

    # ---- telemetry-plane rows (--telemetry) -------------------------
    # explicit opt-in like --ft: its children pick their own config
    telemetry_rows = _telemetry_rows() if (args.telemetry
                                           and n == 1) else None

    result = {
        # throughput-derived: amortized pipelined dispatch minus the
        # observation RTT (the OSU loop), NOT a single-shot latency —
        # that's the *_blocking_single_shot row next to it (the
        # amortized metric is named for what it is)
        "metric": "allreduce_8B_throughput_derived_us",
        "value": round(lat_native_s * 1e6, 2),
        "unit": "us",
        "vs_baseline": round(lat_staged_s / lat_native_s, 2),
        "allreduce_8B_blocking_single_shot_us": round(blocking_us, 2),
        "ranks": n,
        "platform": platform,
        "device_kind": world.devices[0].device_kind,
        "observe_rtt_ms": round(rtt * 1e3, 2),
        "dispatch_only_8B_us": round(dispatch_us, 2),
        "dispatch_bound_8B_us": round(dispatch_bound_us, 2),
        # persistent Start through the pre-bound plan (coll/persistent)
        "persistent_start_8B_us": round(persistent_start_us, 2),
        # the framework-controlled Start residue: total Start cost
        # minus the compiled-dispatch floor (dispatch_bound, the
        # per-call cost the framework cannot go below — both paths pay
        # it). The tentpole contract compares this residue against
        # the one-shot dispatch path.
        "persistent_start_overhead_us": round(
            max(persistent_start_us - dispatch_bound_us, 0.0), 2),
        "persistent_vs_dispatch": round(
            persistent_start_us / max(dispatch_us, 1e-9), 3),
        "persistent_start_le_third": bool(
            max(persistent_start_us - dispatch_bound_us, 0.0)
            <= dispatch_us / 3),
        "persistent_starts_pvar_ok": bool(persistent_pvar_ok),
        "staged_p50_8B_us": round(lat_staged_s * 1e6, 2),
        "large_msg_mb": int(args.size_mb),
        "large_algbw_gbps": round(algbw, 2),
        "large_busbw_gbps": round(busbw, 2),
        "large_native_ms": round(big_native_s * 1e3, 3),
        "large_staged_ms": round(big_staged_s * 1e3, 3),
        "warmup_compile_s": round(warmup_s, 3),
        "correct": correct,
        **osu,
        **({"ab_matrix": ab} if ab is not None else {}),
        **({"perrank": perrank} if perrank is not None else {}),
        **({"compress": compress_rows}
           if compress_rows is not None else {}),
        **({"pcoll": pcoll_rows} if pcoll_rows is not None else {}),
        **({"largemsg": largemsg_rows}
           if largemsg_rows is not None else {}),
        **({"shm": shm_rows} if shm_rows is not None else {}),
        **({"rma": rma_rows} if rma_rows is not None else {}),
        **({"ft": ft_rows} if ft_rows is not None else {}),
        **({"lint": lint_rows} if lint_rows is not None else {}),
        **({"telemetry": telemetry_rows}
           if telemetry_rows is not None else {}),
        "caveat": ("size-1 world: large-message path is identity-aliased "
                   "by XLA (algbw is an upper bound); >1-rank rows and "
                   "algorithm A/B come from the 8-rank CPU-mesh child"
                   if n == 1 else ""),
    }

    if args.trace:
        result["trace"] = _trace_summary()

    print(json.dumps(result))
    # The archive must not depend on the driver's stdout tail window
    # (round-5 postmortem: the ab_matrix, overlap diagnosis, and
    # per-rank rows all fell off the 2000-char tail): persist the FULL
    # result object to a committed BENCHFULL_rNN.json next to the
    # BENCH_rNN.json the driver writes.
    try:
        result["benchfull"] = _write_benchfull(result)
    except OSError as e:
        result["benchfull_error"] = str(e)
    # Compact headline as the FINAL stdout line (round-3 postmortem:
    # the full line above outgrew the driver's tail window and the run
    # of record lost its own headline — BENCH_r03.json parsed: null).
    # Everything the archive must never lose, in <= 500 bytes; the
    # CONTRACT rows (per-job route-vs-A/B agreement, both 8 B rows
    # with their wakeup schedule, the A/B winners) now live here
    # rather than in the droppable body.
    headline = {
        "metric": result["metric"],
        "value": result["value"],
        "unit": result["unit"],
        "vs_baseline": result["vs_baseline"],
        "blocking_8B_us": result["allreduce_8B_blocking_single_shot_us"],
        "dispatch_8B_us": result["dispatch_only_8B_us"],
        "persistent_8B_us": result["persistent_start_8B_us"],
        "persistent_le_third": result["persistent_start_le_third"],
        "large_algbw_gbps": result["large_algbw_gbps"],
        "large_busbw_gbps": result["large_busbw_gbps"],
        "large_msg_mb": result["large_msg_mb"],
        "ranks": result["ranks"],
        "platform": result["platform"],
        "correct": result["correct"],
    }
    contract = _contract_rows(ab, perrank)
    if largemsg_rows is not None:
        # the large-message acceptance rows (docs/LARGEMSG.md): the
        # paced-tier pipelined-vs-serial speedup with its pvar
        # evidence, and the rails=2 byte balance
        pj = largemsg_rows.get("paced") or {}
        pr2 = largemsg_rows.get("paced_rails2") or {}
        if isinstance(pj, dict) and "error" not in pj:
            contract["pipeline_speedup_paced"] = pj.get(
                "allreduce_speedup")
            contract["pipeline_segments"] = pj.get("pipeline_segments")
        if isinstance(pr2, dict) and "error" not in pr2:
            contract["rail_bytes_balanced"] = pr2.get(
                "rail_bytes_balanced")
        # regression gate with the --largemsg section (docs/LARGEMSG.md
        # r12 diagnosis): the round's algbw must hold the newest
        # committed headline's within 10%
        prev = _prev_headline_algbw()
        if prev is not None:
            contract["algbw_no_worse_than_prev"] = {
                "now": result["large_algbw_gbps"], "prev": prev,
                "ok": bool(result["large_algbw_gbps"] >= 0.9 * prev)}
    if shm_rows is not None:
        # the zero-copy acceptance rows (docs/LARGEMSG.md): 2-rank
        # 32 MB pt2pt >= 3x the ring, 8-rank 32 MB allreduce fold
        # >= 2x, both pvar-evidenced (adoptions/folds actually ran)
        j2 = shm_rows.get("2rank") or {}
        j8 = shm_rows.get("8rank") or {}
        if isinstance(j2, dict) and "error" not in j2:
            contract["shm_pt2pt_32m_speedup"] = (
                (j2.get("pt2pt") or {}).get("32MB") or {}).get("speedup")
            contract["shm_adoptions"] = j2.get("adoptions")
        if isinstance(j8, dict) and "error" not in j8:
            contract["shm_allreduce_32m_speedup"] = (
                j8.get("allreduce_32MB") or {}).get("speedup")
            contract["shm_fold_ops"] = j8.get("fold_ops")
    if rma_rows is not None:
        # the one-sided acceptance rows (docs/RMA.md): 32 MB Put >= 3x
        # the two-sided ring, Win_fence <= 2x MPI_Barrier, accumulate
        # fan-in numpy-correct — osc_puts pvar-evidenced
        j4 = rma_rows.get("4rank") or {}
        if isinstance(j4, dict) and "error" not in j4:
            contract["rma_put_32m_speedup"] = (
                j4.get("put_32MB") or {}).get("speedup_vs_ring")
            contract["rma_fence_vs_barrier"] = (
                j4.get("sync") or {}).get("fence_vs_barrier")
            contract["rma_acc_fanin_correct"] = (
                j4.get("acc_fanin_4MB") or {}).get("correct")
            contract["rma_osc_puts"] = j4.get("osc_puts")
    if ft_rows is not None:
        # the resilience acceptance rows (docs/RESILIENCE.md): the
        # heartbeat detector's latency bound and the post-shrink
        # collective's correctness, measured in the 4-process kill
        # drill
        kd = ft_rows.get("kill_drill") or {}
        if isinstance(kd, dict) and "error" not in kd:
            contract["ft_detect_under_2x_timeout"] = kd.get(
                "detect_under_2x_timeout")
            contract["shrink_allreduce_correct"] = kd.get(
                "shrink_allreduce_correct")
    if lint_rows is not None:
        # the static-gate acceptance rows (docs/ANALYSIS.md): the
        # shipped tree lints clean and the full pass stays under the
        # 10 s budget tier-1 pays on every run
        contract["lint_clean"] = lint_rows["clean"]
        contract["lint_under_10s"] = lint_rows["under_10s"]
        contract["lint_seconds"] = lint_rows["seconds"]
    if telemetry_rows is not None:
        # the telemetry acceptance rows (docs/OBSERVABILITY.md): the
        # plane's 8 B allreduce cost stays within 3% of off, and the
        # injected-straggler drill's export path names the slow rank
        ov = telemetry_rows.get("overhead") or {}
        contract["telemetry_overhead_le_3pct"] = ov.get("le_3pct")
        contract["telemetry_overhead_pct"] = ov.get("overhead_pct")
        sd = telemetry_rows.get("straggler_drill") or {}
        contract["telemetry_names_straggler"] = bool(
            sd.get("mpitop_names_rank1")
            and sd.get("flightrec_names_rank1"))
    prev_algbw = _prev_headline_algbw()
    if prev_algbw is not None:
        # regression gate: this round's single-process large-message
        # algbw must not fall below the newest committed headline's
        contract["algbw_vs_prev"] = {
            "now": result["large_algbw_gbps"], "prev": prev_algbw,
            "ok": bool(result["large_algbw_gbps"] >= 0.9 * prev_algbw)}
    if contract:
        headline["contract"] = contract
    if pcoll_rows is not None:
        # the persistent/bucketed acceptance rows: many-small-allreduce
        # speedups per transport + the wire-collective budget
        headline["pcoll"] = {
            lbl: {"one_ms": (job or {}).get("oneshot_ms"),
                  "pers_x": (job or {}).get("speedup_persistent"),
                  "buck_x": (job or {}).get("speedup_bucketed"),
                  "wire_ok": (job or {}).get("wire_budget_ok")}
            for lbl, job in pcoll_rows.items()
            if isinstance(job, dict) and "error" not in job}
    if compress_rows is not None:
        # the compact compression contract: wire ratio + effective-
        # bandwidth multiple on both the raw loopback (honest: near
        # break-even where bytes are memcpy-cheap) and the paced
        # DCN-like tier (the >= 1.5x claim), device-path accuracy
        # (full rows live in the body / BENCHFULL)
        wt = compress_rows.get("wire_tcp", {}) or {}
        wd = compress_rows.get("wire_dcn_sim", {}) or {}
        d8 = (compress_rows.get("device_8rank", {}) or {}) \
            .get("int8_block", {}) or {}
        headline["compress"] = {
            "bw_ratio_tcp": wt.get("effective_bw_ratio"),
            "bw_ratio_dcn_sim": wd.get("effective_bw_ratio"),
            "wire_ratio": wd.get("wire_ratio") or wt.get("wire_ratio"),
            "rel_err_wire": wd.get("max_rel_err"),
            "rel_err_dev": d8.get("max_rel_err"),
        }
    # hard <=500-byte promise to the driver, kept by dropping the
    # least irreplaceable keys first (everything dropped here still
    # lives in BENCHFULL_rNN.json); the contract rows go LAST — they
    # are the evidence a truncated line would lose
    line = json.dumps(headline)
    for drop in ("large_busbw_gbps",
                 "large_msg_mb", ("contract", "ab_win"),
                 ("contract", "wpc"), "contract"):
        if len(line) <= 500:
            break
        if isinstance(drop, tuple):
            headline.get(drop[0], {}).pop(drop[1], None)
        else:
            headline.pop(drop, None)
        line = json.dumps(headline)
    if len(line) > 500:
        line = json.dumps({k: headline[k] for k in
                           ("metric", "value", "unit", "vs_baseline",
                            "platform", "correct")
                           if k in headline})
    print(line)
    MPI.Finalize()


def _prev_headline_algbw():
    """large_algbw_gbps from the newest committed BENCH_rNN.json — the
    regression-gate baseline (r08: 0.75). None when no prior round has
    the row (the gate is advisory, never run-killing)."""
    import glob
    import re
    here = os.path.dirname(os.path.abspath(__file__))
    rounds = sorted(
        ((int(m.group(1)), f) for f in glob.glob(
            os.path.join(here, "BENCH_r*.json"))
         if (m := re.search(r"BENCH_r(\d+)\.json$", f))), reverse=True)
    for _, f in rounds:
        try:
            with open(f) as fh:
                v = (json.load(fh) or {}).get("large_algbw_gbps")
            if v is not None:
                return float(v)
        except (OSError, ValueError, json.JSONDecodeError):
            continue
    return None


def _bench_round() -> int:
    """This run's round number: one past the newest BENCH_rNN.json the
    driver has archived."""
    import glob
    import re
    here = os.path.dirname(os.path.abspath(__file__))
    rounds = [int(m.group(1)) for f in glob.glob(
        os.path.join(here, "BENCH_r*.json"))
        if (m := re.search(r"BENCH_r(\d+)\.json$", f))]
    return (max(rounds) + 1) if rounds else 0


def _write_benchfull(result: dict) -> str:
    name = f"BENCHFULL_r{_bench_round():02d}.json"
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(result, f, indent=1)
        f.write("\n")
    os.replace(tmp, path)
    return name


def _contract_rows(ab, perrank) -> dict:
    """The rows that prove (or break) the round's contracts, compacted
    for the headline: A/B winners per size, each per-rank job's
    route-vs-A/B agreement, and both 8 B rows with the measured
    wakeup schedule."""
    contract = {}
    try:
        if ab and isinstance(ab.get("allreduce_ab"), dict):
            win = {}
            for size, row in ab["allreduce_ab"].items():
                timed = {k[:-3]: v for k, v in row.items()
                         if k.endswith("_ms")}
                if timed:
                    win[size] = min(timed, key=timed.get)
            if win:
                contract["ab_win"] = win
        if perrank:
            r8, route_ok, wpc = {}, {}, {}
            for label, job in perrank.items():
                if not isinstance(job, dict) or "error" in job:
                    continue
                label = "tcp" if label == "tcp_only" else label
                r8[label] = [job.get("allreduce_8B_us"),
                             job.get("allreduce_8B_nd_us")]
                route_ok[label] = job.get("route_agrees_with_ab")
                bd = job.get("allreduce_8B_nd_breakdown") or {}
                wpc[label] = bd.get("wakeups_per_call")
            if r8:
                contract["r8"] = r8
                contract["route_ok"] = route_ok
                contract["wpc"] = wpc
    except Exception:                   # noqa: BLE001 — the contract
        pass                            # block must never kill the run
    return contract


if __name__ == "__main__":
    main()
