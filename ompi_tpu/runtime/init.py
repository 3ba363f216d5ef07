"""ompi_mpi_init / finalize — world bring-up.

Behavioral spec: ``ompi/runtime/ompi_mpi_init.c:397`` through
``ompi/instance/instance.c:361-720``: OPAL up -> PMIx/coordination init ->
peer table -> transport selection -> modex/fence -> COMM_WORLD/SELF
creation -> per-communicator coll selection.

TPU-native re-design: the "transport" is the ICI mesh itself, reached
only through XLA; wire-up collapses to PJRT device enumeration. On a
multi-host deployment ``jax.distributed.initialize`` (the JAX
coordination service: distributed KV + barrier) stands in for PMIx
modex/fence — controlled here by MCA vars; single-host needs none. MPI
ranks bind 1:1 to mesh devices at init, exactly the north-star
requirement (rank topology bound to the device mesh).
"""
from __future__ import annotations

import os
import socket
import time
from typing import List, Optional

import jax

from ompi_tpu.core.communicator import Communicator
from ompi_tpu.core.errhandler import MPIError, ERR_OTHER
from ompi_tpu.core.group import Group
from ompi_tpu.core.info import INFO_ENV
from ompi_tpu.mca import var

THREAD_SINGLE = 0
THREAD_FUNNELED = 1
THREAD_SERIALIZED = 2
THREAD_MULTIPLE = 3

_state = {
    "initialized": False,
    "finalized": False,
    "world": None,
    "self": None,
    "thread_level": THREAD_SINGLE,
    "t0": 0.0,
}

# the parent-job intercommunicator of a spawned world (MPI_Comm_spawn
# child side); None in a directly-launched job
_parent_intercomm = None


def _register_base_vars() -> None:
    var.var_register("mpi", "base", "num_ranks", vtype="int", default=0,
                     help="Number of MPI ranks (0 = one per local device)")
    var.var_register("mpi", "base", "distributed", vtype="bool", default=False,
                     help="Call jax.distributed.initialize (multi-host "
                          "coordination service, the PMIx equivalent)")
    var.var_register("mpi", "base", "coordinator", vtype="str", default="",
                     help="coordinator_address for jax.distributed")
    var.var_register("mpi", "base", "process_id", vtype="int", default=-1,
                     help="process_id for jax.distributed (-1 = from env)")
    var.var_register("mpi", "base", "num_processes", vtype="int", default=0,
                     help="num_processes for jax.distributed (0 = from env)")
    var.var_register("mpi", "base", "per_rank", vtype="bool", default=False,
                     help="Per-rank execution model: one OS process == "
                          "one MPI rank (rank() == jax.process_index()); "
                          "pt2pt over btl/tcp, collectives over XLA or "
                          "textbook p2p algorithms")


def assert_platform_pin() -> None:
    """Re-assert the JAX_PLATFORMS env pin through jax.config before any
    backend use. jax reads the env once, at import: a program (or the C
    ABI's embedded interpreter) that imported jax before the launcher's
    pin reached os.environ would otherwise bring up the chip, which one
    process already holds, instead of the host platform its rank was
    given. Called by EVERY init tier (world init here, the Init-free
    Sessions model in runtime/session.py, and the C ABI through
    both)."""
    plat = os.environ.get("JAX_PLATFORMS")
    if plat:
        jax.config.update("jax_platforms", plat)


def compile_cache_dir() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. A directory already given — ``JAX_COMPILATION_CACHE_DIR``
    or the program's own ``jax_compilation_cache_dir`` — wins untouched;
    otherwise the cache is ``.jax_cache/`` in the checkout, a fixed path
    (the path is part of the cache key) that .gitignore lists. Called
    by chip_smoke.py before its first compile; ``Init``
    leaves the process's cache configuration to the program."""
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or jax.config.jax_compilation_cache_dir)
    if not path:
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def init(requested: int = THREAD_SINGLE,
         devices: Optional[List] = None) -> int:
    """MPI_Init / MPI_Init_thread. Returns the provided thread level."""
    if _state["initialized"]:
        raise MPIError(ERR_OTHER, "MPI already initialized")
    assert_platform_pin()
    _register_base_vars()
    # arm the lock-order witness BEFORE transport/progress bring-up so
    # endpoint locks are created wrapped; off = threading.Lock untouched
    from ompi_tpu.analyze import lockwitness as _lockwitness
    _lockwitness.maybe_install_from_var()
    from ompi_tpu.pml import stacked as _pml_stacked  # noqa: F401
    # (imports register the pml MCA vars — components register at open,
    # mca_base convention)

    if var.var_get("mpi_base_distributed", False):
        kw = {}
        coord = var.var_get("mpi_base_coordinator", "")
        if coord:
            kw["coordinator_address"] = coord
        pid = var.var_get("mpi_base_process_id", -1)
        if pid >= 0:
            kw["process_id"] = pid
        nproc = var.var_get("mpi_base_num_processes", 0)
        if nproc > 0:
            kw["num_processes"] = nproc
        # CPU backend needs a cross-process collectives transport
        # (the DCN tier the reference reaches via btl/tcp); gloo is
        # jax's host implementation. Harmless on TPU, where ICI/DCN
        # collectives are native.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(**kw)       # PMIx-equivalent wire-up

    # arm the tracer when the MCA var (env/param-file) asks for it —
    # BEFORE any communicator exists, so the coll composer sees it and
    # wraps every vtable (docs/OBSERVABILITY.md)
    from ompi_tpu import trace
    trace.maybe_enable_from_var()
    # same timing contract for the telemetry plane (histogram pvars,
    # health monitor, flight recorder): armed before the composers run
    from ompi_tpu import telemetry
    telemetry.maybe_enable_from_var()

    if var.var_get("mpi_base_per_rank", False):
        return _init_per_rank(requested)

    if devices is None:
        devices = list(jax.devices())
        nr = var.var_get("mpi_base_num_ranks", 0)
        if nr and nr <= len(devices):
            devices = devices[:nr]
    n = len(devices)

    world = Communicator(Group(range(n)), devices, name="MPI_COMM_WORLD")
    self_comm = Communicator(Group([0]), [devices[0]], name="MPI_COMM_SELF")

    INFO_ENV.set("command", os.environ.get("_", ""))
    INFO_ENV.set("maxprocs", str(n))
    INFO_ENV.set("soft", str(n))
    INFO_ENV.set("host", socket.gethostname())
    INFO_ENV.set("arch", jax.devices()[0].platform)

    _state.update(initialized=True, finalized=False, world=world,
                  self=self_comm, t0=time.perf_counter(),
                  thread_level=min(requested, THREAD_MULTIPLE))
    return _state["thread_level"]


def _kv_client():
    """The coordination-service KV store (PMIx modex equivalent)."""
    from jax._src import distributed
    client = distributed.global_state.client
    if client is None:
        raise MPIError(ERR_OTHER,
                       "per-rank mode requires jax.distributed "
                       "(set mpi_base_distributed or launch via "
                       "mpirun --per-rank)")
    return client


def _init_per_rank(requested: int) -> int:
    """Per-rank world bring-up: rank() == jax.process_index(), one
    COMM_WORLD member per process, pt2pt endpoints modex'd through the
    coordination-service KV (the reference's add_procs + modex steps,
    instance.c:508-569)."""
    from ompi_tpu.core.group import Group
    from ompi_tpu.core.rankcomm import RankCommunicator
    from ompi_tpu.pml.perrank import Router

    client = _kv_client()
    rank = jax.process_index()
    nprocs = jax.process_count()
    # every span this process records carries its world rank — the
    # exporter's pid and the attribution layer's participant identity
    from ompi_tpu import trace
    trace.set_process_rank(rank)
    router = Router(rank, nprocs, client.key_value_set,
                    lambda k: client.blocking_key_value_get(k, 120_000))
    world = RankCommunicator(Group(range(nprocs)), rank, router,
                             cid="w", name="MPI_COMM_WORLD")
    self_comm = RankCommunicator(Group([rank]), rank, router,
                                 cid=("self", rank),
                                 name="MPI_COMM_SELF")
    # init fence (ompi_mpi_init.c:434-447): nobody proceeds until every
    # rank's endpoint is published; then wire every pair eagerly
    # (add_procs — also completes the failure detector's coverage).
    client.wait_at_barrier("ompi_tpu_init", 120_000)
    router.wire_up()

    # Ring heartbeat failure detector (ft/detector, docs/RESILIENCE.md):
    # off unless mpi_base_ft_hb_period > 0. Heartbeats ride the
    # UNSEQUENCED tcp ctl path — they must not consume _sq slots the
    # ordered data plane accounts for, and a wedged peer's frames
    # mustn't queue behind data. Started AFTER wire_up so the first
    # check tick finds identified connections, not connect storms.
    from ompi_tpu.ft.detector import Detector
    from ompi_tpu.runtime import ft as _ftreg

    def _send_hb(peer: int, _r=router) -> None:
        hb = {"ctl": "hb", "peer": _r.rank}
        from ompi_tpu import telemetry as _tele
        if _tele.active:
            # RTT stamp, only while telemetry is on — the receiver
            # echoes it back as "hbr" (pml/perrank Router); with the
            # plane off the frame is byte-identical to the seed's
            hb["ht"] = time.perf_counter()
        _r.endpoint.tcp.send_frame(peer, hb)

    det = Detector(rank, nprocs, _send_hb, _ftreg.default_registry())
    det.departed = lambda r, _r=router: r in _r._departed
    if det.start():
        router.detector = det

    # telemetry plane per-rank wiring (docs/OBSERVABILITY.md): the
    # straggler health monitor samples from the progress loop and the
    # pml recv ingress; the flight recorder listens for proc failures
    from ompi_tpu import telemetry as _telemetry
    if _telemetry.active:
        from ompi_tpu.telemetry import flightrec as _flightrec
        from ompi_tpu.telemetry import health as _health
        _health.install(rank, nprocs)
        _flightrec.arm(rank)

    # Staged-tier threshold modex: the staging
    # switch point is probe-earned, but the probe is timing-based and
    # the staging decision must be rank-symmetric — so rank 0 measures
    # and publishes; every rank adopts the SAME value. A user-set
    # coll_tuned_stage_min_bytes suppresses the probe (checked inside
    # stage_min_for too; the skip here just avoids the measurement).
    from ompi_tpu.coll import tuned as _tuned
    if not var.var_overridden("coll_tuned_stage_min_bytes"):
        import json as _json
        key = "ompi_tpu/coll/stage_probe"
        if rank == 0:
            try:
                pb = dict(getattr(router.endpoint, "probe_basis",
                                  {}) or {})
                g = None
                if pb.get("ran"):
                    g = (pb.get("sm_gbps") if not pb.get("sm_demoted")
                         else pb.get("tcp_gbps"))
                if not g:
                    # routing probe suppressed (sm disabled or user-set
                    # btl_sm_min_bytes) — the tcp half still measured
                    # the wire, and a host tier modeled with NO
                    # transport cost routed 8 MB against its own A/B
                    # (the r08 tcp route_ok break)
                    g = pb.get("rail_gbps")
                bps = g * 1e9 if g else None
                value, basis = _tuned.staging_probe(
                    transport_bps=bps, nranks=nprocs)
            except Exception:            # noqa: BLE001 — advisory
                value, basis = 1 << 20, {"ran": False, "error": True}
            client.key_value_set(key, _json.dumps({"v": value, **basis}))
        blob = client.blocking_key_value_get(key, 120_000)
        if isinstance(blob, bytes):
            blob = blob.decode()
        d = _json.loads(blob)
        _tuned.adopt_probed_stage_min(int(d.pop("v")), d)

    INFO_ENV.set("command", os.environ.get("_", ""))
    INFO_ENV.set("maxprocs", str(nprocs))
    INFO_ENV.set("host", socket.gethostname())
    INFO_ENV.set("arch", jax.devices()[0].platform)

    _state.update(initialized=True, finalized=False, world=world,
                  self=self_comm, router=router, t0=time.perf_counter(),
                  thread_level=min(requested, THREAD_MULTIPLE))

    # Spawned world: dial back to the parent job through the dpm port
    # plane (MPI_Comm_spawn's PMIx parent-nspace handshake over this
    # runtime's coordination plane); MPI_Comm_get_parent returns the
    # resulting intercommunicator (dpm.c:108-170, comm_get_parent
    # .c.in).
    parent_port = os.environ.get("OMPI_TPU_PARENT_PORT")
    if parent_port:
        from ompi_tpu.core import dpm_perrank as _dpm
        global _parent_intercomm
        _parent_intercomm = _dpm.comm_connect(parent_port, world,
                                              root=0)
    return _state["thread_level"]


def finalize() -> None:
    if not _state["initialized"] or _state["finalized"]:
        raise MPIError(ERR_OTHER, "MPI not initialized or already finalized")
    # Drain async work so "all communication is complete at finalize".
    # With known-dead peers the drain barrier can never complete (a
    # live peer may itself be blocked on the dead one): skip it.
    from ompi_tpu.runtime import ft as _ftmod
    try:
        w = _state["world"]
        if w is not None and not w._freed and not _ftmod.any_failed():
            w.barrier()
    except Exception:
        pass
    # telemetry teardown first: the health monitor's progress callback
    # and the flight recorder's registry listener must not outlive the
    # world they observe
    from ompi_tpu import telemetry as _telemetry
    try:
        _telemetry.shutdown()
    except Exception:                # noqa: BLE001
        pass
    router = _state.pop("router", None)
    if router is not None:
        router.begin_shutdown()      # later EOFs are teardown, not death
        from ompi_tpu.runtime import ft as _ft
        if not _ft.any_failed():     # a dead rank can never reach the
            try:                     # fini fence; survivors skip it
                _kv_client().wait_at_barrier("ompi_tpu_fini", 120_000)
            except Exception:
                pass
        router.close()
        # drop the device-transfer plane with the router: connections,
        # the server, and any unpulled registrations (a stale server
        # address must never leak into a later job's modex)
        from ompi_tpu.btl import devxfer
        devxfer.reset()
    _state["finalized"] = True
    _state["world"] = None
    _state["self"] = None


def initialized() -> bool:
    return _state["initialized"]


def finalized() -> bool:
    return _state["finalized"]


def query_thread() -> int:
    return _state["thread_level"]


def comm_world() -> Communicator:
    if not _state["initialized"] or _state["finalized"]:
        raise MPIError(ERR_OTHER, "MPI is not active (call Init first)")
    return _state["world"]


def comm_self() -> Communicator:
    if not _state["initialized"] or _state["finalized"]:
        raise MPIError(ERR_OTHER, "MPI is not active (call Init first)")
    return _state["self"]


def wtime() -> float:
    return time.perf_counter()


def wtick() -> float:
    return 1e-9


def processor_name() -> str:
    d = jax.devices()[0]
    return f"{socket.gethostname()}/{d.platform}:{d.id}"


def _reset_for_tests() -> None:
    _state.update(initialized=False, finalized=False, world=None, self=None)
    from ompi_tpu.runtime import ft
    ft._reset_for_tests()
