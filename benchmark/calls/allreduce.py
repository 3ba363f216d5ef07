"""MPI_Allreduce on COMM_WORLD as a user gets it: ``MPI.Init()``, the
default component selection, stacked device buffers (one shard per rank
device), out of place. The window drives ``Communicator.allreduce``;
the reference sums every rank's input on the host."""
from __future__ import annotations

import numpy as np

from benchmark import data


class CheckError(AssertionError):
    """An output that cannot be read as the call's result."""


def setup(MPI, config):
    if not MPI.Initialized():
        MPI.Init()
    world = MPI.get_comm_world()
    if world.size != config["ranks"]:
        raise RuntimeError(f"COMM_WORLD has {world.size} ranks, the "
                           f"configuration {config['ranks']}")
    return world


def make_entries(MPI, comm, phase, seed: int, salt: int):
    """Args of each entry: entry e runs case e mod len(cases)."""
    cases = phase["cases"]
    specs, ops = [], []
    for _ in range(phase["pool"]):
        for case in cases:
            elems = (phase["bytes_per_rank"]
                     // data.dtype(case["dtype"]).itemsize)
            specs.append(((comm.size, elems), case["dtype"], case["amax"]))
            ops.append(getattr(MPI, case["op"].upper()))
    bufs = data.make(seed, salt, specs, comm.sharding)
    return [(x, op) for x, op in zip(bufs, ops)]


def function(MPI, comm):
    return comm.allreduce


def inputs(args):
    return np.asarray(args[0])


def output(comm, y) -> np.ndarray:
    """Every rank's row of the result: one shard on each rank's
    device, holding that rank's row."""
    where = {s.device: s.index[0].start or 0 for s in y.addressable_shards}
    if where != {d: r for r, d in enumerate(comm.devices)}:
        raise CheckError(f"result shards {where}")
    return np.asarray(y)


_NP = {"sum": np.sum, "max": np.max, "min": np.min, "prod": np.prod}


def reference(host_input, case) -> np.ndarray:
    """Each rank's expected row: the op over the ranks, in float64
    (exact for the traffic's integer data)."""
    return _NP[case["op"]](host_input, axis=0, dtype=np.float64)


def roofline_bytes(phase, n: int):
    """What any allreduce algorithm must send from each chip:
    2 (n - 1) / n of the buffer (reduce-scatter then all-gather), over
    the chip's ICI peak."""
    return 2 * (n - 1) / n * phase["bytes_per_rank"], "ici_bytes_per_s"


def served(MPI, comm) -> str:
    """Which component and which coll/xla algorithm served each shape
    (read from the executables coll/xla compiled)."""
    from ompi_tpu.coll.tuned import TunedCollModule
    mod = comm.c_coll["allreduce"]
    names = [comm._coll_winners.get("allreduce", "?")]
    if isinstance(mod, TunedCollModule):
        mod, names = mod.device, names + ["device -> xla"]
    algs = sorted({f"{k[1]} {k[2]} {k[5]}" for k in
                   getattr(mod, "_cache", {}) if k[0] == "allreduce"})
    return f"allreduce by {' '.join(names)}; algorithms: {algs}"
