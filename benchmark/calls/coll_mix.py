"""MPI_Allgather, MPI_Alltoall, MPI_Bcast and MPI_Reduce_scatter_block on
COMM_WORLD as a user gets it: ``MPI.Init()``, the default component
selection, stacked device buffers (one shard per rank device), out of
place. One phase per collective, named after it; each entry carries its
collective's name and the window's function dispatches on it.

Allgather, alltoall and bcast move data and take no op: their phases
carry the configuration's one (sum, float32) pair for its type, and the
call ignores the op. The reference is plain numpy on the host copies of
the inputs, the SUM in float64; it is the configuration's own copy,
independent of the library."""
from __future__ import annotations

import numpy as np

from benchmark import data
from benchmark.calls.allreduce import output, setup  # noqa: F401

ROOT = 0                       # bcast's root, OSU's default
# the collectives whose send buffer is one block per peer: (N, N, S/N)
BLOCKED = ("alltoall", "reduce_scatter_block")


def make_entries(MPI, comm, phase, seed: int, salt: int):
    """Args of each entry, ``(x, op, collective)``: entry e runs case
    e mod len(cases). ``x`` is each rank's whole send buffer of
    ``bytes_per_rank``: (N, elems) for allgather and bcast, (N, N,
    elems / N) for alltoall and reduce_scatter_block."""
    coll, n = phase["name"], comm.size
    specs, ops = [], []
    for _ in range(phase["pool"]):
        for case in phase["cases"]:
            elems = (phase["bytes_per_rank"]
                     // data.dtype(case["dtype"]).itemsize)
            shape = (n, n, elems // n) if coll in BLOCKED else (n, elems)
            specs.append((shape, case["dtype"], case["amax"]))
            ops.append(getattr(MPI, case["op"].upper()))
    bufs = data.make(seed, salt, specs, comm.sharding)
    return [(x, op, coll) for x, op in zip(bufs, ops)]


def function(MPI, comm):
    run = {"allgather": lambda x, op: comm.allgather(x),
           "alltoall": lambda x, op: comm.alltoall(x),
           "bcast": lambda x, op: comm.bcast(x, ROOT),
           "reduce_scatter_block": comm.reduce_scatter_block}
    return lambda x, op, coll: run[coll](x, op)


def inputs(args):
    return np.asarray(args[0]), args[2]


def reference(host_inputs, case) -> np.ndarray:
    """Every rank's expected result, stacked: allgather gives each rank
    all N rows, alltoall ``out[j, i] = in[i, j]``, bcast the root's row
    on every rank, reduce_scatter_block ``out[r] = sum_i in[i, r]``."""
    x, coll = host_inputs
    if coll == "allgather":
        return np.broadcast_to(x[None], (len(x),) + x.shape)
    if coll == "alltoall":
        return x.swapaxes(0, 1)
    if coll == "bcast":
        return np.broadcast_to(x[ROOT], x.shape)
    return np.sum(x, axis=0, dtype=np.float64)


def roofline_bytes(phase, n: int):
    """The least bytes any algorithm must bring into each chip over
    ICI, S being a rank's send buffer: allgather the other ranks'
    (n - 1) S, alltoall and reduce_scatter_block the other ranks' blocks
    (n - 1) / n S, bcast the whole S (a non-root receives it all)."""
    s = phase["bytes_per_rank"]
    least = {"allgather": (n - 1) * s, "alltoall": (n - 1) / n * s,
             "bcast": s, "reduce_scatter_block": (n - 1) / n * s}
    return least[phase["name"]], "ici_bytes_per_s"


def served(MPI, comm) -> str:
    """Which component and which coll/xla algorithm served each shape
    (read from the executables coll/xla compiled)."""
    from ompi_tpu.coll.tuned import TunedCollModule
    out = []
    for coll in ("allgather", "alltoall", "bcast", "reduce_scatter_block"):
        mod = comm.c_coll[coll]
        names = [comm._coll_winners.get(coll, "?")]
        if isinstance(mod, TunedCollModule):
            mod, names = mod.device, names + ["device -> xla"]
        # the key ends in the algorithm; bcast's in its segment count
        algs = sorted({f"{k[1]} {k[2]} {k[-2] if coll == 'bcast' else k[-1]}"
                       for k in getattr(mod, "_cache", {}) if k[0] == coll})
        out.append(f"{coll} by {' '.join(names)}; algorithms: {algs}")
    return " | ".join(out)
