"""Child program for the multi-controller integration test.

Launched (twice) by ``tools/mpirun.py --coordinator ...`` — the
re-design of the reference's ``mpirun -n 2`` over PRRTE with PMIx
wire-up (``instance.c:547-569`` modex/fence; ``ompi_mpi_init.c:434-447``
init fence). Each controller contributes 2 virtual CPU devices, so
COMM_WORLD has 4 ranks spanning a genuine process boundary
(``jax.process_index() > 0`` on host 1 — the condition the hier/DCN
algorithm path triggers on).
"""
import os
import sys

# Platform setup must precede jax import: the ranks run on host
# devices, never on the chip.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

import jax                                            # noqa: E402
jax.config.update("jax_platforms", "cpu")

import numpy as np                                    # noqa: E402
import ompi_tpu as MPI                                # noqa: E402
from ompi_tpu.mca import var                          # noqa: E402


def main() -> None:
    MPI.Init()                  # runs jax.distributed.initialize from
    world = MPI.get_comm_world()  # the mpirun-provided MCA env vars
    pi = jax.process_index()
    assert world.size == 4, f"world size {world.size}"
    assert world.is_multiprocess
    procs = {getattr(d, "process_index", 0) for d in world.devices}
    assert procs == {0, 1}, procs

    # one allreduce crossing the process boundary
    x = world.put(np.arange(4 * 3, dtype=np.float32).reshape(4, 3))
    y = world.allreduce(x, MPI.SUM)
    expect = np.arange(12, dtype=np.float32).reshape(4, 3).sum(axis=0)
    for r in (2 * pi, 2 * pi + 1):          # this controller's ranks
        got = world.shard(y, r)
        assert np.allclose(got, expect), (r, got, expect)

    # the hier/DCN two-tier path with a GENUINE process_index > 0
    # trigger: reduce_scatter within the ICI tier, cross-tier exchange,
    # allgather back (coll/xla _hier_allreduce_inner)
    var.var_set("coll_xla_allreduce_algorithm", "hier")
    xmod = world.c_coll["allreduce"].device
    assert xmod._multihost(), "hier trigger requires multihost"
    low, high = xmod._groups()
    assert low == [[0, 1], [2, 3]], low     # per-process ICI groups
    assert high == [[0, 2], [1, 3]], high   # cross-process DCN tier
    y2 = world.allreduce(x, MPI.SUM)
    var.var_set("coll_xla_allreduce_algorithm", "auto")
    got = world.shard(y2, 2 * pi)
    assert np.allclose(got, expect), (got, expect)

    # hier bcast + reduce_scatter_block across the REAL process
    # boundary (round-3: hier beyond allreduce, decision row selects it
    # because spans_processes is genuinely true here)
    alg = world.c_coll["allreduce"].device._algorithm("bcast", 4096)
    assert alg == "hier", alg
    xb = world.put(np.arange(4 * 5, dtype=np.float32).reshape(4, 5))
    yb = world.bcast(xb, root=1)
    assert np.allclose(world.shard(yb, 2 * pi),
                       np.arange(5, dtype=np.float32) + 5)
    xr = world.put(np.ones((4, 4, 3), np.float32))
    yr = world.reduce_scatter_block(xr, MPI.SUM)
    assert np.allclose(world.shard(yr, 2 * pi), 4.0)

    # No silent wrong answers: stacked
    # pt2pt / RMA / SHMEM must raise the clean multi-controller guard,
    # not hand back another controller's stale dict state.
    from ompi_tpu.core.errhandler import MPIError
    for fn in (lambda: world.send(np.zeros(2), 0, 1),
               lambda: world.recv(0, dst=1),
               lambda: world.probe(0)):
        try:
            fn()
        except MPIError as e:
            assert "single-controller" in str(e), e
        else:
            raise AssertionError("stacked pt2pt did not guard")
    try:
        from ompi_tpu.osc.framework import Win
        Win(world, 8)
    except MPIError as e:
        assert "single-controller" in str(e), e
    else:
        raise AssertionError("OSC window did not guard")
    try:
        from ompi_tpu.shmem.api import ShmemCtx
        ShmemCtx(world, heap_size=16)
    except MPIError as e:
        assert "single-controller" in str(e), e
    else:
        raise AssertionError("SHMEM ctx did not guard")

    # barrier across controllers + a sub-communicator that spans both
    world.barrier()
    subs = world.split([r % 2 for r in range(4)])     # {0,2} and {1,3}
    sub = subs[2 * pi]                                 # contains a local rank
    sx = sub.put(np.full((2, 2), 3.0, np.float32))
    sy = sub.allreduce(sx, MPI.SUM)
    mine = [r for r in range(sub.size)
            if getattr(sub.devices[r], "process_index", 0) == pi]
    assert np.allclose(sub.shard(sy, mine[0]), 6.0)

    MPI.Finalize()
    print(f"MULTIPROC-OK process={pi}", flush=True)


if __name__ == "__main__":
    main()
