"""OpenSHMEM across real processes: symmetric heap offsets, one-sided
put/get/atomics, the wait_until flag idiom, scoll-style collectives."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402
from ompi_tpu.shmem.api import CMP_EQ, CMP_GE  # noqa: E402
from ompi_tpu.shmem.perrank import ShmemRankCtx  # noqa: E402

MPI.Init()
world = MPI.get_comm_world()
with ShmemRankCtx(world, heap_size=64) as ctx:
    me, n = ctx.my_pe(), ctx.n_pes()
    assert me == world.rank() and n == world.size

    data = ctx.malloc(8)        # same offset on every PE (symmetry)
    flag = ctx.malloc(1)
    assert data == 0 and flag == 8

    # ring put + flag signal: neighbor polls its LOCAL heap
    right = (me + 1) % n
    ctx.put(data, np.full(4, float(me), np.float32), right)
    ctx.fence()
    ctx.atomic_add(flag, 1.0, right)
    ctx.wait_until(flag, CMP_GE, 1.0)
    got = ctx.get(data, 4, me)          # self-get of what left wrote
    assert np.allclose(got, float((me - 1) % n)), got

    # atomics: shared counter at PE 0
    old = ctx.atomic_fetch_add(16, 1.0, 0)
    ctx.barrier_all()
    assert ctx.atomic_fetch(16, 0) == float(n)

    # collectives through scoll/mpi delegation
    ctx.p(24, float(me * 10), me)
    ctx.barrier_all()
    ctx.broadcast(24, 1, root_pe=1)
    assert ctx.g(24, me) == 10.0
    col = ctx.collect(24, 1)
    assert np.allclose(col, 10.0) and col.size == n
    tot = ctx.reduce(24, 1, MPI.SUM)
    assert tot[0] == 10.0 * n

MPI.Finalize()
print(f"OK p14_shmem rank={me}/{n}", flush=True)
