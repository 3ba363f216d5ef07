"""Resource-lifecycle churn: repeatedly create and free
communicators, RMA windows, partitioned channels, and MPI-IO files;
file descriptors and router registrations must stay bounded (leaks
here accrete for a long-running job's lifetime)."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402
from ompi_tpu.io.perrank import RankFile         # noqa: E402
from ompi_tpu.osc.perrank import RankWindow      # noqa: E402
from ompi_tpu.pml import part_perrank as part    # noqa: E402

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size


def fd_count() -> int:
    return len(os.listdir("/proc/self/fd"))


def reg_count() -> int:
    router = world.router
    with router._lock:
        return len(router._engines) + len(router._rma)


# warm one full cycle so lazily-created machinery (sm rings, compiled
# paths) exists before the baseline
def cycle(i: int) -> None:
    sub = world.dup()
    assert float(np.asarray(sub.allreduce(np.float64(1.0),
                                          MPI.SUM))) == n
    win = RankWindow(sub, 8, dtype=np.float64, name=f"churn{i}")
    win.put(np.array([float(i)]), (r + 1) % n, 0)
    win.fence()
    win.free()
    ps = part.psend_init(sub, [np.array([1.0])], (r + 1) % n,
                         tag=3).start()
    pr = part.precv_init(sub, 1, (r - 1) % n, tag=3).start()
    ps.pready(0)
    pr.wait(timeout=60)
    # rank-INVARIANT path (pids differ per rank; a per-pid name would
    # open N private files instead of the one shared file MPI-IO is
    # about) — derive from the job's coordination address, p21-style
    tag = os.environ["OMPI_TPU_MCA_mpi_base_coordinator"].replace(
        ":", "_")
    path = f"/tmp/otpu_churn_{tag}.dat"
    f = RankFile(sub, path, etype=np.float64)
    f.write_at(r, np.array([float(r)]))
    f.close()
    f.delete()                   # collective unlink w/ error broadcast
    sub.free()


cycle(0)
world.barrier()
fd0, reg0 = fd_count(), reg_count()

for i in range(1, 16):
    cycle(i)
world.barrier()

fd1, reg1 = fd_count(), reg_count()
# bounded: freeing must release engines/windows/files (small slack for
# lazily-opened shared machinery)
assert fd1 <= fd0 + 3, (fd0, fd1)
assert reg1 <= reg0 + 2, (reg0, reg1)

MPI.Finalize()
print(f"OK p26_churn rank={r}/{n} fds {fd0}->{fd1} regs {reg0}->{reg1}",
      flush=True)
