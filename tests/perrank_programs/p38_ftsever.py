"""ft/inject sever recovery: rank 0 abruptly RST-closes its rail-0
connection to rank 1 — on rank 1's wire that is EXACTLY what a process
death looks like (an error on an identified connection), so rank 1
walks the full ULFM survivor path against a peer that is in fact still
running: MPI_ERR_PROC_FAILED on a pending op, get_failed, shrink to a
working singleton communicator. Rank 0 outlives rank 1's whole
recovery (the 12 s nap) to prove the RST — not an exit — was the
ingress (docs/RESILIENCE.md, the sever class's contract)."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
import jax
jax.config.update("jax_platforms", "cpu")
import time                      # noqa: E402
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402
from ompi_tpu.ft import inject   # noqa: E402
from ompi_tpu.mca import var     # noqa: E402

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size
assert n == 2, n

world.barrier()                  # identified connections first
if r == 0:
    var.var_set("mpi_base_ft_inject", True)
    var.var_set("mpi_base_ft_inject_sever", "rank=0,peer=1,count=1")
    inject.refresh()
    assert inject.active
    world.send(np.full(8, 9.0), 1, tag=9)      # sever fires on this send
    assert inject.stats["sever"] == 1, inject.stats
    # stay alive through the survivor's whole recovery: the partition,
    # not our exit, must be what rank 1 observed
    time.sleep(12)
    print(f"OK p38_ftsever rank={r}/{n}", flush=True)
    os._exit(0)                  # partitioned: no fini fence to join

# rank 1: the RST on the identified connection reads as rank 0's death
deadline = time.monotonic() + 10
while world.get_failed() != [0]:
    assert time.monotonic() < deadline, world.get_failed()
    time.sleep(0.05)

t0 = time.monotonic()
req = world.irecv(source=0, tag=50)
try:
    req.wait(timeout=30)
    raise SystemExit("receive from partitioned peer did not error")
except MPI.MPIError as e:
    assert e.error_class == MPI.ERR_PROC_FAILED, e
    assert time.monotonic() - t0 < 5           # fast-fail, not a hang

shrunk = world.shrink()
assert shrunk.size == 1, shrunk.size
total = shrunk.allreduce(np.array([1.0]))
assert total[0] == 1.0, total
shrunk.free()
MPI.Finalize()
print(f"OK p38_ftsever rank={r}/{n}", flush=True)
# the verdict is on stdout and Finalize already ran; skip interpreter
# teardown, where jax's coordination service aborts nondeterministically
# after the peer departed without a jax-level goodbye
os._exit(0)
