"""ULFM over a REAL process death: the victim os._exit()s mid-run; the
survivors' pending receives complete with MPIX_ERR_PROC_FAILED (the
connection monitor is the failure detector), MPIX_Comm_get_failed
reports it, MPIX_Comm_shrink agrees on the survivor set, and the job
continues on the shrunk communicator — the recovery loop ULFM exists
for, exercised against genuine process loss rather than injection."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
import jax
jax.config.update("jax_platforms", "cpu")
import time                      # noqa: E402
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size
assert n >= 3
victim = n - 1

# establish identified connections first (a never-used peer has no
# connection to observe dying)
world.barrier()

if r == victim:
    # die abruptly: no MPI_Finalize, no atexit — the real failure mode
    os._exit(17)

# survivors: a receive pending on the victim completes in error
req = world.irecv(source=victim, tag=99)
try:
    req.wait(timeout=60)
    raise SystemExit("pending receive from dead peer did not error")
except MPI.MPIError as e:
    assert "died" in str(e) or "failed" in str(e), e

failed = world.get_failed()
assert failed == [victim], failed

# a NEW receive from the dead rank fails fast (no hang)
t0 = time.monotonic()
try:
    world.recv(source=victim, tag=5)
    raise SystemExit("new receive from dead peer did not error")
except MPI.MPIError:
    assert time.monotonic() - t0 < 5

# recover: shrink to the survivors and keep computing
shrunk = world.shrink()
assert shrunk.size == n - 1, shrunk.size
assert shrunk.rank() == r
total = shrunk.allreduce(np.array([1.0]), MPI.SUM)
assert total[0] == float(n - 1), total
shrunk.barrier()
shrunk.free()

MPI.Finalize()
print(f"OK p17_ulfm rank={r}/{n}", flush=True)
