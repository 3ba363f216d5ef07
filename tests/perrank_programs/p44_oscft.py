"""The RMA fault-tolerance acceptance drill (docs/RMA.md,
docs/RESILIENCE.md): the heartbeat detector is on, every rank holds an
osc/shm window inside an open fence epoch, and rank 2 SIGKILLs itself
mid-epoch. The survivors must get ``MPI_ERR_PROC_FAILED`` from
``Win_fence`` and from ops targeting the dead rank — not a hang — the
``osc_ft_failed_epochs`` pvar must record the torn epoch, Win_free must
reclaim the survivors' segments even though its completion barrier
errors, and shrink + re-``Win_allocate`` on the 3-rank communicator
must carry a verified fenced ring. The victim's own leaked segment file
is the launcher sweep's to unlink (the test asserts zero orphans)."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
_HB_TIMEOUT = 0.8
os.environ.setdefault("OMPI_TPU_MCA_mpi_base_ft_hb_period", "0.1")
os.environ.setdefault("OMPI_TPU_MCA_mpi_base_ft_hb_timeout",
                      str(_HB_TIMEOUT))
os.environ.setdefault("OMPI_TPU_MCA_mpi_base_ft_hb_miss", "3")
import jax
jax.config.update("jax_platforms", "cpu")
import signal                    # noqa: E402
import time                      # noqa: E402
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402
from ompi_tpu.api import mpi as api  # noqa: E402
from ompi_tpu.mca import pvar    # noqa: E402

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size
assert n == 4, n
victim = 2
nxt, prv = (r + 1) % n, (r - 1) % n

api.Comm_set_errhandler(world, MPI.ERRORS_RETURN)
world.barrier()

elems = 1 << 14
rng = np.random.default_rng(44)
full = rng.normal(size=(n, elems)).astype(np.float32)

win = api.Win_allocate(world, elems, np.float32, name="p44",
                       force="shm")
win.local[:] = 0.0

# -- healthy fenced ring, then the victim dies INSIDE the epoch --------
win.fence()
win.put(full[r], nxt)
win.fence()                          # epoch stays open (fence epoch)
assert np.array_equal(win.local, full[prv]), "healthy ring wrong"

if r == victim:
    os.kill(os.getpid(), signal.SIGKILL)   # no unlink, no goodbye

# -- survivors: detector declares, epochs fail fast --------------------
deadline = time.monotonic() + 15
while world.get_failed() != [victim]:
    assert time.monotonic() < deadline, world.get_failed()
    time.sleep(0.05)

try:
    win.fence()
    raise SystemExit("Win_fence over a dead rank did not error")
except MPI.MPIError as e:
    assert e.error_class == MPI.ERR_PROC_FAILED, e
try:
    win.put(full[r], victim)
    raise SystemExit("put to a dead rank did not error")
except MPI.MPIError as e:
    assert e.error_class == MPI.ERR_PROC_FAILED, e
assert pvar.pvar_read("osc_ft_failed_epochs") >= 1, \
    "torn epoch never counted"

# -- revoke, free (reclaims segments through the failed barrier) -------
if r == 0:
    MPI.MPIX_Comm_revoke(world)
deadline = time.monotonic() + 10
while not MPI.MPIX_Comm_is_revoked(world):
    assert time.monotonic() < deadline, "revoke did not propagate"
    time.sleep(0.02)
try:
    win.free()                       # completion barrier errors ...
except MPI.MPIError:
    pass                             # ... but the segments are gone

# -- shrink + re-Win_allocate: the RMA plane survives the failure ------
shrunk = MPI.MPIX_Comm_shrink(world)
n2, sr = shrunk.size, shrunk.rank()
assert n2 == n - 1, n2
assert sr == {0: 0, 1: 1, 3: 2}[r], (r, sr)

full2 = rng.normal(size=(n2, elems)).astype(np.float32)
win2 = api.Win_allocate(shrunk, elems, np.float32, name="p44b",
                        force="shm")
win2.local[:] = 0.0
win2.fence()
win2.put(full2[sr], (sr + 1) % n2)
win2.fence()
assert np.array_equal(win2.local, full2[(sr - 1) % n2]), \
    "post-shrink ring wrong"
win2.free()

shrunk.barrier()
shrunk.free()
MPI.Finalize()
print(f"P44 OK rank={r}/{n}", flush=True)
# skip interpreter teardown (p34's lesson: jax's coordination service
# aborts nondeterministically once a rank has died); rank 0 hosts the
# service and must outlive the other survivors' OK lines
if r == 0:
    time.sleep(3)
os._exit(0)
