"""ft/inject drop recovery: each rank's FIRST pml frame to its peer
(after the spec arms) is swallowed before sequence stamping. The
dropped message is simply lost — no reorder-buffer hole, no death
report — and the channel keeps working: the NEXT message flows with
its sequence intact (docs/RESILIENCE.md, the drop class's contract)."""
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
other = 1 - r

world.barrier()                  # arm AFTER wire-up traffic settled
var.var_set("mpi_base_ft_inject", True)
var.var_set("mpi_base_ft_inject_drop", f"plane=pml,peer={other},count=1")
inject.refresh()
assert inject.active

world.send(np.full(1024, 1.0 + r), other, tag=1)  # swallowed
time.sleep(0.3)                  # keep the two sends in separate frames
world.send(np.full(1024, 2.0 + r), other, tag=2)  # must still arrive

req = world.irecv(source=other, tag=2)
req.wait(timeout=30)
got = req.get()
assert np.allclose(got, 2.0 + other), got
assert inject.stats["drop"] == 1, inject.stats
assert world.get_failed() == [], world.get_failed()

# the lost frame left no hole: a fresh round-trip still sequences
world.send(np.full(8, 3.0), other, tag=3)
req = world.irecv(source=other, tag=3)
req.wait(timeout=30)
assert np.allclose(req.get(), 3.0)

world.barrier()
MPI.Finalize()
print(f"OK p35_ftdrop rank={r}/{n}", flush=True)
