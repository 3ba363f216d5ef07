"""ft/inject corrupt recovery: rank 0 puts a bad-magic frame on the
tcp stream to rank 1. The receiver's framing check drops the
connection WITHOUT a death report; rank 0's next send finds the broken
socket, evicts it, reconnects, and delivers — corruption costs a
reconnect, never a false obituary (docs/RESILIENCE.md, the corrupt
class's contract)."""
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

world.barrier()
if r == 0:
    var.var_set("mpi_base_ft_inject", True)
    var.var_set("mpi_base_ft_inject_corrupt", "rank=0,peer=1,count=1")
    inject.refresh()
    assert inject.active
    # the corrupt frame goes out on the doomed socket; the injector
    # evicts it in the same breath, so THIS sequenced payload rides a
    # fresh connection and is never lost with the corrupted stream
    world.send(np.full(16, 3.0), 1, tag=3)
    world.send(np.full(16, 4.0), 1, tag=4)
    assert inject.stats["corrupt"] == 1, inject.stats
else:
    for tag in (3, 4):           # nothing sequenced was lost
        req = world.irecv(source=0, tag=tag)
        req.wait(timeout=30)
        assert np.allclose(req.get(), float(tag)), req.get()

# no death report on either side: corruption is not failure
assert world.get_failed() == [], world.get_failed()
world.barrier()                  # both directions of the link work
MPI.Finalize()
print(f"OK p37_ftcorrupt rank={r}/{n}", flush=True)
