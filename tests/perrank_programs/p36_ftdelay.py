"""ft/inject delay recovery: rank 0's next tcp frame to rank 1 is held
``ms`` on the sender. Nothing is lost, nobody is declared dead — the
message arrives late and the stack just runs slower, which the
round-trip time proves (docs/RESILIENCE.md, the delay class's
contract; the detector-facing half of the contract — a sub-timeout
delay is NOT a death — is p39_ftfalsepos)."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
import jax
jax.config.update("jax_platforms", "cpu")
import time                      # noqa: E402
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402
from ompi_tpu.ft import inject   # noqa: E402
from ompi_tpu.mca import var     # noqa: E402

_DELAY_S = 0.6

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size
assert n == 2, n
other = 1 - r

world.barrier()
if r == 0:
    var.var_set("mpi_base_ft_inject", True)
    var.var_set("mpi_base_ft_inject_delay",
                f"rank=0,plane=tcp,peer=1,ms={int(_DELAY_S * 1e3)},count=1")
    inject.refresh()
    assert inject.active
    t0 = time.monotonic()
    world.send(np.full(64, 7.0), 1, tag=7)     # held _DELAY_S somewhere
    req = world.irecv(source=1, tag=8)         # ... on its way out
    req.wait(timeout=30)
    rtt = time.monotonic() - t0
    assert np.allclose(req.get(), 8.0), req.get()
    assert rtt >= _DELAY_S * 0.66, rtt         # the delay really held it
    assert inject.stats["delay"] == 1, inject.stats
else:
    req = world.irecv(source=0, tag=7)
    req.wait(timeout=30)
    assert np.allclose(req.get(), 7.0), req.get()
    world.send(np.full(64, 8.0), 0, tag=8)

assert world.get_failed() == [], world.get_failed()
world.barrier()
MPI.Finalize()
print(f"OK p36_ftdelay rank={r}/{n}", flush=True)
