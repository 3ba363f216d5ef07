"""coll framework interposition tier on per-rank communicators:
coll/monitoring counts calls/bytes per (comm, func) and coll/sync
injects flow-control barriers — driven by the same MCA vars as the
stacked world (passed via mpirun --mca)."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size

assert world._coll_interposers == ["sync", "monitoring"], \
    world._coll_interposers

from ompi_tpu.coll import monitoring  # noqa: E402
monitoring.reset()

# a known mixture of collectives (counts must match exactly)
for i in range(4):
    world.allreduce(np.float64(r), MPI.SUM)
world.bcast(np.arange(8, dtype=np.float64) if r == 0 else None, 0)
world.barrier()

snap = monitoring.snapshot()
assert snap[(world.cid, "allreduce")][0] == 4, snap
assert snap[(world.cid, "bcast")][0] == 1, snap
# bcast bytes recorded at the root (its arg carries nbytes)
if r == 0:
    assert snap[(world.cid, "bcast")][1] == 64, snap
assert snap[(world.cid, "barrier")][0] >= 1, snap

# i-collectives are monitored under their OWN names (separate i-slots,
# like the stacked table) and are sync-exempt — their worker threads
# run class-level implementations, so nothing double-counts
req = world.iallreduce(np.float64(r), MPI.SUM)
req.wait()
snap = monitoring.snapshot()
assert snap[(world.cid, "iallreduce")][0] == 1, snap
assert snap[(world.cid, "allreduce")][0] == 4, snap   # unchanged

# chunk-list payloads count summed bytes
chunks = [np.zeros(2, np.float64) for _ in range(n)]
world.alltoall(chunks)
snap = monitoring.snapshot()
assert snap[(world.cid, "alltoall")] == (1, n * 16), snap

# sub-communicators get their own interposition chain + counters
sub = world.split(0)
assert sub._coll_interposers == ["sync", "monitoring"]
sub.allreduce(np.float64(1.0), MPI.SUM)
snap = monitoring.snapshot()
assert snap[(sub.cid, "allreduce")][0] == 1, snap

# the sync interposer (barrier every 3rd op) is active: a burst of
# collectives completes correctly with the injected barriers in the
# stream (the flow-control aid must never change results)
total = 0.0
for i in range(7):
    total += float(np.asarray(world.allreduce(np.float64(i), MPI.SUM)))
assert total == sum(i * n for i in range(7)), total

world.barrier()
MPI.Finalize()
print(f"OK p24_interpose rank={r}/{n}", flush=True)
