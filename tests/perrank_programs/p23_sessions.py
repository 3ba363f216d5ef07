"""MPI-4 Sessions in the per-rank world: psets enumerate real
processes, session communicators are per-rank comms on the session's
private CID space, two concurrent sessions operate independently, and
finalizing one leaves the other (and the world) working."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402
from ompi_tpu.runtime.session import Session  # noqa: E402

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size

s1 = Session()
s2 = Session()

# pset enumeration reflects processes, not devices
names = [s1.get_nth_pset(i) for i in range(s1.get_num_psets())]
assert "mpi://WORLD" in names and "mpi://SELF" in names
assert int(s1.get_pset_info("mpi://WORLD").get("size")) == n

# comms from both sessions coexist; their traffic cannot cross (own
# CIDs) even with identical tags
g1 = s1.group_from_pset("mpi://WORLD")
c1 = s1.comm_create_from_group(g1, tag="work")
g2 = s2.group_from_pset("mpi://WORLD")
c2 = s2.comm_create_from_group(g2, tag="work")
assert c1.rank() == r and c1.size == n
assert c2.rank() == r and c2.size == n

tot1 = c1.allreduce(np.float64(r), MPI.SUM)
tot2 = c2.allreduce(np.float64(r * 10), MPI.SUM)
want = n * (n - 1) / 2
assert float(np.asarray(tot1)) == want, tot1
assert float(np.asarray(tot2)) == want * 10, tot2

# pt2pt on a session comm rides its own channel
if n >= 2:
    if r == 0:
        c1.send(np.array([42.0]), 1, tag=3)
    elif r == 1:
        data, st = c1.recv(0, tag=3)
        assert float(data[0]) == 42.0 and st.source == 0

# SELF pset -> size-1 comm
cs = s1.comm_create_from_group(s1.group_from_pset("mpi://SELF"),
                               tag="self")
assert cs.size == 1 and cs.rank() == 0

# derived comms join the session's ownership list: finalize must
# quiesce the whole family, not just direct creations
c2d = c2.dup()
assert float(np.asarray(c2d.allreduce(np.float64(1.0), MPI.SUM))) == n

# finalize one session; the other and the world keep working
world.barrier()
s1.finalize()
tot2b = c2.allreduce(np.float64(1.0), MPI.SUM)
assert float(np.asarray(tot2b)) == n
wtot = world.allreduce(np.float64(2.0), MPI.SUM)
assert float(np.asarray(wtot)) == 2 * n
s2.finalize()
assert c2d._freed and c2._freed      # the family was quiesced

world.barrier()
MPI.Finalize()
print(f"OK p23_sessions rank={r}/{n}", flush=True)
