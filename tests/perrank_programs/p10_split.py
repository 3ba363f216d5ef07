"""Comm algebra: split into parity groups, collective inside the child,
dup, split_type, free."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size

sub = world.split(color=r % 2, key=-r)      # key reverses rank order
members = [i for i in range(n) if i % 2 == r % 2]
assert sub.size == len(members)
# key=-r sorts members descending by world rank
expect_rank = sorted(members, reverse=True).index(r)
assert sub.rank() == expect_rank, (sub.rank(), expect_rank)

s = sub.allreduce(np.array([float(r)]), MPI.SUM)
assert np.allclose(s, sum(members)), (s, members)

d = world.dup()
assert d.rank() == r and d.size == n
y = d.allreduce(np.array([1.0]), MPI.SUM)
assert y[0] == n
d.free()

shared = world.split_type(MPI.COMM_TYPE_SHARED)
assert shared.size == n and shared.rank() == r   # all ranks on one host
shared.free()
none = world.split(MPI.UNDEFINED)
assert none is None
sub.free()

MPI.Finalize()
print(f"OK p10_split rank={r}/{n}", flush=True)
