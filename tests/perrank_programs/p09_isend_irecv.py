"""Nonblocking pt2pt: irecv posted first, wildcard source/tag, Waitall."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size

# every rank posts receives from every other rank FIRST, then sends
reqs = [world.irecv(source=MPI.ANY_SOURCE, tag=5) for _ in range(n - 1)]
for peer in range(n):
    if peer != r:
        world.isend(np.array([r, peer]), peer, tag=5)
MPI.Waitall(reqs)
seen = set()
for q in reqs:
    data = q.get()
    assert data[1] == r            # addressed to me
    seen.add(int(data[0]))
assert seen == set(range(n)) - {r}, seen

MPI.Finalize()
print(f"OK p09_isend_irecv rank={r}/{n}", flush=True)
