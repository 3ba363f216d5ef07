"""Textbook 2-D cartesian decomposition: Cart_create, cart_shift +
sendrecv halo exchange, neighbor collectives."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size
assert n == 4

cart = world.create_cart([2, 2], periods=[True, True])
me = cart.rank()
ci, cj = cart.cart_coords()
assert cart.cart_rank([ci, cj]) == me

# halo exchange along dim 0 with cart_shift + sendrecv
src, dest = cart.cart_shift(direction=0, disp=1)
local = np.full(3, float(me))
halo, st = cart.sendrecv(local, dest=dest, source=src,
                         sendtag=4, recvtag=4)
assert st.source == src
assert np.allclose(halo, float(src)), (halo, src)

# neighbor_allgather: one buffer per neighbor slot (-i, +i, -j, +j)
nbrs = cart.topo.neighbors(me)
got = cart.neighbor_allgather(np.full(2, float(me)))
assert len(got) == len(nbrs) == 4
for nb, g in zip(nbrs, got):
    assert np.allclose(g, float(nb)), (nb, g)

# neighbor_alltoall: chunk j tagged for my j-th neighbor
chunks = [np.array([float(me), float(j)]) for j in range(4)]
recv = cart.neighbor_alltoall(chunks)
for j, (nb, c) in enumerate(zip(nbrs, recv)):
    assert c[0] == float(nb), (j, c)

cart.free()

# regression: periodic ring of size 3 — neighbor exchange must not
# deadlock (post-all-irecvs-then-send-all; review finding)
sub = world.split(0 if r < 3 else MPI.UNDEFINED)
if sub is not None:
    ring = sub.create_cart([3], periods=[True])
    got3 = ring.neighbor_allgather(np.array([float(ring.rank())]))
    left, right = (ring.rank() - 1) % 3, (ring.rank() + 1) % 3
    assert got3[0][0] == float(left) and got3[1][0] == float(right), got3
    ring.free()
    sub.free()

MPI.Finalize()
print(f"OK p15_cart_halo rank={r}/{n}", flush=True)
