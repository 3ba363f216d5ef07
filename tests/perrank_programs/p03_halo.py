"""Sendrecv halo exchange on a periodic 1-D decomposition."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size
right, left = (r + 1) % n, (r - 1) % n
local = np.full(4, float(r))

# ship my right edge right, receive my left halo from the left
left_halo, _ = world.sendrecv(local[-1:], dest=right, source=left,
                              sendtag=1, recvtag=1)
# ship my left edge left, receive my right halo from the right
right_halo, _ = world.sendrecv(local[:1], dest=left, source=right,
                               sendtag=2, recvtag=2)
assert left_halo[0] == float(left), (left_halo, left)
assert right_halo[0] == float(right), (right_halo, right)

MPI.Finalize()
print(f"OK p03_halo rank={r}/{n}", flush=True)
