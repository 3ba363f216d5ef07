"""Natural-order send/recv ring: every rank but 0 receives BEFORE it
sends — the ordering the single-controller engine could never express."""
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

if r == 0:
    world.send(np.array([0], dtype=np.int64), right, tag=7)
    token, st = world.recv(left, tag=7)
    assert st.source == left and st.tag == 7
    assert token.sum() == n * (n - 1) // 2, token
else:
    token, st = world.recv(left, tag=7)      # recv first: blocks for real
    assert st.source == left
    world.send(np.concatenate([token, [r]]), right, tag=7)

# monitoring: my traffic rows show the ring edges (tools/profile over
# the per-rank engine); aggregate across ranks via allgather
from ompi_tpu.tools import profile as prof
mine = prof.pt2pt_matrix(world, "messages")
rows = world.allgather(mine)
total = sum(rows)
assert total[r, right] == 1 and total[left, r] == 1, total

MPI.Finalize()
print(f"OK p02_ring rank={r}/{n}", flush=True)
