"""Gather to a non-zero root; scatter back out."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size
root = n - 1

rows = world.gather(np.full(2, float(r)), root=root)
if r == root:
    assert len(rows) == n
    for i, row in enumerate(rows):
        assert np.allclose(row, float(i)), (i, row)
    chunks = [np.full(3, 10.0 + i) for i in range(n)]
else:
    assert rows is None
    chunks = None

mine = world.scatter(chunks, root=root)
assert np.allclose(mine, 10.0 + r), mine

MPI.Finalize()
print(f"OK p06_gather_scatter rank={r}/{n}", flush=True)
