"""Pairwise alltoall on the host tier; XLA all_to_all on the device
tier. allgather both ways too."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp          # noqa: E402
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size

# host tier: chunk for peer j carries (my_rank, j)
got = world.alltoall([np.array([r, j]) for j in range(n)])
for i, c in enumerate(got):
    assert np.array_equal(c, [i, r]), (i, c)

rows = world.allgather(np.array([r * 10]))
assert [int(x[0]) for x in rows] == [i * 10 for i in range(n)]

# device tier
gotd = world.alltoall([jnp.array([float(r), float(j)])
                       for j in range(n)])
for i, c in enumerate(gotd):
    assert np.allclose(np.asarray(c), [i, r]), (i, c)

rowsd = world.allgather(jnp.array([float(r + 1)]))
assert [float(np.asarray(x)[0]) for x in rowsd] == \
    [float(i + 1) for i in range(n)]

MPI.Finalize()
print(f"OK p07_alltoall rank={r}/{n}", flush=True)
