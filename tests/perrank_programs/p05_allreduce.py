"""Allreduce: host tier (numpy -> p2p algorithms) and device tier
(jax.Array -> ONE compiled XLA psum over the global process mesh)."""
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

# host tier
y = world.allreduce(np.full(5, float(r + 1)), MPI.SUM)
assert np.allclose(y, n * (n + 1) / 2), y
m = world.allreduce(np.array([float(r)]), MPI.MAX)
assert m[0] == n - 1, m

# scalar + user op on the host tier
tot = world.allreduce(r + 1, MPI.SUM)
assert tot == n * (n + 1) // 2, tot

# device tier: XLA collective over the ICI/DCN mesh
xd = jnp.full((3,), float(r + 1))
yd = world.allreduce(xd, MPI.SUM)
assert np.allclose(np.asarray(yd), n * (n + 1) / 2), yd
md = world.allreduce(jnp.array([float(r)]), MPI.MAX)
assert float(np.asarray(md)[0]) == n - 1, md

MPI.Finalize()
print(f"OK p05_allreduce rank={r}/{n}", flush=True)
