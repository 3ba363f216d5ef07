"""Binomial bcast from two roots; arrays and generic objects."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size

x = np.arange(8, dtype=np.float32) if r == 0 else None
x = world.bcast(x, root=0)
assert np.array_equal(x, np.arange(8, dtype=np.float32)), x

obj = {"msg": "hi", "from": n - 1} if r == n - 1 else None
obj = world.bcast(obj, root=n - 1)
assert obj == {"msg": "hi", "from": n - 1}, obj

MPI.Finalize()
print(f"OK p04_bcast rank={r}/{n}", flush=True)
