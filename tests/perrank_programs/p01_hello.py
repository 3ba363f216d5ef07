"""Textbook hello: rank identity is real (rank() == process_index)."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
import jax
jax.config.update("jax_platforms", "cpu")
import ompi_tpu as MPI           # noqa: E402

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size
assert r == jax.process_index(), (r, jax.process_index())
assert n == jax.process_count(), (n, jax.process_count())
assert 0 <= r < n
name = MPI.Get_processor_name()
assert name
MPI.Finalize()
print(f"OK p01_hello rank={r}/{n}", flush=True)
