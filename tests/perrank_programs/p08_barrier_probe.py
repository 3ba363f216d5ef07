"""Barrier; probe/iprobe observe a pending message without receiving."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size

for _ in range(3):
    world.barrier()

if n >= 2:
    if r == 0:
        world.send(np.arange(6), dest=1, tag=42)
        world.barrier()
    elif r == 1:
        world.barrier()            # guarantees the send happened
        st = world.probe(source=0, tag=42)
        assert st.source == 0 and st.tag == 42 and st.count == 6, \
            (st.source, st.tag, st.count)
        ok, st2 = world.iprobe(source=0)
        assert ok and st2.count == 6
        data, _ = world.recv(source=0, tag=42)
        assert np.array_equal(data, np.arange(6))
        ok, _ = world.iprobe(source=0)
        assert not ok              # consumed
    else:
        world.barrier()

MPI.Finalize()
print(f"OK p08_barrier_probe rank={r}/{n}", flush=True)
