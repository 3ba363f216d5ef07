"""Synchronous send handshake; matched probe + mrecv."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size

if n >= 2:
    if r == 0:
        # ssend blocks until rank 1's receive matches — completing at
        # all proves the ack handshake works
        world.ssend(np.array([123]), dest=1, tag=9)
        world.send({"k": "v"}, dest=1, tag=10)
    elif r == 1:
        data, st = world.recv(source=0, tag=9)
        assert data[0] == 123 and st.source == 0
        msg = world.mprobe(source=0, tag=10)
        obj, st = world.mrecv(msg)
        assert obj == {"k": "v"} and st.tag == 10

world.barrier()
MPI.Finalize()
print(f"OK p12_ssend_mprobe rank={r}/{n}", flush=True)
