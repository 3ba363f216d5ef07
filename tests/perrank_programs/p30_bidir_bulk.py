"""Bidirectional bulk synchronous sends: both ranks Irecv then Ssend a
payload far larger than the kernel socket buffers to each other. The
acid test for reader-thread liveness — if a btl reader ever blocks
sending (the Ssend ack) while its own app thread sits in sendall, two
ranks wedge in a permanent cycle (each full socket waits on a reader
that is waiting on the full socket). The reference avoids this by
construction: ob1 acks ride libevent callbacks that never block the
progress loop (opal_progress, btl_tcp_frag send queues)."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size
assert n == 2, "run with -n 2"
peer = 1 - r

MB = 1 << 20
payload = np.full(48 * MB, r + 1, dtype=np.uint8)

req = world.irecv(peer, tag=9)
world.ssend(payload, peer, tag=9)     # ack-bearing send, both ways
st = req.wait()
got = req.get()
assert st.source == peer
assert got.nbytes == payload.nbytes
assert got[0] == peer + 1 and got[-1] == peer + 1

# a second crossing on the same sockets (buffers drained and reused)
req = world.irecv(peer, tag=10)
world.ssend(payload, peer, tag=10)
req.wait()
assert req.get().nbytes == payload.nbytes

MPI.Finalize()
print(f"OK p30_bidir_bulk rank={r}/{n}")
