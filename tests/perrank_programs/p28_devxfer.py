"""Device-to-device pt2pt: large jax.Array payloads ride the PJRT
cross-host transfer plane (rendezvous pull), not host pickle — the
ob1 eager/rendezvous protocol switch (pml_ob1_sendreq.h:389-460)
re-designed for the PJRT transfer service."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp          # noqa: E402
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402
from ompi_tpu.mca import var     # noqa: E402

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size
ELEMS = 1 << 19                  # 2 MB f32: above the 1 MB limit

# ring exchange of large device arrays
right, left = (r + 1) % n, (r - 1) % n
x = jnp.arange(ELEMS, dtype=jnp.float32) + 1000.0 * r
req = world.irecv(left, tag=3)
world.send(x, right, tag=3)
st = req.wait()
y = req.get()
# the payload arrives as a DEVICE array (it never became host bytes)
assert isinstance(y, jax.Array), type(y)
ya = np.asarray(y)
assert ya[12345] == 12345.0 + 1000.0 * left, ya[12340:12350]
# status byte counts were right before resolution (probe semantics)
assert st.nbytes == ELEMS * 4, st.nbytes

# blocking recv path + device compute on the result without transfer
z = world.sendrecv(x * 2, right)[0]
assert isinstance(z, jax.Array)
assert float(jnp.sum(z[:2]).block_until_ready()) == \
    2 * (0 + 1 + 2000.0 * left), z[:2]

# small device arrays stay on the eager host path (below the limit)
s = world.sendrecv(jnp.full(8, float(r)), right)[0]
assert np.asarray(s)[0] == float(left)

# the switch honors the MCA limit: raise it and large goes eager too
var.var_set("btl_devxfer_min_bytes", 1 << 30)
w = world.sendrecv(x, right)[0]
assert np.asarray(w)[0] == 1000.0 * left
var.var_set("btl_devxfer_min_bytes", 1 << 20)

# persistent receives resolve device payloads too (base-Request path)
preq = world.recv_init(left, tag=7)
preq.start()
world.send(x + 5.0, right, tag=7)
preq.wait()
pv = preq.get()
assert isinstance(pv, jax.Array), type(pv)
assert float(np.asarray(pv)[0]) == 1000.0 * left + 5.0

# THREAD_MULTIPLE-ish: two directions in flight at once, no deadlock
a = jnp.full(ELEMS, float(r), jnp.float32)
q1 = world.irecv(right, tag=9)
q2 = world.irecv(left, tag=9)
world.send(a, left, tag=9)
world.send(a + 1, right, tag=9)
q1.wait()
q2.wait()
assert float(np.asarray(q1.get())[0]) == float(right)      # their r
assert float(np.asarray(q2.get())[0]) == float(left) + 1
MPI.Finalize()
print(f"OK p28_devxfer rank={r}/{n}", flush=True)
