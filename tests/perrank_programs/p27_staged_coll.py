"""Staged device tier: large HOST (numpy) buffers ride the compiled XLA
collective — the coll/accelerator bracket inverted
(coll_accelerator_allreduce.c:55-80 stages device->host; we stage
host->device). This is the path that puts textbook C buffers on the
fabric: api/cabi.py hands numpy views to these same entry points."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402
from ompi_tpu.mca import var     # noqa: E402
from ompi_tpu.runtime import spc  # noqa: E402

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size

var.var_set("coll_tuned_stage_min_bytes", 1 << 16)   # 64 KB for the test
ELEMS = (1 << 18)                                    # 1 MB f32 payloads

# allreduce: every rank holds a large numpy buffer -> staged psum
before = spc.read("coll_staged_device")
y = world.allreduce(np.full(ELEMS, float(r + 1), np.float32), MPI.SUM)
assert isinstance(y, np.ndarray), type(y)
assert y.shape == (ELEMS,) and y[0] == n * (n + 1) / 2, y[:4]
assert spc.read("coll_staged_device") == before + 1, "allreduce not staged"

# small buffers stay on the host tier (below the threshold)
before = spc.read("coll_staged_device")
ys = world.allreduce(np.full(4, float(r + 1), np.float32), MPI.SUM)
assert ys[0] == n * (n + 1) / 2
assert spc.read("coll_staged_device") == before, "small msg staged"

# bcast: root's staging decision propagates; non-roots pass nothing
before = spc.read("coll_staged_device")
payload = (np.arange(ELEMS, dtype=np.float32) if r == 1 else None)
g = world.bcast(payload, root=1)
assert isinstance(g, np.ndarray) and g.shape == (ELEMS,)
assert g[12345] == 12345.0
assert spc.read("coll_staged_device") == before + 1, "bcast not staged"

# reduce: staged allreduce, result delivered at root only
rr = world.reduce(np.full(ELEMS, 2.0, np.float32), MPI.SUM, root=0)
if r == 0:
    assert rr is not None and rr[0] == 2.0 * n, rr[:2]
else:
    assert rr is None

# allgather / alltoall stage only under the explicit uniformity
# promise (the C-signature guarantee; ragged generic chunks are legal
# on the host tier, so the rank-symmetric staging decision needs it)
before = spc.read("coll_staged_device")
rows = world.allgather(np.full(ELEMS // n, float(r), np.float32),
                       uniform=True)
assert len(rows) == n and all(rows[i][0] == float(i) for i in range(n))
assert spc.read("coll_staged_device") == before + 1, "ag not staged"

chunks = [np.full(ELEMS // n, float(r * n + j), np.float32)
          for j in range(n)]
out = world.alltoall(chunks, uniform=True)
assert all(out[i][0] == float(i * n + r) for i in range(n)), \
    [float(o[0]) for o in out]
assert spc.read("coll_staged_device") == before + 2, "a2a not staged"

# without the promise, the same large buffers stay on the host tier
rows2 = world.allgather(np.full(ELEMS // n, float(r), np.float32))
assert all(rows2[i][0] == float(i) for i in range(n))
assert spc.read("coll_staged_device") == before + 2

# MAX and a non-prim predefined op (PROD -> on-device ordered fold)
m = world.allreduce(np.full(ELEMS, float(r), np.float32), MPI.MAX)
assert m[0] == float(n - 1)
p = world.allreduce(np.full(ELEMS, 2.0, np.float32), MPI.PROD)
assert p[0] == float(2 ** n)

# int64 stays correct: either staged under x64 or host-tier otherwise
i8 = world.allreduce(
    np.full(ELEMS, np.int64(1) << 40, np.int64), MPI.SUM)
assert int(i8[0]) == n * (1 << 40), i8[0]

MPI.Finalize()
print(f"OK p27_staged_coll rank={r}/{n}", flush=True)
