"""scan / exscan / reduce (commutative + non-commutative) /
reduce_scatter_block."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size

s = world.scan(np.array([float(r + 1)]), MPI.SUM)
assert s[0] == (r + 1) * (r + 2) / 2, s

e = world.exscan(np.array([float(r + 1)]), MPI.SUM)
if r == 0:
    assert e is None
else:
    assert e[0] == r * (r + 1) / 2, e

t = world.reduce(np.array([float(r)]), MPI.SUM, root=0)
if r == 0:
    assert t[0] == n * (n - 1) / 2, t
else:
    assert t is None

# non-commutative op exercises the ordered linear fold
mat = MPI.op_create(lambda a, b: a @ b, commute=False, name="matmul")
m = np.array([[1.0, float(r + 1)], [0.0, 1.0]])
p = world.reduce(m, mat, root=0)
if r == 0:
    expect = np.eye(2)
    for i in range(n):
        expect = expect @ np.array([[1.0, float(i + 1)], [0.0, 1.0]])
    assert np.allclose(p, expect), (p, expect)

rs = world.reduce_scatter_block(
    [np.array([float(r + j)]) for j in range(n)], MPI.SUM)
assert rs[0] == sum(i + r for i in range(n)), rs

MPI.Finalize()
print(f"OK p11_scan_reduce rank={r}/{n}", flush=True)
