"""One-sided RMA across real processes: put/get/accumulate/fetch_op/
compare_and_swap against a remote window, fence epochs, passive-target
lock/unlock. The target's application thread never cooperates — true
one-sided progress over the btl/tcp active-message plane."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402
from ompi_tpu.osc.perrank import LOCK_EXCLUSIVE, RankWindow  # noqa: E402

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size

win = RankWindow(world, 16, np.float32)

# active-target epoch: everyone puts its rank into slot r of rank 0
win.fence()
win.put(np.array([float(r + 1)]), target=0, disp=r)
win.fence()
if r == 0:
    assert np.allclose(win.local[:n],
                       np.arange(1, n + 1, dtype=np.float32)), win.local

# accumulate: everyone adds 1 into slot 8 of rank n-1
win.fence()
win.accumulate([1.0], target=n - 1, disp=8, op="sum")
win.fence()
if r == n - 1:
    assert win.local[8] == float(n), win.local[8]

# get reads a remote region one-sidedly
got = win.get(target=0, disp=0, count=n)
assert np.allclose(got, np.arange(1, n + 1, dtype=np.float32)), got

# fetch_and_op serializes a shared counter at rank 0 slot 12
old = win.fetch_and_op(1.0, target=0, disp=12, op="sum")
assert 0.0 <= old < n
win.fence()
if r == 0:
    assert win.local[12] == float(n)

# compare_and_swap: exactly one rank wins the election at slot 15
prev = win.compare_and_swap(0.0, float(r + 1), target=0, disp=15)
wins = world.allreduce(1 if prev == 0.0 else 0, MPI.SUM)
assert wins == 1, wins

# passive target: serialize read-modify-write under an exclusive lock
win.fence()
for _ in range(3):
    win.lock(1, LOCK_EXCLUSIVE)
    cur = win.get(target=1, disp=3, count=1)[0]
    win.put([cur + 1.0], target=1, disp=3)
    win.unlock(1)
world.barrier()
if r == 1:
    assert win.local[3] == float(3 * n), win.local[3]

win.free()

# cross-comm wid agreement: ranks with DIFFERENT window-creation
# histories (a subcomm window on evens only) must still agree on the
# next world window's id — the sequence is per-comm, not per-process
sub = world.split(color=r % 2)
if r % 2 == 0:
    wsub = RankWindow(sub, 4, np.float32)
    wsub.put([float(r + 50)], target=0, disp=0)
    wsub.fence()
    wsub.free()
w2 = RankWindow(world, 4, np.float32)
w2.put([float(r)], target=(r + 1) % n, disp=0)
w2.fence()
assert w2.local[0] == float((r - 1) % n), w2.local
w2.free()
sub.free()

# regression: asymmetric window sizes — origin checks the TARGET's
# exposure size and a target-side failure raises promptly (error
# reply), never wedging the connection
w3 = RankWindow(world, 16 if r == 0 else 4, np.float32)
assert w3.sizes[0] == 16 and all(s == 4 for s in w3.sizes[1:])
if r == 1:
    w3.put([1.0] * 8, target=0, disp=2)     # fits 0's larger region
try:
    w3.put([1.0], target=1, disp=10)        # past 1's exposure
    raise SystemExit("no bounds error for remote window")
except MPI.MPIError:
    pass
w3.fence()
# the connection survived the rejected op: normal traffic still flows
w3.put([float(r)], target=0, disp=r)
w3.fence()
w3.free()
print(f"OK p13b_asym rank={r}/{n}", flush=True)

# request-based RMA (osc.h:269-279 rput/rget/raccumulate): the request
# completes at remote completion; rget's payload is the fetched array
w4 = RankWindow(world, 4, np.float64)
w4.local[:] = 0.0
w4.fence()
right = (r + 1) % n
req = w4.rput(np.array([10.0 + r, 20.0 + r]), right, disp=1)
req.wait()
g = w4.rget(right, disp=1, count=2)
g.wait()
got = g.get()
assert got[0] == 10.0 + r and got[1] == 20.0 + r, got
ra = w4.raccumulate(np.array([0.25, 0.25]), right, disp=1, op="sum")
ra.wait()
g2 = w4.rget(right, disp=1, count=2)
g2.wait()
assert g2.get()[0] == 10.25 + r, g2.get()
w4.fence()
# my own slots were written by my LEFT neighbor
left = (r - 1) % n
assert w4.local[1] == 10.25 + left, w4.local
w4.free()
print(f"OK p13c_request_rma rank={r}/{n}", flush=True)

MPI.Finalize()
print(f"OK p13_rma rank={r}/{n}", flush=True)
