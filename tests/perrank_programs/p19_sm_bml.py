"""btl/sm + bml/r2: same-host pt2pt payloads >= btl_sm_min_bytes ride
shared-memory rings (bandwidth plane, tcp-poke doorbell), small frames
stay on tcp (latency plane), ring-busting frames fall back to tcp —
and the mixed transports NEVER reorder a sender's stream (the ob1
sequencing rule at the bml boundary)."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
# Pin the routing threshold (env = user-set source): this program
# tests the sm/bml MECHANICS, so the init micro-probe must not demote
# sm on hosts where the ring measures slower than sockets.
os.environ.setdefault("OMPI_TPU_MCA_btl_sm_min_bytes", str(32 << 10))
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size
assert n == 2, "this program is written for -n 2"
peer = 1 - r

from ompi_tpu.runtime.init import _state        # noqa: E402
ep = _state["router"].endpoint
assert ep.sm is not None, "sm plane should be up on a same-host job"
assert not ep.probe_basis.get("ran"), \
    "user-set btl_sm_min_bytes must suppress the probe"

# interleave small (tcp), medium (sm ring), and ring-busting (tcp
# fallback) messages; the receiver must see them exactly in send order
# even though they ride different transports
med_elems = (256 << 10) // 8     # 256 KB >= min_bytes -> sm
big_elems = (8 << 20) // 8       # 8 MB > the 4 MB ring -> tcp
sizes = [1, med_elems, 1, big_elems, med_elems, 1]
if r == 0:
    for i, sz in enumerate(sizes):
        world.send(np.full(sz, i, dtype=np.int64), peer, tag=3)
else:
    for i, sz in enumerate(sizes):
        data, st = world.recv(0, tag=3)
        assert int(data[0]) == i, (i, int(data[0]))
        assert data.size == sz, (i, data.size, sz)

world.barrier()

# transport accounting: the two medium frames took sm, rest tcp
stats = ep.stats
if r == 0:
    assert stats["sm"] >= 2, stats
    assert stats["tcp"] >= 4, stats

# bandwidth sanity on the sm plane: stream 16 x 256 KB one way
import time                      # noqa: E402
world.barrier()
reps, chunk = 16, np.zeros(med_elems, dtype=np.int64)
t0 = time.perf_counter()
if r == 0:
    for _ in range(reps):
        world.send(chunk, peer, tag=11)
    world.recv(peer, tag=12)     # drain ack
else:
    for _ in range(reps):
        world.recv(0, tag=11)
    world.send(np.array([1]), 0, tag=12)
gbps = reps * chunk.nbytes / (time.perf_counter() - t0) / 1e9
world.barrier()

MPI.Finalize()
print(f"OK p19_sm_bml rank={r}/{n} stream={gbps:.2f}GB/s "
      f"sm={stats['sm']} tcp={stats['tcp']}", flush=True)
