"""MPI-4 partitioned pt2pt across real processes (incremental pready
transfer, parrived polling), the real mpisync clock-offset table, and
the comm_method transport matrix fed by bml's per-btl counters."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
import jax
jax.config.update("jax_platforms", "cpu")
import time                      # noqa: E402
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402
from ompi_tpu.pml import part_perrank as part  # noqa: E402

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size
assert n >= 2

# -- partitioned send/recv between ranks 0 and 1 ----------------------
NP = 4
if r == 0:
    parts = [np.full(3, 10.0 * k) for k in range(NP)]
    ps = part.psend_init(world, parts, dest=1, tag=5).start()
    # contribute out of order, with gaps the receiver observes
    ps.pready(2)
    ps.pready(0)
    time.sleep(0.2)
    ps.pready_range(1, 1)
    ps.pready_list([3])
    done, _ = ps.test()
    assert done
    ps.wait()
elif r == 1:
    pr = part.precv_init(world, NP, source=0, tag=5).start()
    # early partitions arrive while late ones are still unproduced
    deadline = time.monotonic() + 30
    while not (pr.parrived(0) and pr.parrived(2)):
        assert time.monotonic() < deadline
        time.sleep(0.005)
    pr.wait(timeout=60)
    got = pr.get()
    for k in range(NP):
        assert np.allclose(got[k], 10.0 * k), (k, got[k])
world.barrier()

# a second round through the SAME persistent requests (MPI-4 start
# semantics)
if r == 0:
    ps2 = part.psend_init(world, [np.array([7.0]), np.array([8.0])],
                          dest=1, tag=6).start()
    ps2.pready(1)
    ps2.pready(0)
    ps2.start()                          # restart resets ready state
    ps2.pready(0)
    ps2.pready(1)
elif r == 1:
    pr2 = part.precv_init(world, 2, source=0, tag=6).start()
    pr2.wait(timeout=60)
    pr2.start()
    pr2.wait(timeout=60)
    assert np.allclose(pr2.get()[0], 7.0)
world.barrier()

# -- mpisync: real cross-process clock offsets ------------------------
from ompi_tpu.tools import mpisync  # noqa: E402
rows = mpisync.sync_report_perrank(world, rounds=6)
assert len(rows) == n
assert rows[0]["offset_s"] == 0.0
for row in rows[1:]:
    # same host, same clock source: offsets are microseconds-scale,
    # bounded by the measured RTT (mpigclock's own invariant)
    assert abs(row["offset_s"]) <= max(row["rtt_s"], 1e-3), row
    assert row["rtt_s"] > 0

# -- comm_method transport matrix -------------------------------------
from ompi_tpu.tools import comm_method  # noqa: E402
t = comm_method.table(world)
assert "pt2pt_transports" in t, t
assert t["pt2pt_transports"]["tcp"] > 0, t
assert t["btl_sm"] in (True, False)

world.barrier()
MPI.Finalize()
print(f"OK p22_part_sync rank={r}/{n}", flush=True)
