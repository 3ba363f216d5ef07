"""The ISSUE-11 acceptance drill: telemetry is on, ft/inject holds
EVERY pml frame rank 1 sends for 200 ms (a persistent straggler, not a
death — heartbeats ride the tcp plane and are untouched), and the
drill runs all-pairs pt2pt rounds plus a full-world allreduce so every
rank owns three peers' worth of recv-wait evidence. The health monitor
on each healthy rank must DECLARE rank 1 (``telemetry.straggler`` +
flight-recorder snapshot), and every rank dumps its telemetry so the
driving test can prove ``mpitop`` elects rank 1 as slow_rank and the
merged flight-recorder incident report names it critical
(docs/OBSERVABILITY.md)."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
_OUT = os.environ.get("P41_OUT", ".")
_SLOW = 1
_DELAY_MS = 200
# the drill's telemetry/resilience config rides the MCA env surface (a
# driver's --mca flags would override via the same names)
os.environ.setdefault("OMPI_TPU_MCA_mpi_base_telemetry", "1")
os.environ.setdefault("OMPI_TPU_MCA_mpi_base_telemetry_sample_s", "0.1")
os.environ.setdefault("OMPI_TPU_MCA_mpi_base_telemetry_window_s", "10")
os.environ.setdefault("OMPI_TPU_MCA_mpi_base_telemetry_straggler_score",
                      "0.02")
os.environ.setdefault("OMPI_TPU_MCA_mpi_base_telemetry_straggler_miss",
                      "2")
os.environ.setdefault("OMPI_TPU_MCA_mpi_base_telemetry_flightrec_dir",
                      _OUT)
os.environ.setdefault("OMPI_TPU_MCA_mpi_base_ft_hb_period", "0.1")
os.environ.setdefault("OMPI_TPU_MCA_mpi_base_ft_hb_timeout", "3.0")
os.environ.setdefault("OMPI_TPU_MCA_mpi_base_ft_inject", "1")
os.environ.setdefault("OMPI_TPU_MCA_mpi_base_ft_inject_delay",
                      f"rank={_SLOW},plane=pml,ms={_DELAY_MS},count=-1")
import jax
jax.config.update("jax_platforms", "cpu")
import time                      # noqa: E402
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402
from ompi_tpu import telemetry   # noqa: E402
from ompi_tpu.ft import inject   # noqa: E402
from ompi_tpu.telemetry import health  # noqa: E402

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size
assert n == 4, n
assert telemetry.active          # the env gate armed the plane
world.barrier()                  # identified connections all around

# -- the evidence phase: all-pairs pt2pt + one collective per round ----
# every rank recvs from THREE peers, so the cross-peer median exists
# and the 200 ms outlier waits on rank 1 are attributable to it alone.
ROUNDS = 6
for rnd in range(ROUNDS):
    for peer in range(n):
        if peer != r:
            world.send(np.full(16, float(r)), peer, tag=100 + rnd)
    for peer in range(n):
        if peer != r:
            data, st = world.recv(source=peer, tag=100 + rnd)
            assert np.allclose(data, float(peer)), (peer, data)
    x = world.allreduce(np.full(8, 1.0))
    assert np.allclose(x, float(n)), x

# -- the verdict: every healthy rank's monitor declares rank 1 ---------
mon = health.monitor()
assert mon is not None
if r != _SLOW:
    deadline = time.monotonic() + 20
    while _SLOW not in mon.declared():
        assert time.monotonic() < deadline, \
            (mon.scores(), mon.declared())
        mon.sample()
        time.sleep(0.05)
else:
    assert inject.stats["delay"] > 0, inject.stats

# each rank persists its telemetry for mpitop / the incident merge
telemetry.dump(os.path.join(_OUT, f"telemetry_{r}.json"), rank=r)

assert world.get_failed() == [], world.get_failed()   # slow != dead
world.barrier()
MPI.Finalize()
print(f"OK p41_straggler rank={r}/{n}", flush=True)
