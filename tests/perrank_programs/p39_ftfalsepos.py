"""Detector false-positive drill (the hysteresis contract,
docs/RESILIENCE.md): rank 0's heartbeat stream to rank 1 is stalled by
an injected 1.8 s delay — past ``ft_hb_timeout`` (1.0 s), so rank 1
SUSPECTS — but well under the declaration threshold
(timeout + miss * period = 2.6 s), so when the stalled beat lands the
suspicion clears: a slow rank is NOT a dead rank."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
os.environ.setdefault("OMPI_TPU_MCA_mpi_base_ft_hb_period", "0.2")
os.environ.setdefault("OMPI_TPU_MCA_mpi_base_ft_hb_timeout", "1.0")
os.environ.setdefault("OMPI_TPU_MCA_mpi_base_ft_hb_miss", "8")
import jax
jax.config.update("jax_platforms", "cpu")
import time                      # noqa: E402
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402
from ompi_tpu.ft import inject   # noqa: E402
from ompi_tpu.mca import var     # noqa: E402

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size
assert n == 2, n
det = world.router.detector
assert det is not None, "heartbeat detector should be on"

world.barrier()
if r == 0:
    # stall the NEXT tcp frame to rank 1 — with both ranks idle that
    # is a heartbeat, and the sleep happens on the detector thread, so
    # the whole beat stream pauses 1.8 s
    var.var_set("mpi_base_ft_inject", True)
    var.var_set("mpi_base_ft_inject_delay",
                "rank=0,plane=tcp,peer=1,ms=1800,count=1")
    inject.refresh()
    time.sleep(5)
else:
    # poll through the stall window: the suspect level must rise
    # (silence passed the timeout) and then clear (the beat landed
    # before the miss hysteresis ran out)
    suspected = False
    end = time.monotonic() + 5
    while time.monotonic() < end:
        suspected = suspected or det.stats["suspects"] == 1
        time.sleep(0.02)
    assert suspected, "delay never crossed the suspicion threshold"
    assert det.stats["suspects"] == 0, det.stats   # cleared, not latched
    assert det.stats["declared"] == 0, det.stats
    assert det.stats["heartbeats"] > 5, det.stats

# nobody died: the channel and the membership both say so
assert world.get_failed() == [], world.get_failed()
world.send(np.full(8, float(r)), 1 - r, tag=6)
req = world.irecv(source=1 - r, tag=6)
req.wait(timeout=30)
assert np.allclose(req.get(), float(1 - r)), req.get()

world.barrier()
MPI.Finalize()
print(f"OK p39_ftfalsepos rank={r}/{n}", flush=True)
