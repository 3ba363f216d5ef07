"""Probe-earned staging threshold: the staged
device tier's switch point comes from a rank-0 measurement published
through the modex — every rank adopts the SAME value (the staging
decision is collective and must stay rank-symmetric), the decision
layer never routes a collective to a tier the probe shows slower, and
a user-set var still overrides the probe (the bml's
``btl_sm_min_bytes`` discipline, ``btl/bml.py``)."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402
from ompi_tpu.coll import tuned  # noqa: E402
from ompi_tpu.mca import var     # noqa: E402
from ompi_tpu.runtime import spc  # noqa: E402

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size

# 1. every rank adopted a probe result at init, and it is the SAME
#    value everywhere (rank 0 measured; the modex carried it)
basis = tuned.probed_stage_basis()
assert basis.get("ran"), f"probe basis missing at rank {r}: {basis}"
assert "value" in basis, basis
mins = world.allgather(int(basis["value"]))
assert all(m == mins[0] for m in mins), f"asymmetric thresholds: {mins}"

# 2. the effective threshold IS the probed value (no user override set)
eff = tuned.stage_min_for("allreduce")
assert eff == int(basis["value"]), (eff, basis["value"])

# 3. the decision layer obeys its own measurement: an 8 MB allreduce
#    stages if and only if the probe says 8 MB is past the crossover
big = np.full((8 << 20) // 4, float(r + 1), np.float32)
before = spc.read("coll_staged_device")
y = world.allreduce(big, MPI.SUM)
assert y[0] == n * (n + 1) / 2, y[:2]
staged = spc.read("coll_staged_device") > before
should_stage = big.nbytes >= eff
assert staged == should_stage, (staged, should_stage, eff)

# 4. comm_method surfaces the measured basis (operators see WHY)
from ompi_tpu.tools.comm_method import table  # noqa: E402
t = table(world)
assert "stage_probe" in t, sorted(t)
assert t["stage_probe"].get("staged_per_mb_ms") is not None, t["stage_probe"]

# 5. a user-set var overrides the probe, exactly like btl_sm_min_bytes
var.var_set("coll_tuned_stage_min_bytes", 1 << 16)
assert tuned.stage_min_for("allreduce") == 1 << 16
before = spc.read("coll_staged_device")
y2 = world.allreduce(np.full(1 << 16, 1.0, np.float32), MPI.SUM)
assert y2[0] == float(n)
assert spc.read("coll_staged_device") == before + 1, "override ignored"

MPI.Finalize()
print(f"OK p29_stage_probe rank={r}/{n}", flush=True)
