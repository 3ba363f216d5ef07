"""Large-message data plane, live over real rank processes: the
segment-pipelined ring allreduce and chain bcast (core/rankcomm) whose
chunk hops ride the pml's pipelined rendezvous (pml/pipeline), striped
over ``mpi_base_btl_rails`` rails (btl/bml). Forced onto the host tier
(stage_min huge) so the pipelined hops are the ones under test.
Parity contract (docs/LARGEMSG.md): pipelined results match the
serial reduce+bcast schedule, all ranks hold identical bits, and with
rails>1 every rail carries segment traffic."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
# host tier only: the staged device path would swallow the payload
os.environ["OMPI_TPU_MCA_coll_tuned_stage_min_bytes"] = str(1 << 62)
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402
from ompi_tpu.mca import pvar, var  # noqa: E402

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size

# thresholds low enough that an 8 MB payload pipelines hard
var.var_set("mpi_base_pipeline_min_bytes", 1 << 20)
var.var_set("mpi_base_pipeline_segment_bytes", 512 << 10)

elems = 1 << 21                      # 8 MB f32 per rank
rng = np.random.default_rng(11)      # same stream on every rank
full = rng.normal(size=(n, elems)).astype(np.float32)
mine = full[r].copy()
ref = full.sum(axis=0)

# pipelined ring allreduce: segments must flow, result must be right
s0 = pvar.pvar_read("pml_pipeline_segments")
i0 = pvar.pvar_read("pml_pipeline_inits")
y1 = world.allreduce(mine, MPI.SUM)
segs = pvar.pvar_read("pml_pipeline_segments") - s0
inits = pvar.pvar_read("pml_pipeline_inits") - i0
assert inits >= 1, "no pipelined rendezvous train started"
assert segs > 1, f"pipeline never segmented ({segs})"
assert np.allclose(y1, ref, rtol=1e-4, atol=1e-3), "ring result wrong"

# parity with the serial (unpipelined) schedule — the ring
# reassociates f32 folds, so allclose, plus bitwise agreement below
var.var_set("mpi_base_pipeline_enable", False)
y0 = world.allreduce(mine, MPI.SUM)
var.var_set("mpi_base_pipeline_enable", True)
assert np.allclose(y0, y1, rtol=1e-5, atol=1e-4), \
    "pipelined != unpipelined"

# integer payload: the fold order is value-exact, demand equality
imine = (full[r] * 100).astype(np.int64)
iref = sum((full[k] * 100).astype(np.int64) for k in range(n))
iy1 = world.allreduce(imine, MPI.SUM)
assert np.array_equal(iy1, iref), "int ring not exact"

# cross-rank determinism: one computation point per chunk means every
# rank must hold the same BITS
gathered = world.gather(y1.copy(), 0)
if r == 0:
    for row in gathered[1:]:
        assert np.array_equal(row, gathered[0]), "ranks diverged"

# pipelined chain bcast: bcast moves bytes, demand exact equality
data = full[0].copy() if r == 0 else None
b1 = world.bcast(data, 0)
assert np.array_equal(np.asarray(b1), full[0]), "chain bcast wrong"
var.var_set("mpi_base_pipeline_enable", False)
b0 = world.bcast(data, 0)
var.var_set("mpi_base_pipeline_enable", True)
assert np.array_equal(np.asarray(b0), full[0]), "serial bcast wrong"

# overlap accounting fed (loopback hops report 0; real ranks overlap)
assert pvar.pvar_read("pml_overlap_ratio") >= 0.0

rails = int(var.var_get("mpi_base_btl_rails", 1))
if rails > 1:
    per = [pvar.pvar_read(f"btl_rail_bytes_c{c}") for c in range(rails)]
    assert all(b > 0 for b in per), f"idle rail: {per}"

print("OK p33_largemsg")
MPI.Finalize()
