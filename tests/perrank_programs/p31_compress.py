"""Compressed wire collectives: the host-tier binomial reduce/bcast
chains move quantized payloads (codes + per-block scales) above the
compression threshold, and the pvars account the byte savings
(docs/COMPRESSION.md). Forced onto the host tier (stage_min huge) so
the compressed hops are the ones under test."""
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

elems = 1 << 18                      # 1 MB f32 per rank
rng = np.random.default_rng(7)       # same stream on every rank
full = rng.normal(size=(n, elems)).astype(np.float32)
mine = full[r].copy()
ref = full.sum(axis=0)

# uncompressed baseline
y0 = world.allreduce(mine, MPI.SUM)
assert np.allclose(y0, ref, atol=1e-3), "baseline allreduce wrong"

# compressed: threshold below the payload, int8 block codec
var.var_set("mpi_base_compress", True)
var.var_set("mpi_base_compress_min_bytes", 1 << 20)
bi0 = pvar.pvar_read("compress_bytes_in")
bo0 = pvar.pvar_read("compress_bytes_out")
y1 = world.allreduce(mine, MPI.SUM)
bi = pvar.pvar_read("compress_bytes_in") - bi0
bo = pvar.pvar_read("compress_bytes_out") - bo0
assert bi > 0, "compressed path never engaged"
ratio = bo / bi
assert ratio <= 0.3, f"wire ratio {ratio} > 0.3"

# documented error model: per-hop int8 error accumulates over the
# log2(n) reduce hops + 1 bcast quantization; bound it loosely by the
# watermark times the hop count
err = np.abs(y1 - ref).max()
scale = np.abs(ref).max()
assert err <= 0.02 * scale, f"compressed error {err} vs scale {scale}"
wm = pvar.pvar_read("compress_max_abs_error")
assert wm > 0, "error watermark never fed"

# every rank must hold the same result (bcast forwards codes losslessly)
gathered = world.gather(y1.copy(), 0)
if r == 0:
    for row in gathered[1:]:
        assert np.array_equal(row, gathered[0]), "ranks diverged"

# off again: bit-identical to the uncompressed baseline
var.var_set("mpi_base_compress", False)
y2 = world.allreduce(mine, MPI.SUM)
assert np.array_equal(y2, y0), "disabled path not bit-identical"

MPI.Finalize()
print(f"OK p31_compress rank={r}/{n} ratio={ratio:.3f}", flush=True)
