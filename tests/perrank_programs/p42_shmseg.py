"""Zero-copy shared-memory data plane, live over real rank processes
(btl/shmseg): single-copy pt2pt adoption, the in-segment node-local
fold, and the byte-identical off-gate. Forced onto the host tier
(stage_min huge) so the segment plane is what is under test.

Modes (P42_MODE):
- ``basic`` (default): pt2pt zero-copy parity vs the ring path
  (pvar-asserted adoption), ssend descriptor-ack path, off-gate
  byte-identity, in-segment fold parity vs the ring schedules
  (pvar-asserted fold), cross-rank bitwise agreement.
- ``pipe``: slots deliberately smaller than the payload, so pt2pt
  rides the pipelined rendezvous whose rail segments pack into shared
  slots (the ``_seg`` detour in btl/bml) — pvar-asserted packs; runs
  under the depth-sweep / rails composition envs.

Composition envs the test file applies on top: pipeline depth sweep,
``mpi_base_compress=1`` (compression keeps its allreduce claim; the
fold must yield), ``mpi_base_btl_rails=2``.
"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
# host tier only: the staged device path would swallow the payload
os.environ["OMPI_TPU_MCA_coll_tuned_stage_min_bytes"] = str(1 << 62)
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402
from ompi_tpu.mca import pvar, var  # noqa: E402

MODE = os.environ.get("P42_MODE", "basic")

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size

var.var_set("mpi_base_shm_zerocopy", True)
if MODE == "pipe":
    # slots smaller than the payload: pt2pt declines the single-slot
    # path and the pipelined train's rail segments pack slot by slot
    var.var_set("mpi_base_pipeline_min_bytes", 1 << 20)
    var.var_set("mpi_base_pipeline_segment_bytes", 512 << 10)

compressed = bool(var.var_get("mpi_base_compress", False))
slot_bytes = int(var.var_get("mpi_base_shm_seg_bytes", 32 << 20))

elems = 1 << 20                      # 4 MB f32 per rank
rng = np.random.default_rng(7)      # same stream on every rank
full = rng.normal(size=(n, elems)).astype(np.float32)
mine = full[r].copy()

# -- pt2pt: zero-copy vs ring must be byte-identical ------------------
a0 = pvar.pvar_read("btl_shm_adoptions")
p0 = pvar.pvar_read("btl_shm_seg_packs")
if r == 0:
    world.send(mine, 1, 77)
    world.ssend(mine, 1, 77)         # descriptor-ack (sync) path
    world.send(full[0], 1, 78)       # again with the gate OFF below
    var.var_set("mpi_base_shm_zerocopy", False)
    world.send(full[0], 1, 79)
    var.var_set("mpi_base_shm_zerocopy", True)
elif r == 1:
    g1 = np.asarray(world.recv(0, 77)[0])
    g2 = np.asarray(world.recv(0, 77)[0])
    assert np.array_equal(g1, full[0]), "zero-copy recv wrong"
    assert np.array_equal(g2, full[0]), "sync zero-copy recv wrong"
    # adopted arrays are plain writable ndarrays (decode_payload
    # semantics) and mutating one never corrupts a later transfer
    g1 += 1.0
    on = np.asarray(world.recv(0, 78)[0])
    off = np.asarray(world.recv(0, 79)[0])
    if compressed:
        # the lossy codec owns the OFF path's bytes; zero-copy stays
        # exact (shm beats compression for pt2pt: no wire to save).
        # p31's documented error model: err <= 2% of the payload max.
        assert np.array_equal(on, full[0]), "zero-copy lost bits"
        err = np.abs(off - full[0]).max()
        scale = np.abs(full[0]).max()
        assert err <= 0.02 * scale, f"codec error {err} vs {scale}"
    else:
        assert on.tobytes() == off.tobytes(), \
            "off-gate not byte-identical"
    del g1, g2, on, off              # drop adoptions -> slots recycle
if MODE == "basic" and mine.nbytes <= slot_bytes:
    if r == 1:
        assert pvar.pvar_read("btl_shm_adoptions") - a0 >= 3, \
            "zero-copy pt2pt path never adopted"
    if r == 0:
        assert pvar.pvar_read("btl_shm_seg_packs") - p0 >= 3, \
            "zero-copy pt2pt path never packed"
if MODE == "pipe" and r == 0:
    assert pvar.pvar_read("btl_shm_seg_packs") - p0 > 0, \
        "pipelined segments never rode the shared slots"

# -- allreduce: in-segment fold parity vs the ring schedules ----------
f0 = pvar.pvar_read("btl_shm_fold_ops")
y1 = world.allreduce(mine, MPI.SUM)
var.var_set("mpi_base_shm_zerocopy", False)
y0 = world.allreduce(mine, MPI.SUM)
var.var_set("mpi_base_shm_zerocopy", True)
assert np.allclose(y1, y0, rtol=1e-4, atol=1e-3), "fold != ring"
folds = pvar.pvar_read("btl_shm_fold_ops") - f0
if MODE == "basic" and not compressed and mine.nbytes <= slot_bytes:
    assert folds >= 1, "in-segment fold never ran"

# integer payload: the rank-order fold is value-exact, demand equality
imine = (full[r] * 100).astype(np.int64)
iref = sum((full[k] * 100).astype(np.int64) for k in range(n))
iy = world.allreduce(imine, MPI.SUM)
assert np.array_equal(iy, iref), "int fold not exact"

# cross-rank determinism: every slice folded once, in rank order, so
# every rank must hold the same BITS
gathered = world.gather(y1.copy(), 0)
if r == 0:
    for row in gathered[1:]:
        assert np.array_equal(row, gathered[0]), "ranks diverged"

rails = int(var.var_get("mpi_base_btl_rails", 1))
if rails > 1 and MODE == "pipe":
    per = [pvar.pvar_read(f"btl_rail_bytes_c{c}") for c in range(rails)]
    assert all(b > 0 for b in per), f"idle rail: {per}"

print("OK p42_shmseg")
MPI.Finalize()
