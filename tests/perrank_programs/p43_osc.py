"""One-sided RMA acceptance drill (docs/RMA.md), live over real rank
processes: Win_allocate through the osc selection step, a fenced
Put/Get/Accumulate ring whose every rank verifies against the numpy
reference, and the passive-target lock/put/flush/unlock cycle — on
the component ``P43_OSC`` pins (``shm`` or ``pt2pt``; both must pass
the same assertions, the checkparity rule-7 contract taken end to
end)."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402
from ompi_tpu.api import mpi as api  # noqa: E402
from ompi_tpu.mca import pvar    # noqa: E402

COMP = os.environ.get("P43_OSC", "shm")

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size
assert n == 4, n
nxt, prv = (r + 1) % n, (r - 1) % n

elems = 1 << 16                      # 256 KB f32 per window
rng = np.random.default_rng(43)      # same stream on every rank
full = rng.normal(size=(n, elems)).astype(np.float32)

p0 = pvar.pvar_read("osc_puts")
win = api.Win_allocate(world, elems, np.float32, name="p43",
                       force=COMP)
assert win.component == COMP, win.component
win.local[:] = 0.0

# -- fenced put ring: r writes its vector into (r+1)'s window ---------
win.fence()
win.put(full[r], nxt)
win.fence()
assert np.array_equal(win.local, full[prv]), "put ring wrong"

# -- fenced get ring: r reads (r+2)'s window (holds full[r+1]) --------
win.fence()
view = win.get((r + 2) % n, 0, elems)
got = np.asarray(view).copy()
win.fence()
assert np.array_equal(got, full[(r + 1) % n]), "get ring wrong"
if COMP == "shm":
    # the zero-copy contract: get adopted the segment in place
    assert not np.asarray(view).flags.owndata, "shm get copied"
del view

# -- fenced accumulate fan-in: everyone folds into rank 0 (sum) and
#    rank 1 (max over |x|); rank order must not matter -----------------
win.fence()
win.local[:] = 0.0                   # owner store between fences
win.fence()
win.accumulate(full[r], 0, op="sum")
win.accumulate(np.abs(full[r]), 1, op="max")
win.fence()
if r == 0:
    ref = full.sum(axis=0, dtype=np.float32)
    assert np.allclose(win.local, ref, rtol=1e-4, atol=1e-4), \
        "sum fan-in wrong"
if r == 1:
    ref = np.abs(full).max(axis=0)
    assert np.array_equal(win.local, ref), "max fan-in wrong"

# -- passive target: lock/put/flush/unlock, then barrier + verify -----
win.lock(nxt)                        # exclusive
win.put(full[r] * 2.0, nxt)
win.flush(nxt)
win.unlock(nxt)
world.barrier()
assert np.array_equal(win.local, full[prv] * 2.0), "passive put wrong"

# -- the instrumentation plane saw the traffic ------------------------
assert pvar.pvar_read("osc_puts") - p0 >= 2, "osc_puts never counted"
assert pvar.pvar_read("osc_fences") >= 7, "fences never counted"
if COMP == "shm":
    assert pvar.pvar_read("osc_windows_shm") >= 1
else:
    assert pvar.pvar_read("osc_windows_pt2pt") >= 1

world.barrier()                      # all asserts done before free
win.free()
print(f"P43 OK rank={r}/{n} comp={COMP}", flush=True)
MPI.Finalize()
