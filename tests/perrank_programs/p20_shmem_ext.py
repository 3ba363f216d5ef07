"""Extended per-rank OpenSHMEM surface: put_signal gating on real
async delivery, distributed locks that genuinely block across OS
processes, multi-variable wait (ivars), and bitwise atomics applied on
the target's reader thread."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402
from ompi_tpu.shmem.api import CMP_EQ, CMP_NE    # noqa: E402
from ompi_tpu.shmem.perrank import ShmemRankCtx  # noqa: E402

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size
assert n >= 3, "needs >= 3 PEs"

ctx = ShmemRankCtx(world, heap_size=256, dtype=np.int64)
DATA = ctx.malloc(8)      # payload slots
SIG = ctx.malloc(1)       # signal word
LOCK = ctx.malloc(1)      # distributed lock word
CNT = ctx.malloc(1)       # lock-protected counter on PE 0
FLAGS = [ctx.malloc(1) for _ in range(n)]   # ivar set
ctx.barrier_all()

# -- put_signal: payload must be visible when the signal fires --------
if r == 1:
    ctx.put_signal(DATA, np.arange(8, dtype=np.int64) + 100, SIG, 7,
                   pe=0, sig_op=0)
if r == 0:
    got = ctx.signal_wait_until(SIG, CMP_EQ, 7, timeout=60)
    assert got == 7
    local = ctx.get(DATA, 8, pe=0)
    assert local[0] == 100 and local[7] == 107, local
ctx.barrier_all()

# -- distributed lock: every PE increments the shared counter under
# mutual exclusion (read-modify-write made safe only by the lock) ----
for _ in range(5):
    ctx.set_lock(LOCK, timeout=60)
    cur = int(ctx.g(CNT, pe=0))
    ctx.p(CNT, cur + 1, pe=0)
    ctx.clear_lock(LOCK)
ctx.barrier_all()
if r == 0:
    total = int(ctx.g(CNT, pe=0))
    assert total == 5 * n, total

# -- ivars: PE 0 waits for ANY flag; the first writer is staggered ----
if r == 0:
    winner = ctx.wait_until_any([FLAGS[i] for i in range(1, n)],
                                CMP_NE, 0, timeout=60)
    assert 0 <= winner < n - 1
    # then wait for ALL of them
    ctx.wait_until_all([FLAGS[i] for i in range(1, n)], CMP_NE, 0,
                       timeout=60)
else:
    import time
    time.sleep(0.05 * r)             # staggered arrivals
    ctx.atomic_set(FLAGS[r], r + 1, pe=0)
ctx.barrier_all()

# -- bitwise atomics on PE 2's heap ----------------------------------
BITS = ctx.malloc(1)
ctx.barrier_all()
if r == 2:
    ctx.p(BITS, 0, pe=2)
ctx.barrier_all()
ctx.atomic_or(BITS, 1 << r, pe=2)
ctx.barrier_all()
if r == 2:
    v = int(ctx.g(BITS, pe=2))
    assert v == (1 << n) - 1, v
    old = int(ctx.atomic_fetch_xor(BITS, 0b1, pe=2))
    assert old == (1 << n) - 1
ctx.barrier_all()

assert ctx.pe_accessible(n - 1) and not ctx.pe_accessible(n)
assert ctx.addr_accessible(BITS, 0)
assert ctx.info_get_version() == (1, 5)

ctx.finalize()
MPI.Finalize()
print(f"OK p20_shmem_ext rank={r}/{n}", flush=True)
