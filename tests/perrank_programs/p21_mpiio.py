"""Per-rank MPI-IO: N real processes on ONE file — independent
positioned IO, two-phase collective writes/reads, window-atomic shared
file pointer, and rank-ordered IO."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
import jax
jax.config.update("jax_platforms", "cpu")
import sys                       # noqa: E402
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402
from ompi_tpu.io.perrank import RankFile  # noqa: E402

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size
path = sys.argv[1] if len(sys.argv) > 1 else \
    f"/tmp/ompi_tpu_p21_{os.environ['OMPI_TPU_MCA_mpi_base_coordinator'].replace(':', '_')}.dat"

with RankFile(world, path, etype=np.float64) as f:
    # independent positioned IO: disjoint blocks of 4
    f.write_at(r * 4, np.arange(4, dtype=np.float64) + 10 * r)
    f.sync()
    peer = (r + 1) % n
    got = f.read_at(peer * 4, 4)
    assert np.allclose(got, np.arange(4) + 10 * peer), got

    # collective two-phase write: INTERLEAVED singles (rank r owns
    # elements r, n+r, 2n+r ...) — the aggregator coalesces them into
    # one run
    base = 4 * n
    mine = np.array([100.0 * r + k for k in range(3)])
    # strided writes through write_at_all, one element at a time
    for k in range(3):
        f.write_at_all(base + k * n + r, mine[k:k + 1])
    f.sync()
    whole = f.read_at(base, 3 * n)
    for k in range(3):
        for who in range(n):
            assert whole[k * n + who] == 100.0 * who + k, (k, who)

    # collective read: everyone pulls its own block through the
    # aggregator (one span read at rank 0, scattered)
    myrow = f.read_at_all(r * 4, 4)
    assert np.allclose(myrow, np.arange(4) + 10 * r)

    # shared file pointer: concurrent appends claim disjoint regions
    sp_base = base + 3 * n
    f.seek_shared(sp_base)
    start = f.write_shared(np.full(2 + r, 1000.0 + r))
    assert start >= sp_base
    f.sync()
    # every region landed intact (read back each rank's claim)
    starts = world.allgather(np.int64(start))
    sizes = world.allgather(np.int64(2 + r))
    claimed = sorted((int(s), int(c)) for s, c in zip(starts, sizes))
    # disjoint, tightly packed coverage of the appended span
    total = sum(c for _, c in claimed)
    assert claimed[0][0] == sp_base
    for (a, ca), (b, _cb) in zip(claimed, claimed[1:]):
        assert a + ca == b, claimed
    for s, c in zip(starts, sizes):
        seg = f.read_at(int(s), int(c))
        who = round(seg[0] - 1000.0)
        assert np.allclose(seg, 1000.0 + who) and c == 2 + who

    # ordered IO: rank-ordered regions
    f.seek_shared(sp_base + total)
    pos = f.write_ordered(np.full(r + 1, 7.0 * (r + 1)))
    before = sum(k + 1 for k in range(r))
    assert pos == sp_base + total + before, (pos, before)
    f.sync()
    if r == 0:
        flat = f.read_at(sp_base + total, sum(k + 1 for k in range(n)))
        want = np.concatenate([np.full(k + 1, 7.0 * (k + 1))
                               for k in range(n)])
        assert np.allclose(flat, want), flat

    # nonblocking positioned IO
    req = f.iwrite_at(0, np.array([-1.0, -2.0]))
    req.wait()
    rreq = f.iread_at(0, 2)
    rreq.wait()
    assert np.allclose(rreq.get(), [-1.0, -2.0])

    assert f.get_size() > 0

world.barrier()
if r == 0:
    os.unlink(path)
MPI.Finalize()
print(f"OK p21_mpiio rank={r}/{n}", flush=True)
