"""Two-tier (han-style) collectives beyond allreduce: hier bcast /
allgather / reduce_scatter_block / barrier, and the allreduce
cross-tier step as a scattered-chunk exchange (psum_scatter over the
high groups) instead of gather+sum."""
import numpy as np
import pytest

import ompi_tpu as MPI
from ompi_tpu.mca import var


@pytest.fixture
def hier(world):
    funcs = ["bcast", "allgather", "reduce_scatter_block", "barrier",
             "allreduce"]
    for f in funcs:
        var.var_set(f"coll_xla_{f}_algorithm", "hier")
    yield world
    for f in funcs:
        var.var_set(f"coll_xla_{f}_algorithm", "auto")


def test_hier_bcast_all_roots(hier, rng):
    n = hier.size
    x = rng.standard_normal((n, 21)).astype(np.float32)
    for root in range(n):
        y = np.asarray(hier.bcast(hier.put(x), root=root))
        for r in range(n):
            np.testing.assert_allclose(y[r], x[root], rtol=1e-6)


def test_hier_allgather(hier, rng):
    n = hier.size
    x = rng.standard_normal((n, 7)).astype(np.float32)
    g = np.asarray(hier.allgather(hier.put(x)))
    for r in range(n):
        np.testing.assert_allclose(g[r], x, rtol=1e-6)


def test_hier_reduce_scatter_block(hier, rng):
    n = hier.size
    x = rng.standard_normal((n, n, 6)).astype(np.float32)
    y = np.asarray(hier.reduce_scatter_block(hier.put(x), MPI.SUM))
    for r in range(n):
        np.testing.assert_allclose(y[r], x[:, r].sum(0), rtol=1e-4)


def test_hier_rsb_non_sum_falls_back(hier, rng):
    """hier rsb is the psum lowering; MAX must demote cleanly."""
    n = hier.size
    x = rng.standard_normal((n, n, 4)).astype(np.float32)
    y = np.asarray(hier.reduce_scatter_block(hier.put(x), MPI.MAX))
    for r in range(n):
        np.testing.assert_allclose(y[r], x[:, r].max(0), rtol=1e-5)


def test_hier_barrier(hier):
    for _ in range(3):
        hier.barrier()


def test_hier_allreduce_scattered_cross_tier(hier, rng):
    """Odd payloads exercise both padding layers (low chunk and high
    sub-chunk)."""
    n = hier.size
    for length in (1, 13, 37, 128):
        x = rng.standard_normal((n, length)).astype(np.float32)
        y = np.asarray(hier.allreduce(hier.put(x), MPI.SUM))
        np.testing.assert_allclose(y[0], x.sum(0), rtol=1e-4,
                                   atol=1e-5)


def test_hier_decision_rows_multihost():
    """The decision layer selects hier for the extended set on
    multihost meshes."""
    from ompi_tpu.coll import decision
    for func in ("allreduce", "bcast", "allgather",
                 "reduce_scatter_block", "barrier"):
        assert decision.decide(func, 8, 1 << 20, True, None) == "hier", \
            func
    # and not for pt2pt-shaped ops
    assert decision.decide("reduce", 8, 1 << 20, True, None) != "hier"
