"""Algorithm selection tests (coll/xla + coll/decision).

Every collective's default lowering must give exactly what numpy gives,
on the whole world and on an odd-sized sub-communicator, for the sum,
non-sum and non-commutative ops — the analogue of the reference
validating every coll_base algorithm against basic_linear. The pinned
schedules that remain (``hier``, reduce's ``in_order_binary``) must
match too, and the decision table must pick what its rows say.
"""
import functools

import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.coll import decision
from ompi_tpu.mca import var


@pytest.fixture
def alg(request):
    """Set one coll_xla_*_algorithm var for the test, restore after."""
    def _set(func, name):
        key = f"coll_xla_{func}_algorithm"
        var.var_set(key, name)
        request.addfinalizer(lambda: var.var_set(key, "auto"))
    return _set


def _rank_data(world, shape=(5,), dtype=np.float32, seed=0):
    rng = np.random.default_rng(seed)
    rows = [rng.standard_normal(shape).astype(dtype) + r
            for r in range(world.size)]
    return rows, world.stack(rows)


@pytest.mark.parametrize("name", ["hier"])
def test_allreduce_algorithms_match_direct(mpi, world, alg, name):
    rows, x = _rank_data(world, (7,))
    alg("allreduce", name)
    y = np.asarray(world.allreduce(x, mpi.SUM))
    want = np.sum(rows, axis=0)
    assert np.allclose(y, np.broadcast_to(want, y.shape), atol=1e-4)


_HIER_RULES = {"allreduce": {"algorithm_rules": [
    [0, 0, "direct"], [0, 64 << 20, "hier"]]}}
_BCAST_HIER_RULES = {"bcast": {"algorithm_rules": [
    [0, 0, "direct"], [0, 64 << 20, "hier"]]}}


@pytest.mark.parametrize("func,platform,nbytes,multihost,dyn,want", [
    ("allreduce", "tpu", 64, False, None, "direct"),
    ("allreduce", "tpu", 64 << 20, False, None, "direct"),
    ("allreduce", "tpu", 256 << 20, False, None, "direct"),
    ("allreduce", "", 128 << 20, False, None, "direct"),
    ("allreduce", "cpu", 1 << 20, False, None, "direct"),
    ("allreduce", "tpu", 64, True, None, "hier"),
    ("allreduce", "tpu", 256 << 20, False, _HIER_RULES, "hier"),
    ("bcast", "", 128 << 20, False, None, "direct"),
    ("bcast", "tpu", 64 << 20, False, None, "direct"),
    ("bcast", "tpu", 256 << 20, False, None, "direct"),
    ("bcast", "tpu", 256 << 20, False, _BCAST_HIER_RULES, "hier"),
])
def test_decision_fixed_table_structure(func, platform, nbytes, multihost,
                                        dyn, want):
    # last-match-wins over (min_comm_size, min_bytes) thresholds; the
    # dynamic-rules file still overrides the fixed table
    assert decision.decide(func, 8, nbytes, multihost, dyn,
                           platform=platform) == want


def test_decision_malformed_rules_skipped():
    dyn = {"allreduce": {"algorithm_rules": [["0", "0", "direct"],
                                             [0, 0, "hier"]]}}
    # string thresholds are skipped, well-formed rules still apply
    assert decision.decide("allreduce", 8, 64, False, dyn) == "hier"


def test_decision_dynamic_rules_override():
    dyn = {"allgather": {"algorithm_rules": [[0, 0, "direct"],
                                             [4, 1024, "hier"]]}}
    assert decision.decide("allgather", 2, 64, False, dyn) == "direct"
    assert decision.decide("allgather", 8, 4096, False, dyn) == "hier"


def test_non_commutative_falls_back_to_direct(mpi, world, alg):
    # A non-commutative user op must not run a reordering schedule.
    rows, x = _rank_data(world, (3,), seed=9)
    # "take the right operand" is associative but NOT commutative: an
    # ordered left fold yields the highest rank's data; a reordering
    # schedule would yield some other rank's.
    f = mpi.op_create(lambda a, b: b, commute=False)
    alg("allreduce", "hier")
    y = np.asarray(world.allreduce(x, f))
    assert np.allclose(y[0], rows[world.size - 1], atol=1e-6)


@pytest.mark.parametrize("root", [0, 3])
def test_reduce_in_order_binary(mpi, world, alg, root):
    rows, x = _rank_data(world, (4,), seed=24)
    alg("reduce", "in_order_binary")
    y = np.asarray(world.reduce(x, mpi.SUM, root))
    assert np.allclose(y[root], np.sum(rows, axis=0), atol=1e-4)


def test_reduce_in_order_binary_non_commutative(mpi, world, alg):
    """THE point of the in-order tree: a non-commutative (associative)
    op reduces in exact rank order — no demotion to direct needed."""
    rows, x = _rank_data(world, (3,), seed=25)
    f = mpi.op_create(lambda a, b: b, commute=False)  # right-take
    alg("reduce", "in_order_binary")
    y = np.asarray(world.reduce(x, f, 0))
    # ordered fold of right-take == the LAST rank's row
    assert np.allclose(y[0], rows[world.size - 1], atol=1e-6)


# -- the default lowering of every collective against numpy ----------------
# (collective, op or dtype, communicator, root). "RIGHT" is a
# non-commutative right-take op: an ordered fold of it is the last
# operand, so a reordered combine shows.
_FOLDS = {"SUM": np.add, "MAX": np.maximum, "PROD": np.multiply,
          "RIGHT": lambda a, b: b}
_COMMS = ("world", "sub3")
_CASES = (
    [("allreduce", op, c, None)
     for op in ("SUM", "MAX", "PROD", "RIGHT") for c in _COMMS]
    + [("reduce_scatter_block", op, c, None)
       for op in ("SUM", "MAX", "RIGHT") for c in _COMMS]
    + [("scan", op, c, None) for op in ("SUM", "MAX", "RIGHT")
       for c in _COMMS]
    + [("exscan", op, c, None) for op in ("SUM", "RIGHT") for c in _COMMS]
    + [("reduce", op, "world", root) for op in ("SUM", "MAX")
       for root in (0, -1)]
    + [("bcast", "float32", "world", 0), ("bcast", "float32", "world", 3),
       ("bcast", "float32", "sub3", 1), ("bcast", "bool", "world", 2)]
    + [(f, None, c, None) for f in ("allgather", "alltoall")
       for c in _COMMS]
    + [("barrier", None, c, None) for c in _COMMS]
)


@pytest.fixture(scope="module")
def sub3(mpi, world):
    """A 3-rank (odd, non-power-of-two) sub-communicator."""
    return world.split([0] * 3 + [mpi.UNDEFINED] * (world.size - 3))[0]


@pytest.mark.parametrize(
    "coll,arg,which,root", _CASES,
    ids=["-".join(str(p) for p in c if p is not None) for c in _CASES])
def test_default_lowering_matches_numpy(mpi, world, sub3, rng, coll,
                                        arg, which, root):
    comm = world if which == "world" else sub3
    n = comm.size
    # small integers: every fold (|PROD| <= 2^8) is exact in float32
    shape = (n, 2) if coll in ("reduce_scatter_block", "alltoall") else (5,)
    rows = [rng.integers(-2, 3, size=shape).astype(np.float32)
            for _ in range(n)]
    if coll == "barrier":
        for _ in range(3):
            comm.barrier()
        return
    op = None
    if arg in _FOLDS:
        op = (mpi.op_create(lambda a, b: b, commute=False)
              if arg == "RIGHT" else getattr(mpi, arg))
        fold = functools.partial(functools.reduce, _FOLDS[arg])
    if arg == "bool":
        rows = [r > 0 for r in rows]
    x = comm.stack(rows)
    if coll == "allreduce":
        y = np.asarray(comm.allreduce(x, op))
        want = [fold(rows)] * n
    elif coll == "reduce":
        root %= n
        y = np.asarray(comm.reduce(x, op, root))[root:root + 1]
        want = [fold(rows)]
    elif coll == "reduce_scatter_block":
        y = np.asarray(comm.reduce_scatter_block(x, op))
        want = [fold([row[r] for row in rows]) for r in range(n)]
    elif coll == "scan":
        y = np.asarray(comm.scan(x, op))
        want = [fold(rows[:r + 1]) for r in range(n)]
    elif coll == "exscan":
        # rank 0's exscan result is undefined by MPI
        y = np.asarray(comm.exscan(x, op))[1:]
        want = [fold(rows[:r]) for r in range(1, n)]
    elif coll == "bcast":
        y = np.asarray(comm.bcast(x, root=root))
        want = [rows[root]] * n
    elif coll == "allgather":
        y = np.asarray(comm.allgather(x))
        want = [np.stack(rows)] * n
    else:                                   # alltoall
        y = np.asarray(comm.alltoall(x))
        want = [np.stack([rows[s][r] for s in range(n)])
                for r in range(n)]
    assert y.dtype == rows[0].dtype
    assert np.array_equal(y, np.stack(want)), (coll, arg, which)


# -- reduce_scatter_block SUM by block shape and type ---------------------
@pytest.fixture(scope="module")
def quad(mpi, world):
    """A 4-rank sub-communicator, the v5e 2x2 host's size."""
    return world.split([0 if r < 4 else 1 for r in range(world.size)])[0]


@pytest.mark.parametrize("block", [(256,), (200,), (3, 128)],
                         ids=["1d-lanes", "1d-ragged", "2d"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int32"])
def test_reduce_scatter_block_sum_exact(mpi, quad, dtype, block):
    """``out[r] = sum_i in[i, r]`` bit for bit on four ranks, for a 1-D
    block of whole 128-lane rows (all_to_all and a local sum for 32-bit
    types), a ragged 1-D block and a 2-D block: integers small enough
    that any order of combining is exact in bf16."""
    n = quad.size
    rng = np.random.default_rng(28)
    xh = rng.integers(-8, 9, size=(n, n) + block).astype(np.float32)
    x = quad.put(jnp.asarray(xh, getattr(jnp, dtype)))
    y = quad.reduce_scatter_block(x, mpi.SUM)
    assert y.dtype == x.dtype and y.shape == (n,) + block
    assert np.array_equal(np.asarray(y, np.float32), xh.sum(axis=0))


def test_reduce_scatter_v_lane_multiple_exact(mpi, quad):
    """MPI_Reduce_scatter whose padded count m is a multiple of 128
    rides the (N, N, m) block: ragged counts come back exact."""
    n = quad.size
    counts = [256, 128, 200, 256]
    rng = np.random.default_rng(29)
    xh = rng.integers(-8, 9, size=(n, sum(counts))).astype(np.float32)
    out = quad.reduce_scatter(quad.put(xh), counts, mpi.SUM)
    offs = np.concatenate([[0], np.cumsum(counts)[:-1]])
    for r in range(n):
        want = xh[:, offs[r]:offs[r] + counts[r]].sum(axis=0)
        assert np.array_equal(np.asarray(out[r]), want)
