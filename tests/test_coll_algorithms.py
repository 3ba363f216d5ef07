"""Explicit algorithm registry tests (coll/xla + coll/decision).

Each explicit schedule (ring, recursive doubling, Rabenseifner, bruck,
binomial, pairwise, dissemination) must produce the same result as the
``direct`` fused-XLA lowering — the analogue of the reference validating
every coll_base algorithm against basic_linear.
"""
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


@pytest.mark.parametrize("name", ["ring", "recursive_doubling",
                                  "rabenseifner", "hier"])
def test_allreduce_algorithms_match_direct(mpi, world, alg, name):
    rows, x = _rank_data(world, (7,))
    alg("allreduce", name)
    y = np.asarray(world.allreduce(x, mpi.SUM))
    want = np.sum(rows, axis=0)
    assert np.allclose(y, np.broadcast_to(want, y.shape), atol=1e-4)


def test_recursive_doubling_bitwise_identical_across_ranks(mpi, world,
                                                           alg):
    # The normalized (lower, higher) combine order must give every rank
    # the exact same float bits.
    _, x = _rank_data(world, (16,), seed=3)
    alg("allreduce", "recursive_doubling")
    y = np.asarray(world.allreduce(x, mpi.SUM))
    for r in range(1, world.size):
        assert np.array_equal(y[0], y[r])


def test_allreduce_max_via_recursive_doubling(mpi, world, alg):
    rows, x = _rank_data(world, (4,), seed=5)
    alg("allreduce", "recursive_doubling")
    y = np.asarray(world.allreduce(x, mpi.MAX))
    assert np.allclose(y[0], np.max(rows, axis=0))


@pytest.mark.parametrize("name", ["ring", "bruck", "neighborexchange",
                                  "two_procs"])
def test_allgather_algorithms(mpi, world, alg, name):
    rows, x = _rank_data(world, (3,), seed=1)
    alg("allgather", name)
    y = np.asarray(world.allgather(x))
    want = np.stack(rows)                     # (n, 3)
    for r in range(world.size):
        assert np.allclose(y[r], want)


@pytest.mark.parametrize("name", ["binomial", "knomial", "chain",
                                  "pipeline", "scatter_allgather"])
def test_bcast_algorithms(mpi, world, alg, name):
    rows, x = _rank_data(world, (6,), seed=2)
    root = 3
    alg("bcast", name)
    y = np.asarray(world.bcast(x, root=root))
    for r in range(world.size):
        assert np.allclose(y[r], rows[root], atol=1e-6)


def test_alltoall_pairwise(mpi, world, alg):
    n = world.size
    rows = [np.arange(n * 2, dtype=np.float32).reshape(n, 2) + 100 * r
            for r in range(n)]
    x = world.stack(rows)
    alg("alltoall", "pairwise")
    y = np.asarray(world.alltoall(x))
    for r in range(n):
        for s in range(n):
            assert np.allclose(y[r, s], rows[s][r])


def test_reduce_scatter_ring(mpi, world, alg):
    n = world.size
    rows = [np.random.default_rng(r).standard_normal((n, 3))
            .astype(np.float32) for r in range(n)]
    x = world.stack(rows)
    alg("reduce_scatter_block", "ring")
    y = np.asarray(world.reduce_scatter_block(x, mpi.SUM))
    want = np.sum(rows, axis=0)               # (n, 3)
    for r in range(n):
        assert np.allclose(y[r], want[r], atol=1e-4)


def test_barrier_dissemination(mpi, world, alg):
    alg("barrier", "dissemination")
    world.barrier()                            # completes -> pass


_RABENSEIFNER_RULES = {"allreduce": {"algorithm_rules": [
    [0, 0, "direct"], [0, 64 << 20, "rabenseifner"]]}}
_SCATTER_ALLGATHER_RULES = {"bcast": {"algorithm_rules": [
    [0, 0, "direct"], [0, 64 << 20, "scatter_allgather"]]}}


@pytest.mark.parametrize("func,platform,nbytes,multihost,dyn,want", [
    ("allreduce", "tpu", 64, False, None, "direct"),
    ("allreduce", "tpu", 64 << 20, False, None, "direct"),
    ("allreduce", "tpu", 256 << 20, False, None, "direct"),
    ("allreduce", "", 128 << 20, False, None, "direct"),
    ("allreduce", "cpu", 1 << 20, False, None, "rabenseifner"),
    ("allreduce", "tpu", 64, True, None, "hier"),
    ("allreduce", "tpu", 256 << 20, False, _RABENSEIFNER_RULES,
     "rabenseifner"),
    ("bcast", "", 128 << 20, False, None, "direct"),
    ("bcast", "tpu", 64 << 20, False, None, "direct"),
    ("bcast", "tpu", 256 << 20, False, None, "direct"),
    ("bcast", "tpu", 256 << 20, False, _SCATTER_ALLGATHER_RULES,
     "scatter_allgather"),
])
def test_decision_fixed_table_structure(func, platform, nbytes, multihost,
                                        dyn, want):
    # last-match-wins over (min_comm_size, min_bytes) thresholds; the
    # dynamic-rules file still overrides the fixed table
    assert decision.decide(func, 8, nbytes, multihost, dyn,
                           platform=platform) == want


def test_decision_malformed_rules_skipped():
    dyn = {"allreduce": {"algorithm_rules": [["0", "0", "ring"],
                                             [0, 0, "rabenseifner"]]}}
    # string thresholds are skipped, well-formed rules still apply
    assert decision.decide("allreduce", 8, 64, False, dyn) == \
        "rabenseifner"


def test_decision_dynamic_rules_override():
    dyn = {"allgather": {"algorithm_rules": [[0, 0, "ring"],
                                             [4, 1024, "bruck"]]}}
    assert decision.decide("allgather", 2, 64, False, dyn) == "ring"
    assert decision.decide("allgather", 8, 4096, False, dyn) == "bruck"


def test_non_commutative_falls_back_to_direct(mpi, world, alg):
    # A non-commutative user op must not run a reordering schedule.
    rows, x = _rank_data(world, (3,), seed=9)
    # "take the right operand" is associative but NOT commutative: an
    # ordered left fold yields the highest rank's data; a reordering
    # schedule would yield some other rank's.
    f = mpi.op_create(lambda a, b: b, commute=False)
    alg("allreduce", "ring")
    y = np.asarray(world.allreduce(x, f))
    assert np.allclose(y[0], rows[world.size - 1], atol=1e-6)


@pytest.mark.parametrize("root", [0, 5])
def test_reduce_knomial(mpi, world, alg, root):
    rows, x = _rank_data(world, (4,), seed=11)
    alg("reduce", "knomial")
    y = np.asarray(world.reduce(x, mpi.SUM, root))
    assert np.allclose(y[root], np.sum(rows, axis=0), atol=1e-4)
    y2 = np.asarray(world.reduce(x, mpi.MAX, root))
    assert np.allclose(y2[root], np.max(rows, axis=0))


def test_barrier_tree(mpi, world, alg):
    alg("barrier", "tree")
    for _ in range(3):
        world.barrier()


def test_neighborexchange_demotes_on_odd_size(mpi, world, alg):
    """EVEN_ONLY gate: an odd-size sub-communicator silently runs the
    direct lowering instead."""
    n = world.size
    sub = world.split([0] * 3 + [1] * (n - 3))[0]   # size 3
    alg("allgather", "neighborexchange")
    rows = [np.full((2,), float(r)) for r in range(3)]
    y = np.asarray(sub.allgather(sub.stack(rows)))
    for r in range(3):
        assert np.allclose(y[r], np.stack(rows))


def test_pipeline_bcast_segments(mpi, world, alg):
    """Pipeline uses multiple segments once the payload passes segsize."""
    alg("bcast", "pipeline")
    var.var_set("coll_xla_segsize", 64)
    try:
        rows, x = _rank_data(world, (256,), seed=3)
        y = np.asarray(world.bcast(x, root=1))
        for r in range(world.size):
            assert np.allclose(y[r], rows[1], atol=1e-6)
    finally:
        var.var_set("coll_xla_segsize", 1 << 20)


def test_reduce_scatter_recursive_halving(mpi, world, alg):
    n = world.size
    rows = [np.random.default_rng(10 + r).standard_normal((n, 3))
            .astype(np.float32) for r in range(n)]
    x = world.stack(rows)
    alg("reduce_scatter_block", "recursive_halving")
    y = np.asarray(world.reduce_scatter_block(x, mpi.SUM))
    want = np.sum(rows, axis=0)               # (n, 3)
    for r in range(n):
        assert np.allclose(y[r], want[r], atol=1e-4)


def test_reduce_scatter_recursive_halving_max(mpi, world, alg):
    # a non-sum commutative op through the same halving schedule
    n = world.size
    rows = [np.random.default_rng(20 + r).standard_normal((n, 2))
            .astype(np.float32) for r in range(n)]
    x = world.stack(rows)
    alg("reduce_scatter_block", "recursive_halving")
    y = np.asarray(world.reduce_scatter_block(x, mpi.MAX))
    want = np.max(rows, axis=0)
    for r in range(n):
        assert np.allclose(y[r], want[r])


def test_alltoall_bruck(mpi, world, alg):
    n = world.size
    rows = [np.arange(n * 2, dtype=np.float32).reshape(n, 2) + 100 * r
            for r in range(n)]
    x = world.stack(rows)
    alg("alltoall", "bruck")
    y = np.asarray(world.alltoall(x))
    for r in range(n):
        for s in range(n):
            assert np.allclose(y[r, s], rows[s][r])


@pytest.mark.parametrize("opname,ref", [("SUM", np.add),
                                        ("MAX", np.maximum)])
def test_scan_recursive_doubling(mpi, world, alg, opname, ref):
    rows, x = _rank_data(world, (6,), seed=31)
    alg("scan", "recursive_doubling")
    y = np.asarray(world.scan(x, getattr(mpi, opname)))
    acc = rows[0].copy()
    assert np.allclose(y[0], acc, atol=1e-4)
    for r in range(1, world.size):
        acc = ref(acc, rows[r])
        assert np.allclose(y[r], acc, atol=1e-4), r


def test_exscan_recursive_doubling(mpi, world, alg):
    rows, x = _rank_data(world, (4,), seed=32)
    alg("scan", "recursive_doubling")
    y = np.asarray(world.exscan(x, mpi.SUM))
    acc = rows[0].copy()
    for r in range(1, world.size):
        assert np.allclose(y[r], acc, atol=1e-4), r
        acc = acc + rows[r]


def test_scan_rd_matches_direct_exactly_ordered(mpi, world, alg):
    # rd-scan folds the contiguous left range IN FRONT of the local
    # value, so it is order-preserving: valid for non-commutative
    # combines (unlike the REORDERING allreduce schedules)
    rows, x = _rank_data(world, (3,), seed=33)
    alg("scan", "recursive_doubling")
    y_rd = np.asarray(world.scan(x, mpi.SUM))
    alg("scan", "direct")
    y_dir = np.asarray(world.scan(x, mpi.SUM))
    assert np.allclose(y_rd, y_dir, atol=1e-5)


def test_scan_rd_allowed_for_non_commutative(mpi, world, alg):
    # rd-scan is ORDER_PRESERVING: unlike the allreduce schedules, a
    # non-commutative op must NOT demote it — and the ordered result
    # must match the direct lowering's left fold.
    f = mpi.op_create(lambda a, b: b, commute=False)   # right-take
    rows, x = _rank_data(world, (3,), seed=41)
    alg("scan", "recursive_doubling")
    y = np.asarray(world.scan(x, f))
    for r in range(world.size):
        # left fold of right-take over ranks 0..r = rank r's own data
        assert np.allclose(y[r], rows[r], atol=1e-6), r
    assert ("scan", "recursive_doubling") in decision.ORDER_PRESERVING


def test_scan_rd_on_odd_size_subcomm(mpi, world, alg):
    # POW2_EXEMPT: scan's recursive doubling handles any size — an
    # odd-sized sub-communicator must still run it (allreduce's
    # same-named schedule stays pow2-only)
    colors = [0, 0, 0] + [1] * (world.size - 3)
    sub = world.split(colors)[0]
    assert sub.size == 3
    rows = [np.full(4, r + 1, np.float32) for r in range(3)]
    x = sub.stack(rows)
    alg("scan", "recursive_doubling")
    y = np.asarray(sub.scan(x, mpi.SUM))
    acc = rows[0].copy()
    assert np.allclose(y[0], acc)
    for r in range(1, 3):
        acc = acc + rows[r]
        assert np.allclose(y[r], acc), r


def test_allgather_sparbit(mpi, world, alg):
    rows, x = _rank_data(world, (3,), seed=21)
    alg("allgather", "sparbit")
    y = np.asarray(world.allgather(x))
    want = np.stack(rows)
    for r in range(world.size):
        assert np.allclose(y[r], want, atol=1e-6), r


def test_reduce_scatter_butterfly(mpi, world, alg):
    rows, x = _rank_data(world, (world.size, 4), seed=22)
    alg("reduce_scatter_block", "butterfly")
    y = np.asarray(world.reduce_scatter_block(x, mpi.SUM))
    want = np.sum(rows, axis=0)          # (n, 4): row r -> rank r
    for r in range(world.size):
        assert np.allclose(y[r], want[r], atol=1e-4), r
    ymax = np.asarray(world.reduce_scatter_block(x, mpi.MAX))
    wmax = np.max(rows, axis=0)
    for r in range(world.size):
        assert np.allclose(ymax[r], wmax[r]), r


def test_reduce_scatter_butterfly_odd_subcomm(mpi, world, alg):
    """The registry row butterfly exists for: halving on a NON-power-
    of-two member count (recursive_halving demotes there)."""
    n = world.size
    if n < 3:
        pytest.skip("needs >= 3 ranks")
    subs = world.split([0] * 3 + [mpi.UNDEFINED] * (n - 3))
    sub = subs[0]
    assert sub is not None and sub.size == 3
    rng = np.random.default_rng(23)
    rows = [rng.standard_normal((3, 2)).astype(np.float32) + r
            for r in range(3)]
    x = sub.stack(rows)
    alg("reduce_scatter_block", "butterfly")
    y = np.asarray(sub.reduce_scatter_block(x, mpi.SUM))
    want = np.sum(rows, axis=0)
    for r in range(3):
        assert np.allclose(y[r], want[r], atol=1e-4), r


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
