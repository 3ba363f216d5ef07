"""Root-targeted reduce/gather/scatter lowerings.

The round-1 aliases (reduce -> allreduce, gather -> allgather) are now
the latency-regime choice only; above the decision threshold the xla
component emits genuine root-directed schedules:

- reduce: psum_scatter + binomial collect into root
  (ompi_coll_base_reduce_intra_redscat_gather) — half the alias's wire
  traffic;
- gather: binomial block-doubling tree toward root
  (ompi_coll_base_gather_intra_binomial) — 1/n the aggregate bytes;
- scatter: binomial block-halving fan-out from root
  (ompi_coll_base_scatter_intra_binomial).

Each is validated against NumPy for every root, on the 8-rank world and
on a 6-rank (non-power-of-two) subcommunicator, plus the runtime D2D
``gather_root``/``scatter_root`` pair whose result is materialized on
root's device only (the true 1/n-memory property).
"""
import jax
import numpy as np
import pytest

import ompi_tpu as MPI
from ompi_tpu.mca import var


@pytest.fixture()
def force(request):
    """Force an algorithm var for the duration of a test."""
    done = []

    def _set(name, value):
        done.append(name)
        var.var_set(name, value)
    yield _set
    for name in done:
        var.var_set(name, "auto")


@pytest.fixture()
def comm6(world):
    """A 6-rank (non-pow2) subcommunicator of the 8-rank world."""
    colors = [0] * 6 + [MPI.UNDEFINED] * (world.size - 6)
    return world.split(colors)[0]


def _reduce_case(comm, force, rng):
    n = comm.size
    force("coll_xla_reduce_algorithm", "rabenseifner_root")
    x = rng.standard_normal((n, 37)).astype(np.float32)   # non-divisible
    for root in range(n):
        y = comm.reduce(comm.stack(list(x)), MPI.SUM, root=root)
        np.testing.assert_allclose(comm.shard(y, root), x.sum(0),
                                   rtol=1e-4, atol=1e-5)


def _gather_case(comm, force, rng):
    n = comm.size
    force("coll_xla_gather_algorithm", "binomial")
    x = rng.standard_normal((n, 5)).astype(np.float32)
    for root in range(n):
        y = comm.gather(comm.stack(list(x)), root)
        np.testing.assert_allclose(comm.shard(y, root), x, rtol=1e-6)


def _scatter_case(comm, force, rng):
    n = comm.size
    force("coll_xla_scatter_algorithm", "binomial")
    chunks = rng.standard_normal((n, 4)).astype(np.float32)
    for root in range(n):
        send = np.zeros((n, n, 4), dtype=np.float32)
        send[root] = chunks
        y = comm.scatter(comm.stack(list(send)), root)
        for r in range(n):
            np.testing.assert_allclose(comm.shard(y, r), chunks[r],
                                       rtol=1e-6)


def test_reduce_rabenseifner_root(world, force, rng):
    _reduce_case(world, force, rng)


def test_reduce_rabenseifner_root_non_pow2(comm6, force, rng):
    _reduce_case(comm6, force, rng)


def test_gather_binomial(world, force, rng):
    _gather_case(world, force, rng)


def test_gather_binomial_non_pow2(comm6, force, rng):
    _gather_case(comm6, force, rng)


def test_scatter_binomial(world, force, rng):
    _scatter_case(world, force, rng)


def test_scatter_binomial_non_pow2(comm6, force, rng):
    _scatter_case(comm6, force, rng)


def test_reduce_non_sum_falls_back(world, force, rng):
    """MAX has no psum_scatter; selection must degrade to alias and
    still be correct."""
    force("coll_xla_reduce_algorithm", "rabenseifner_root")
    n = world.size
    x = rng.standard_normal((n, 9)).astype(np.float32)
    y = world.reduce(world.stack(list(x)), MPI.MAX, root=3)
    np.testing.assert_allclose(world.shard(y, 3), x.max(0), rtol=1e-6)


def test_distinct_cache_keys_per_root(world, force, rng):
    """Distinct executables per root."""
    force("coll_xla_gather_algorithm", "binomial")
    n = world.size
    x = world.stack(list(rng.standard_normal((n, 5)).astype(np.float32)))
    world.gather(x, 0)
    world.gather(x, 1)
    xmod = world.c_coll["gather"].device
    keys = [k for k in xmod._cache if k[0] == "gather"]
    roots = {k[-2] for k in keys}         # (..., n, root, alg)
    assert {0, 1} <= roots, keys


def test_gather_root_memory_locality(world, rng):
    """gather_root materializes the result on root's device ONLY —
    non-root devices hold nothing (the 1/n-memory property the
    in-graph stacked gather cannot express)."""
    n = world.size
    x = rng.standard_normal((n, 6)).astype(np.float32)
    for root in (0, n - 1):
        y = world.gather_root(world.stack(list(x)), root)
        assert y.shape == (n, 6)
        assert y.sharding.device_set == {world.devices[root]}
        np.testing.assert_allclose(np.asarray(y), x, rtol=1e-6)


def test_scatter_root_roundtrip(world, rng):
    n = world.size
    chunks = rng.standard_normal((n, 3)).astype(np.float32)
    st = world.scatter_root(chunks, root=2)
    assert st.sharding.is_equivalent_to(world.sharding, st.ndim)
    for r in range(n):
        np.testing.assert_allclose(world.shard(st, r), chunks[r],
                                   rtol=1e-6)
    # round-trip: gather_root(scatter_root(c)) == c
    back = world.gather_root(st, root=2)
    np.testing.assert_allclose(np.asarray(back), chunks, rtol=1e-6)


def test_auto_threshold_switches(world, tmp_path, rng):
    """Auto selection picks the root-targeted schedule above the rule
    threshold and the alias below it. On the CPU test platform the
    fixed table's symmetric fallback would mask the threshold logic, so
    the tuned dynamic-rules file (which decide() consults FIRST,
    bypassing platform fallbacks) carries the 64 KiB rule — also
    covering the dynamic-rules path itself."""
    import json
    rules = tmp_path / "rules.json"
    rules.write_text(json.dumps({"reduce": {"algorithm_rules": [
        [0, 0, "alias"], [0, 64 << 10, "rabenseifner_root"]]}}))
    var.var_set("coll_tuned_dynamic_rules", str(rules))
    try:
        n = world.size
        xmod = world.c_coll["reduce"].device
        for elems, want in ((16, "alias"),
                            (32 * 1024, "rabenseifner_root")):
            x = rng.standard_normal((n, elems)).astype(np.float32)
            nbytes = elems * 4
            assert xmod._algorithm("reduce", nbytes, True) == want
            y = world.reduce(world.stack(list(x)), MPI.SUM, root=1)
            np.testing.assert_allclose(world.shard(y, 1), x.sum(0),
                                       rtol=1e-3, atol=1e-4)
        keys = [k for k in xmod._cache
                if k[0] == "reduce" and "rabenseifner_root" in k]
        assert keys, "threshold never selected the root-targeted path"
    finally:
        var.var_set("coll_tuned_dynamic_rules", "")


def _compressed_ring_case(comm, rng, elems, segsize):
    """The compressed allreduce's segmented quantized ring
    (coll_base_allreduce.c:345-357 chains, coll/compressed) at a size
    that splits into several segments with a tiny forced segsize:
    within the codec's error bound, the same on every rank."""
    var.var_set("mpi_base_compress", True)
    var.var_set("mpi_base_compress_min_bytes", 64)
    var.var_set("coll_xla_segsize", segsize)
    c = comm.dup()                      # selection sees compression on
    try:
        n = c.size
        x = rng.standard_normal((n, elems)).astype(np.float32)
        y = np.asarray(c.allreduce(c.stack(list(x)), MPI.SUM))
        dev = c.c_coll["allreduce"].device
        nsegs = [k[6] for k in dev._cache if k[0] == "c_allreduce"]
        assert nsegs and min(nsegs) > 1, nsegs
        ref = x.sum(0, dtype=np.float64)
        assert np.abs(y[0] - ref).max() <= 0.02 * np.abs(ref).max()
        for r in range(1, n):
            assert np.array_equal(y[0], y[r]), r
    finally:
        c.free()
        var.var_set("coll_xla_segsize", 1 << 20)
        var.var_set("mpi_base_compress_min_bytes", 4 << 20)
        var.var_set("mpi_base_compress", False)


def test_ring_segmented_allreduce(world, rng):
    _compressed_ring_case(world, rng, 515, 64)   # odd size, 5 segments


def test_ring_segmented_non_pow2(comm6, rng):
    _compressed_ring_case(comm6, rng, 100, 16)   # 5 segments
