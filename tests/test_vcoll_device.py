"""Device-native v-collectives.

Round 1 padded ragged buffers on the host and returned lists of host
arrays. Round 2: device inputs are padded on device, the collective
result is sliced lazily, and every output is a device array — asserted
here via ``check_addr`` so a host round-trip regression fails loudly.
``reduce_scatter(counts)`` additionally must ride psum_scatter (its
wire bytes scale with N*max(counts), not with a full allreduce): its
executable cache must show a reduce_scatter_block entry, not just
allreduce ones.
"""
import jax
import jax.numpy as jnp
import numpy as np

import ompi_tpu as MPI
from ompi_tpu.accelerator import LOCUS_DEVICE, check_addr


def _dev(a):
    return jnp.asarray(np.asarray(a, dtype=np.float32))


def test_allgatherv_device(world, rng):
    n = world.size
    per = [_dev(rng.standard_normal(r + 1)) for r in range(n)]
    out = world.allgatherv(per)
    expect = np.concatenate([np.asarray(a) for a in per])
    assert len(out) == n
    for o in out:
        assert check_addr(o) == LOCUS_DEVICE, type(o)
        np.testing.assert_allclose(np.asarray(o), expect, rtol=1e-6)


def test_gatherv_device(world, rng):
    n = world.size
    per = [_dev(rng.standard_normal(2 * r + 1)) for r in range(n)]
    out = world.gatherv(per, root=n - 1)
    assert check_addr(out) == LOCUS_DEVICE
    np.testing.assert_allclose(
        np.asarray(out), np.concatenate([np.asarray(a) for a in per]),
        rtol=1e-6)


def test_scatterv_device(world, rng):
    n = world.size
    chunks = [_dev(rng.standard_normal(r + 2)) for r in range(n)]
    out = world.scatterv(chunks, root=1)
    assert len(out) == n
    for r, o in enumerate(out):
        assert check_addr(o) == LOCUS_DEVICE
        np.testing.assert_allclose(np.asarray(o), np.asarray(chunks[r]),
                                   rtol=1e-6)


def test_alltoallv_device(world, rng):
    n = world.size
    send = [[_dev(rng.standard_normal((i + j) % 3 + 1))
             for j in range(n)] for i in range(n)]
    recv = world.alltoallv(send)
    for j in range(n):
        for i in range(n):
            assert check_addr(recv[j][i]) == LOCUS_DEVICE
            np.testing.assert_allclose(np.asarray(recv[j][i]),
                                       np.asarray(send[i][j]), rtol=1e-6)


def test_alltoallv_host_inputs_still_work(world, rng):
    n = world.size
    send = [[rng.standard_normal(2).astype(np.float32)
             for _ in range(n)] for _ in range(n)]
    recv = world.alltoallv(send)
    for j in range(n):
        for i in range(n):
            np.testing.assert_allclose(np.asarray(recv[j][i]),
                                       send[i][j], rtol=1e-6)


def test_reduce_scatter_counts_device_and_scaled(world, rng):
    n = world.size
    counts = [r + 1 for r in range(n)]
    total = sum(counts)
    x = rng.standard_normal((n, total)).astype(np.float32)
    st = world.stack(list(x))
    before = dict(getattr(world.c_coll["reduce_scatter_block"],
                          "device", world.c_coll["reduce_scatter_block"]
                          )._cache)
    outs = world.reduce_scatter(st, counts, MPI.SUM)
    red = x.sum(0)
    off = 0
    for r, c in enumerate(counts):
        assert check_addr(outs[r]) == LOCUS_DEVICE
        np.testing.assert_allclose(np.asarray(outs[r]),
                                   red[off:off + c], rtol=1e-4,
                                   atol=1e-5)
        off += c
    # the lowering must be reduce_scatter_block (psum_scatter), not a
    # full allreduce: a new rsb executable appeared for the (n, n, m)
    # padded wire shape
    mod = world.c_coll["reduce_scatter_block"]
    xmod = getattr(mod, "device", mod)
    new = [k for k in xmod._cache
           if k[0] == "reduce_scatter_block" and k not in before]
    assert new, "reduce_scatter(counts) did not ride psum_scatter"


def test_reduce_scatter_counts_host_input(world, rng):
    n = world.size
    counts = [2] * n
    x = rng.standard_normal((n, 2 * n)).astype(np.float32)
    outs = world.reduce_scatter(x, counts, MPI.SUM)
    red = x.sum(0)
    for r in range(n):
        np.testing.assert_allclose(np.asarray(outs[r]),
                                   red[2 * r:2 * r + 2], rtol=1e-4,
                                   atol=1e-5)
