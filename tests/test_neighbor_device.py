"""Device-native neighbor collectives: cart
and graph neighbor exchanges keep data on device, lowered to
edge-colored ppermute waves (topo/neighbor.py). The host NumPy paths
remain for host buffers; both must agree."""
import jax
import numpy as np
import pytest

import ompi_tpu as MPI
from ompi_tpu.accelerator import LOCUS_DEVICE, check_addr
from ompi_tpu.topo import neighbor as nbr


def _cart(world, dims, periods):
    return world.create_cart(dims, periods)


def test_halo_exchange_2d_cart_device(world):
    """The halo-exchange workhorse: 2-D cart, device buffers in, device
    buffers out, one collective-permute wave per edge color."""
    n = world.size
    cart = _cart(world, [2, n // 2], [True, False])
    x = cart.put(np.arange(n * 3, dtype=np.float32).reshape(n, 3))
    out = cart.neighbor_allgather(x)
    host = cart.neighbor_allgather(np.asarray(x))
    assert len(out) == n
    for r in range(n):
        assert isinstance(out[r], jax.Array)
        assert check_addr(out[r]) == LOCUS_DEVICE
        np.testing.assert_allclose(np.asarray(out[r]), host[r])

    # the lowering cached a compiled ppermute program for this shape
    # (on the plan, so a topo change invalidates both together)
    key = ("ag", x.shape, str(x.dtype))
    plan = cart._nbr_plan[1]
    assert key in plan._fns
    assert plan.n_waves >= 1
    # every wave is a valid collective-permute: unique dests, unique srcs
    for w in plan.waves:
        dsts = [d for _, d in w["perm"]]
        srcs = [s for s, _ in w["perm"]]
        assert len(set(dsts)) == len(dsts)
        assert len(set(srcs)) == len(srcs)


def test_neighbor_alltoall_device_matches_host(world):
    n = world.size
    cart = _cart(world, [n], [True])
    deg = len(cart.topo.neighbors(0))
    send = np.arange(n * deg * 2, dtype=np.float32).reshape(n, deg, 2)
    dev = cart.neighbor_alltoall(cart.put(send))
    host = cart.neighbor_alltoall(send)
    for r in range(n):
        assert check_addr(dev[r]) == LOCUS_DEVICE
        np.testing.assert_allclose(np.asarray(dev[r]), host[r])


def test_neighbor_alltoall_nonperiodic_edges(world):
    """Non-periodic boundaries: edge ranks have fewer neighbors; the
    device path must compress slots exactly like the host path."""
    n = world.size
    cart = _cart(world, [n], [False])
    deg = 2
    send = np.arange(n * deg * 2, dtype=np.float32).reshape(n, deg, 2)
    dev = cart.neighbor_alltoall(cart.put(send))
    host = cart.neighbor_alltoall(send)
    for r in range(n):
        assert dev[r].shape == host[r].shape, r
        np.testing.assert_allclose(np.asarray(dev[r]), host[r])


def test_neighbor_allgather_graph_device(world):
    """General graph (non-uniform degrees): a star topology."""
    n = world.size
    # rank 0 is the hub: edges 0<->k for all k
    index, edges = [], []
    cum = 0
    for r in range(n):
        nbrs = list(range(1, n)) if r == 0 else [0]
        cum += len(nbrs)
        index.append(cum)
        edges.extend(nbrs)
    g = world.create_graph(index, edges)
    x = g.put(np.arange(n * 2, dtype=np.float32).reshape(n, 2))
    dev = g.neighbor_allgather(x)
    host = g.neighbor_allgather(np.asarray(x))
    for r in range(n):
        assert check_addr(dev[r]) == LOCUS_DEVICE
        np.testing.assert_allclose(np.asarray(dev[r]), host[r])
    # hub receives n-1 buffers, leaves receive 1
    assert dev[0].shape[0] == n - 1
    assert dev[1].shape[0] == 1


def test_neighbor_allgatherv_device(world):
    n = world.size
    cart = _cart(world, [n], [True])
    import jax.numpy as jnp
    per_rank = [jnp.arange(r + 1, dtype=jnp.float32) for r in range(n)]
    dev = cart.neighbor_allgatherv(per_rank)
    host = cart.neighbor_allgatherv([np.asarray(a) for a in per_rank])
    for r in range(n):
        assert isinstance(dev[r], jax.Array)
        np.testing.assert_allclose(np.asarray(dev[r]), host[r])


def test_neighbor_alltoallv_device(world):
    n = world.size
    cart = _cart(world, [n], [True])
    import jax.numpy as jnp
    send_d = [[jnp.full((r + j + 1,), float(r * 10 + j))
               for j in range(len(cart.topo.neighbors(r)))]
              for r in range(n)]
    send_h = [[np.asarray(c) for c in row] for row in send_d]
    dev = cart.neighbor_alltoallv(send_d)
    host = cart.neighbor_alltoallv(send_h)
    for r in range(n):
        assert len(dev[r]) == len(host[r])
        for k in range(len(dev[r])):
            np.testing.assert_allclose(np.asarray(dev[r][k]),
                                       host[r][k])
