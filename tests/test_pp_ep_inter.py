"""Pipeline parallelism, expert parallelism, intercommunicators."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import ompi_tpu as MPI
from ompi_tpu.parallel import InGraphComm
from ompi_tpu.parallel.moe import init_moe_params, moe_apply
from ompi_tpu.parallel.pipeline import pipeline_apply

def _smap(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def test_pipeline_matches_sequential(world, rng):
    """4-stage pipeline of affine stages == sequential composition."""
    n, n_micro, bm, d = 4, 6, 2, 8
    mesh = Mesh(np.array(jax.devices()[:n]), ("pp",))
    pp = InGraphComm("pp", n)
    # stage r: x -> tanh(x @ W_r + b_r); params stacked (n, ...)
    W = rng.standard_normal((n, d, d)).astype(np.float32) * 0.3
    b = rng.standard_normal((n, d)).astype(np.float32) * 0.1
    x = rng.standard_normal((n_micro, bm, d)).astype(np.float32)

    def stage(params, a):
        w, bb = params
        return jnp.tanh(a @ w + bb)

    f = _smap(lambda w, bb, xm: pipeline_apply(stage, (w[0], bb[0]),
                                               xm, pp)[None],
              mesh, (P("pp"), P("pp"), P()), P("pp"))
    out = np.asarray(jax.jit(f)(W, b, x))[-1]    # valid on the last stage

    ref = x
    for r in range(n):
        ref = np.tanh(ref @ W[r] + b[r])
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=1e-5)


def test_moe_dispatch_combine(world, rng):
    """ep=4 MoE: every kept token's output equals its expert's MLP
    applied to it, weighted by the gate probability."""
    n, T, D, F, cap = 4, 8, 6, 12, 8
    mesh = Mesh(np.array(jax.devices()[:n]), ("ep",))
    ep = InGraphComm("ep", n)
    gate = rng.standard_normal((D, n)).astype(np.float32)
    W1 = rng.standard_normal((n, D, F)).astype(np.float32) * 0.2
    W2 = rng.standard_normal((n, F, D)).astype(np.float32) * 0.2
    X = rng.standard_normal((n, T, D)).astype(np.float32)  # per-rank tokens

    def body(x, w1, w2):
        params = {"gate": jnp.asarray(gate), "w1": w1[0], "w2": w2[0]}
        return moe_apply(x[0], params, ep, cap)[None]

    f = _smap(body, mesh, (P("ep"), P("ep"), P("ep")), P("ep"))
    out = np.asarray(jax.jit(f)(X, W1, W2))               # (n, T, D)

    # reference: route each rank's tokens to global experts
    p = np.exp(X @ gate - (X @ gate).max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    expert = p.argmax(-1)                                 # (n, T)
    prob = p.max(-1)
    for r in range(n):
        for t in range(T):
            e = expert[r, t]
            h = np.tanh  # placeholder; real is gelu — compute with jax
            ref = np.asarray(jax.nn.gelu(X[r, t] @ W1[e])) @ W2[e]
            np.testing.assert_allclose(out[r, t], ref * prob[r, t],
                                       rtol=2e-3, atol=2e-4)


def test_moe_capacity_drop(world, rng):
    """capacity=1 with tokens forced to one expert: only the first
    survives; the rest combine to zero."""
    n, T, D, F = 2, 4, 4, 8
    mesh = Mesh(np.array(jax.devices()[:n]), ("ep",))
    ep = InGraphComm("ep", n)
    gate = np.zeros((D, n), np.float32)
    gate[:, 1] = 10.0                    # everything routes to expert 1
    W1 = rng.standard_normal((n, D, F)).astype(np.float32) * 0.2
    W2 = rng.standard_normal((n, F, D)).astype(np.float32) * 0.2
    # positive tokens => positive gate logits => routing is uniform
    X = np.abs(rng.standard_normal((n, T, D))).astype(np.float32) + 0.1

    def body(x, w1, w2):
        params = {"gate": jnp.asarray(gate), "w1": w1[0], "w2": w2[0]}
        return moe_apply(x[0], params, ep, 1)[None]

    out = np.asarray(jax.jit(_smap(body, mesh,
                                   (P("ep"), P("ep"), P("ep")),
                                   P("ep")))(X, W1, W2))
    assert np.any(out[0, 0] != 0)                   # kept
    np.testing.assert_allclose(out[0, 1:], 0.0)     # dropped


def test_intercomm_basics(world):
    from ompi_tpu.core.intercomm import intercomm_create
    n = world.size
    subs = world.split([0 if r < n // 2 else 1 for r in range(n)])
    a, b = subs[0], subs[-1]
    inter = intercomm_create(a, b)
    assert inter.size == n // 2 and inter.remote_size == n - n // 2
    with pytest.raises(MPI.MPIError):
        intercomm_create(a, a)                      # overlapping groups

    la = a.stack([np.full(2, r + 1.0, np.float32) for r in range(a.size)])
    rb = b.stack([np.full(2, 10.0 * (r + 1), np.float32)
                  for r in range(b.size)])
    lo, ro = inter.allreduce(la, rb, MPI.SUM)
    # local side receives the REMOTE group's reduction and vice versa
    np.testing.assert_allclose(np.asarray(lo)[0],
                               sum(10.0 * (r + 1) for r in range(b.size)))
    np.testing.assert_allclose(np.asarray(ro)[0],
                               sum(r + 1.0 for r in range(a.size)))

    out = inter.bcast(np.asarray([5.0, 6.0], np.float32), root=1,
                      root_side="local")
    np.testing.assert_allclose(np.asarray(out)[0], [5.0, 6.0])
    assert out.shape[0] == b.size

    merged = inter.merge()
    assert merged.size == n
    merged_high = inter.merge(high=True)
    assert merged_high.group.world_ranks[:b.size] == b.group.world_ranks
    inter.barrier()


def test_intercomm_alltoall(world):
    from ompi_tpu.core.intercomm import intercomm_create
    n = world.size
    subs = world.split([0 if r < n // 2 else 1 for r in range(n)])
    a, b = subs[0], subs[-1]
    inter = intercomm_create(a, b)
    ls, rs = a.size, b.size
    la = np.arange(ls * rs * 1, dtype=np.float32).reshape(ls, rs, 1)
    rb = 100 + np.arange(rs * ls * 1, dtype=np.float32).reshape(rs, ls, 1)
    lo, ro = inter.alltoall(a.stack(list(la)), b.stack(list(rb)))
    lo, ro = np.asarray(lo), np.asarray(ro)
    for i in range(ls):
        for j in range(rs):
            assert ro[j, i, 0] == la[i, j, 0]
            assert lo[i, j, 0] == rb[j, i, 0]
