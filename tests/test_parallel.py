"""In-graph communicators + DP x TP training-step equivalence.

The equivalence test is the framework's strongest correctness statement:
a 2x2 (dp x tp) sharded training step through InGraphComm collectives
must produce the SAME loss and parameters as the plain single-device
step on the same global batch.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ompi_tpu.models import transformer as T
from ompi_tpu.parallel import InGraphComm

def _smap(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _mesh1d(n, name):
    return Mesh(np.array(jax.devices()[:n]), (name,))


def test_ingraph_allreduce_and_rank(world):
    mesh = _mesh1d(4, "r")
    c = InGraphComm("r", 4)

    def body(x):
        return c.allreduce(x) + c.rank()

    f = jax.jit(_smap(body, mesh, P("r"), P("r")))
    x = jnp.arange(4.0)[:, None]
    y = f(x)
    # each shard: sum(0..3)=6 plus its rank
    np.testing.assert_allclose(np.asarray(y)[:, 0], 6.0 + np.arange(4))


def test_ingraph_ring_shift(world):
    n = 4
    mesh = _mesh1d(n, "r")
    c = InGraphComm("r", n)
    f = jax.jit(_smap(lambda x: c.ring_shift(x, 1), mesh, P("r"), P("r")))
    x = jnp.arange(float(n))[:, None]
    y = np.asarray(f(x))[:, 0]
    np.testing.assert_allclose(y, np.roll(np.arange(float(n)), 1))


def test_ingraph_bcast_scan(world):
    n = 4
    mesh = _mesh1d(n, "r")
    c = InGraphComm("r", n)
    f = jax.jit(_smap(lambda x: (c.bcast(x, 2), c.scan(x)),
                      mesh, P("r"), (P("r"), P("r"))))
    x = jnp.arange(1.0, n + 1)[:, None]
    b, s = f(x)
    np.testing.assert_allclose(np.asarray(b)[:, 0], 3.0)
    np.testing.assert_allclose(np.asarray(s)[:, 0],
                               np.cumsum(np.arange(1.0, n + 1)))


def _tiny_cfg():
    return T.Config(vocab=32, d_model=16, n_heads=4, n_layers=2, d_ff=32,
                    seq=8, dtype=jnp.float32)


def test_dp_tp_train_step_matches_single_device(world, rng):
    cfg = _tiny_cfg()
    params = T.init_params(jax.random.PRNGKey(3), cfg)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (4, cfg.seq + 1)),
                         jnp.int32)

    batch = (tokens[:, :-1], tokens[:, 1:])

    # --- single-device reference step
    ref_params, ref_loss = jax.jit(
        lambda p, b: T.sgd_train_step(p, b, cfg, 1e-2))(params, batch)

    # --- dp=2 x tp=2 sharded step via InGraphComm
    from __graft_entry__ import _param_specs
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    specs = _param_specs(params, P)
    sharded = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs)
    b_sharded = tuple(jax.device_put(b, NamedSharding(mesh, P("dp")))
                      for b in batch)
    dp_c, tp_c = InGraphComm("dp", 2), InGraphComm("tp", 2)
    step = _smap(lambda p, b: T.sgd_train_step(p, b, cfg, 1e-2, dp_c, tp_c),
                 mesh, (specs, (P("dp"), P("dp"))), (specs, P()))
    new_params, loss = jax.jit(step)(sharded, b_sharded)

    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    flat_ref = jax.tree_util.tree_leaves(ref_params)
    flat_new = jax.tree_util.tree_leaves(new_params)
    for a, b in zip(flat_ref, flat_new):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-4, atol=2e-6)


def test_graft_entry_single_chip(world):
    from __graft_entry__ import entry
    fn, args = entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (2, 64, 256)
    assert bool(jnp.isfinite(out).all())


def test_graft_dryrun_multichip(world):
    from __graft_entry__ import dryrun_multichip
    dryrun_multichip(8)


def test_ring_attention_matches_full(world, rng):
    """Ring attention over sp=4 must equal plain full causal attention."""
    from ompi_tpu.parallel.ring_attention import ring_attention
    B, S, H, D, n = 2, 16, 2, 8, 4
    q = rng.standard_normal((B, S, H, D)).astype(np.float32)
    k = rng.standard_normal((B, S, H, D)).astype(np.float32)
    v = rng.standard_normal((B, S, H, D)).astype(np.float32)

    # reference: full causal attention
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    mask = np.tril(np.ones((S, S), bool))
    s = np.where(mask[None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bkhd->bqhd", p, v)

    mesh = _mesh1d(n, "sp")
    c = InGraphComm("sp", n)
    f = jax.jit(_smap(lambda a, b, d: ring_attention(a, b, d, c),
                      mesh, (P(None, "sp"),) * 3, P(None, "sp")))
    out = f(q, k, v)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-5)


def test_sp_train_step_matches_single_device(world, rng):
    """sp=2 sequence-parallel training step (ring attention + sp grad
    sync) equals the single-device step."""
    cfg = _tiny_cfg()
    params = T.init_params(jax.random.PRNGKey(5), cfg)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (2, cfg.seq + 1)),
                         jnp.int32)
    batch = (tokens[:, :-1], tokens[:, 1:])
    ref_params, ref_loss = jax.jit(
        lambda p, b: T.sgd_train_step(p, b, cfg, 1e-2))(params, batch)

    mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
    from __graft_entry__ import _param_specs
    specs = jax.tree_util.tree_map(lambda _: P(), params)
    sharded = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P())), params)
    b_sharded = tuple(jax.device_put(b, NamedSharding(mesh, P(None, "sp")))
                      for b in batch)
    sp_c = InGraphComm("sp", 2)
    step = _smap(lambda p, b: T.sgd_train_step(p, b, cfg, 1e-2,
                                               sp_comm=sp_c),
                 mesh, (specs, (P(None, "sp"), P(None, "sp"))),
                 (specs, P()))
    new_params, loss = jax.jit(step)(sharded, b_sharded)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(ref_params),
                    jax.tree_util.tree_leaves(new_params)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-4, atol=2e-6)


def test_allreduce_ring_and_hier_algorithms(world, rng):
    """The han-style hierarchical lowering must match the direct psum
    (algorithm registry parity)."""
    from ompi_tpu.mca import var
    n = world.size
    x = rng.standard_normal((n, 37)).astype(np.float32)   # odd size: pad
    buf = world.stack(list(x))
    import ompi_tpu as MPI
    direct = np.asarray(world.allreduce(buf, MPI.SUM))
    var.var_set("coll_xla_allreduce_algorithm", "hier")
    try:
        got = np.asarray(world.allreduce(buf, MPI.SUM))
    finally:
        var.var_set("coll_xla_allreduce_algorithm", "auto")
    np.testing.assert_allclose(got, direct, rtol=1e-5)
    # hier with a non-commutative op falls back to the ordered path
    op = MPI.op_create(lambda a, b: b, commute=False, name="take_right")
    var.var_set("coll_xla_allreduce_algorithm", "hier")
    try:
        got = np.asarray(world.allreduce(buf, op))
    finally:
        var.var_set("coll_xla_allreduce_algorithm", "auto")
    np.testing.assert_allclose(got[0], x[-1])


def test_ulysses_attention_matches_full(world, rng):
    """The all-to-all sequence-parallel schedule (two reshard
    all_to_alls + plain dense attention on a head subset) must equal
    full causal attention — and the ring variant — exactly."""
    from ompi_tpu.parallel.ulysses import ulysses_attention
    B, S, H, D, n = 2, 16, 4, 8, 4
    q = rng.standard_normal((B, S, H, D)).astype(np.float32)
    k = rng.standard_normal((B, S, H, D)).astype(np.float32)
    v = rng.standard_normal((B, S, H, D)).astype(np.float32)

    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    mask = np.tril(np.ones((S, S), bool))
    s = np.where(mask[None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bkhd->bqhd", p, v)

    mesh = _mesh1d(n, "sp")
    c = InGraphComm("sp", n)
    f = jax.jit(_smap(lambda a, b, d: ulysses_attention(a, b, d, c),
                      mesh, (P(None, "sp"),) * 3, P(None, "sp")))
    out = f(q, k, v)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4,
                               atol=2e-5)
    # cross-equivalence with the ring schedule: the two long-context
    # strategies must agree on the same inputs
    from ompi_tpu.parallel.ring_attention import ring_attention
    fr = jax.jit(_smap(lambda a, b, d: ring_attention(a, b, d, c),
                       mesh, (P(None, "sp"),) * 3, P(None, "sp")))
    np.testing.assert_allclose(np.asarray(out), np.asarray(fr(q, k, v)),
                               rtol=2e-4, atol=2e-5)


def test_ulysses_non_causal_and_head_guard(world, rng):
    from ompi_tpu.parallel.ulysses import ulysses_attention
    B, S, H, D, n = 1, 8, 4, 4, 4
    q = rng.standard_normal((B, S, H, D)).astype(np.float32)
    k = rng.standard_normal((B, S, H, D)).astype(np.float32)
    v = rng.standard_normal((B, S, H, D)).astype(np.float32)
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bkhd->bqhd", p, v)
    mesh = _mesh1d(n, "sp")
    c = InGraphComm("sp", n)
    f = jax.jit(_smap(
        lambda a, b, d: ulysses_attention(a, b, d, c, causal=False),
        mesh, (P(None, "sp"),) * 3, P(None, "sp")))
    np.testing.assert_allclose(np.asarray(f(q, k, v)), ref, rtol=2e-4,
                               atol=2e-5)
    # H=3 not divisible by 4 -> clear error, not silent corruption
    import pytest as _pt
    with _pt.raises(ValueError, match="divisible"):
        ulysses_attention(np.zeros((1, 2, 3, 4), np.float32),
                          np.zeros((1, 2, 3, 4), np.float32),
                          np.zeros((1, 2, 3, 4), np.float32), c)


def test_flash_attention_path_matches_dense(mpi, world):
    """The flagship's flash local-attention path (ops/flash_attention
    block kernel) is numerically the dense softmax attention."""
    import jax
    import jax.numpy as jnp
    from ompi_tpu.models import transformer as T
    cfg_d = T.Config(vocab=32, d_model=32, n_heads=4, n_layers=2,
                     d_ff=64, seq=16, dtype=jnp.float32)
    cfg_f = T.Config(vocab=32, d_model=32, n_heads=4, n_layers=2,
                     d_ff=64, seq=16, dtype=jnp.float32,
                     use_flash=True)
    params = T.init_params(jax.random.PRNGKey(3), cfg_d)
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 16), 0, 32)
    a = T.forward(params, toks, cfg_d)
    b = T.forward(params, toks, cfg_f)
    assert jnp.allclose(a, b, atol=2e-4), float(jnp.abs(a - b).max())


def test_pp_train_step_single_axis_matches_ref():
    """pp_train_step with pp=1 on a 1-device mesh reduces to the plain
    training step (same loss, same updated params)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from ompi_tpu.models import transformer as T
    from ompi_tpu.parallel import InGraphComm

    cfg = T.Config(vocab=32, d_model=32, n_heads=4, n_layers=2,
                   d_ff=64, seq=8, dtype=jnp.float32)
    key = jax.random.PRNGKey(0)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 9), 0, 32)
    batch = (toks[:, :-1], toks[:, 1:])

    flat = T.init_params(key, cfg)
    ref_p, ref_loss = jax.jit(
        lambda p, b: T.sgd_train_step(p, b, cfg, 1e-2))(flat, batch)

    pp_params = T.init_pp_params(key, cfg, pp=1)
    mesh = Mesh(np.array(jax.devices()[:1]), ("pp",))
    pp = InGraphComm("pp", 1)

    def step(p, i, t):
        return T.pp_train_step(p, (i, t), cfg, 1e-2, pp_comm=pp,
                               n_micro=2)
    smap = jax.shard_map(step, mesh=mesh,
                         in_specs=(P(), P(), P()),
                         out_specs=(P(), P()), check_vma=False)
    new_p, loss = jax.jit(smap)(pp_params, *batch)
    assert jnp.allclose(loss, ref_loss, atol=1e-5), (loss, ref_loss)
    # spot-check one stage weight evolved identically to the flat ref
    w_ref = ref_p["tp"]["layers"][1]["w1"]
    w_pp = new_p["stage"][1]["w1"][0]
    assert jnp.allclose(w_ref, w_pp, atol=1e-5), \
        float(jnp.abs(w_ref - w_pp).max())


def test_moe_grads_keep_replicated_params_replicated():
    """The Megatron f operator on the MoE path: gradients of
    tp-replicated params (norms, gate, rep) must be IDENTICAL across
    tp ranks — a per-rank partial would silently diverge them."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from ompi_tpu.models import transformer as T
    from ompi_tpu.parallel import InGraphComm
    from __graft_entry__ import _stage_specs

    cfg = T.Config(vocab=32, d_model=16, n_heads=4, n_layers=2,
                   d_ff=32, seq=8, dtype=jnp.float32, moe=True,
                   moe_experts=2)
    params = T.init_pp_params(jax.random.PRNGKey(0), cfg, pp=1)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2),
                ("pp", "tp"))
    specs = _stage_specs(params, cfg, P)
    params = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 9), 0, 32)
    pp = InGraphComm("pp", 1)
    tp = InGraphComm("tp", 2)

    def divergence(p, i, t):
        def loss(p):
            # reuse the step's loss plumbing via grad of pp_train_step
            # internals: one forward through the layer stack
            x = p["rep"]["emb"][i].astype(cfg.dtype)
            causal = jnp.tril(jnp.ones((i.shape[1],) * 2, jnp.bool_))
            for lay in p["stage"]:
                lr_ = {"ln1": lay["ln1"][0], "ln2": lay["ln2"][0]}
                lt_ = {k: v[0] for k, v in lay.items()
                       if k not in ("ln1", "ln2")}
                x = T._layer(x, lr_, lt_, causal, cfg, tp, None, tp)
            h = T._rmsnorm(x, p["rep"]["ln_f"])
            logits = jnp.einsum("bsd,vd->bsv",
                                h.astype(jnp.float32), p["rep"]["emb"])
            lp = jax.nn.log_softmax(logits, axis=-1)
            return jnp.mean(-jnp.take_along_axis(lp, t[..., None],
                                                 axis=-1))
        g = jax.grad(loss)(p)
        reps = [g["rep"]["emb"], g["rep"]["ln_f"]] + \
            [lay[k] for lay in g["stage"] for k in ("ln1", "ln2")]
        div = sum(jnp.sum((x - tp.pmean(x)) ** 2) for x in reps)
        return tp.pmean(div)

    smap = jax.shard_map(divergence, mesh=mesh,
                         in_specs=(specs, P(), P()),
                         out_specs=P(), check_vma=False)
    div = jax.jit(smap)(params, toks[:, :-1], toks[:, 1:])
    assert float(div) < 1e-9, float(div)


def test_pp2_train_step_matches_flat_reference():
    """pp=2 pipeline training matches the flat step EXACTLY: stage
    stacking, activation handoff, per-stage gradient routing, and the
    rep-grad pp-sum all verified against the single-device math."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from ompi_tpu.models import transformer as T
    from ompi_tpu.parallel import InGraphComm

    cfg = T.Config(vocab=32, d_model=32, n_heads=4, n_layers=2,
                   d_ff=64, seq=8, dtype=jnp.float32)
    key = jax.random.PRNGKey(0)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 9), 0, 32)
    batch = (toks[:, :-1], toks[:, 1:])

    flat = T.init_params(key, cfg)
    ref_p, ref_loss = jax.jit(
        lambda p, b: T.sgd_train_step(p, b, cfg, 1e-2))(flat, batch)

    pp_params = T.init_pp_params(key, cfg, pp=2)
    mesh = Mesh(np.array(jax.devices()[:2]), ("pp",))
    spec = {"rep": jax.tree_util.tree_map(lambda _: P(),
                                          pp_params["rep"]),
            "stage": [{k: P("pp") for k in slot}
                      for slot in pp_params["stage"]]}
    pp_params = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        pp_params, spec)
    pp = InGraphComm("pp", 2)

    def step(p, i, t):
        return T.pp_train_step(p, (i, t), cfg, 1e-2, pp_comm=pp,
                               n_micro=2)
    smap = jax.shard_map(step, mesh=mesh,
                         in_specs=(spec, P(), P()),
                         out_specs=(spec, P()), check_vma=False)
    new_p, loss = jax.jit(smap)(pp_params, *batch)
    assert jnp.allclose(loss, ref_loss, atol=1e-5), (loss, ref_loss)
    # layer 0 lives on stage 0 slot 0; layer 1 on stage 1 slot 0
    for li, (s, j) in ((0, (0, 0)), (1, (1, 0))):
        w_ref = ref_p["tp"]["layers"][li]["w1"]
        w_pp = new_p["stage"][j]["w1"][s]
        assert jnp.allclose(w_ref, w_pp, atol=1e-5), (li,)
        n_ref = ref_p["rep"]["layers"][li]["ln1"]
        n_pp = new_p["stage"][j]["ln1"][s]
        assert jnp.allclose(n_ref, n_pp, atol=1e-5), (li,)
    assert jnp.allclose(ref_p["rep"]["emb"], new_p["rep"]["emb"],
                        atol=1e-5)


def test_moe_grads_replicated_on_dedicated_ep_axis():
    """The MoE f operator must ride the EP axis itself: with tp absent
    and experts on a dedicated axis, replicated-param gradients must
    still be identical across expert ranks."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from ompi_tpu.models import transformer as T
    from ompi_tpu.parallel import InGraphComm

    cfg = T.Config(vocab=32, d_model=16, n_heads=4, n_layers=1,
                   d_ff=32, seq=8, dtype=jnp.float32, moe=True,
                   moe_experts=2)
    params = T.init_params(jax.random.PRNGKey(0), cfg, tp=2)
    mesh = Mesh(np.array(jax.devices()[:2]), ("ep",))
    lay_spec = {"wqkv": P(), "wo": P(),
                "gate": P(), "w1": P("ep"), "w2": P("ep")}
    spec = {"rep": jax.tree_util.tree_map(lambda _: P(),
                                          params["rep"]),
            "tp": {"layers": [dict(lay_spec)]}}
    params = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, spec)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0, 32)
    ep = InGraphComm("ep", 2)

    def divergence(p, i, t):
        def loss(p):
            logits = T.forward(p, i, cfg, tp_comm=None, ep_comm=ep)
            lp = jax.nn.log_softmax(logits, axis=-1)
            return jnp.mean(-jnp.take_along_axis(lp, t[..., None],
                                                 axis=-1))
        g = jax.grad(loss)(p)
        reps = [g["rep"]["emb"], g["rep"]["ln_f"],
                g["tp"]["layers"][0]["wqkv"],
                g["rep"]["layers"][0]["ln1"]]
        div = sum(jnp.sum((x - ep.pmean(x)) ** 2) for x in reps)
        return ep.pmean(div)

    smap = jax.shard_map(divergence, mesh=mesh,
                         in_specs=(spec, P(), P()),
                         out_specs=P(), check_vma=False)
    div = jax.jit(smap)(params, toks[:, :-1], toks[:, 1:])
    assert float(div) < 1e-9, float(div)
