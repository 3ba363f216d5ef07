"""The flagship causal transformer LM — every parallelism strategy in
ONE model.

This model exists to exercise the framework the way real users exercise
the reference: a training step whose every cross-device byte moves
through ``ompi_tpu.parallel.InGraphComm`` collectives — the §2.6
strategy table made concrete in a single composed program:

- **tp**: attention heads / MLP hidden sharded Megatron-style
  (column/row pairs; psum after row-parallel matmuls).
- **sp**: ring attention over the sequence axis (K/V circulate by
  ppermute, flash-style online softmax).
- **dp**: gradient allreduce (pmean) over the batch axis.
- **pp**: GPipe microbatch pipelining over layer stages
  (``pipeline_apply``: activations ring-shift between stages inside a
  ``lax.scan``; backward is AD through the shifts).
- **ep**: Switch-style MoE MLPs with one expert per rank of the
  expert axis (``moe_apply``: two alltoalls dispatch/combine).
- local attention lowers through ``ops/flash_attention``'s
  differentiable online-softmax fold when ``cfg.use_flash`` (the
  pallas kernel serves forward-only uses until a custom VJP lands).

Layout: bfloat16 activations, float32 params — MXU-friendly.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ompi_tpu.parallel import InGraphComm
from ompi_tpu.parallel.moe import moe_apply
from ompi_tpu.parallel.pipeline import pipeline_apply
from ompi_tpu.parallel.ring_attention import ring_attention


@dataclass(frozen=True)
class Config:
    vocab: int = 256
    d_model: int = 128
    n_heads: int = 8
    n_layers: int = 2
    d_ff: int = 512
    seq: int = 64
    dtype: Any = jnp.bfloat16
    moe: bool = False            # MLPs become Switch MoE blocks
    moe_experts: int = 0         # expert count (0: the tp arg/axis)
    moe_capacity: int = 0        # per-(src, expert) slots; 0 = auto
    use_flash: bool = False      # local attention via ops/flash

    @property
    def d_head(self) -> int:
        return self.d_model // self.n_heads


def init_params(key, cfg: Config, tp: int = 1) -> Dict:
    """Initialize params. ``tp`` > 1 returns the *local* shard for one tp
    rank-size (heads and d_ff divided by tp); with shard_map the same
    code initializes per-shard params inside the mesh.

    Pytree layout separates replicated from tp-sharded leaves so the
    gradient-sync rule (psum over dp for all; also over tp for
    replicated) is explicit.
    """
    assert cfg.n_heads % tp == 0 and cfg.d_ff % tp == 0
    hl, fl = cfg.n_heads // tp, cfg.d_ff // tp
    d, dh = cfg.d_model, cfg.d_head
    ks = jax.random.split(key, 2 + 4 * cfg.n_layers)
    scale = lambda fan_in: 1.0 / jnp.sqrt(fan_in)
    rep = {
        "emb": jax.random.normal(ks[0], (cfg.vocab, d), jnp.float32) * 0.02,
        "ln_f": jnp.ones((d,), jnp.float32),
    }
    rep["layers"] = [{"ln1": jnp.ones((d,), jnp.float32),
                      "ln2": jnp.ones((d,), jnp.float32)}
                     for _ in range(cfg.n_layers)]
    tp_layers = []
    for i in range(cfg.n_layers):
        k1, k2, k3, k4 = ks[2 + 4 * i: 6 + 4 * i]
        lay = {
            "wqkv": jax.random.normal(k1, (d, 3, hl, dh), jnp.float32)
            * scale(d),
            "wo": jax.random.normal(k2, (hl, dh, d), jnp.float32)
            * scale(cfg.n_heads * dh),
        }
        if cfg.moe:
            # Switch MoE: gate replicated; w1/w2 hold ALL experts on a
            # leading expert axis (sharded over the expert-axis ranks
            # by the caller; the expert axis reuses tp, so `tp` here
            # is n_experts and each rank's shard is its one expert)
            k5 = jax.random.fold_in(k4, 7)
            n_exp = cfg.moe_experts or max(tp, 1)
            lay["gate"] = jax.random.normal(
                k5, (d, n_exp), jnp.float32) * 0.02
            lay["w1"] = jax.random.normal(
                k3, (n_exp, d, cfg.d_ff), jnp.float32) * scale(d)
            lay["w2"] = jax.random.normal(
                k4, (n_exp, cfg.d_ff, d), jnp.float32) * scale(cfg.d_ff)
        else:
            lay["w1"] = jax.random.normal(
                k3, (d, fl), jnp.float32) * scale(d)
            lay["w2"] = jax.random.normal(
                k4, (fl, d), jnp.float32) * scale(cfg.d_ff)
        tp_layers.append(lay)
    return {"rep": rep, "tp": {"layers": tp_layers}}


def _rmsnorm(x, g):
    x32 = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + 1e-6)
    return (x32 * r * g).astype(x.dtype)


def _flash_causal(q, k, v, cfg: Config):
    """Single-block causal attention through the flash online-softmax
    fold (ops/flash_attention): mode 1 is exactly the causal diagonal
    block."""
    from ompi_tpu.ops.flash_attention import fold_jnp
    B, S, H, D = q.shape
    scale = jnp.asarray(cfg.d_head, jnp.float32) ** -0.5
    qf = (jnp.transpose(q, (0, 2, 1, 3)).reshape(B * H, S, D)
          .astype(jnp.float32) * scale)
    kf = jnp.transpose(k, (0, 2, 1, 3)).reshape(B * H, S, D) \
        .astype(jnp.float32)
    vf = jnp.transpose(v, (0, 2, 1, 3)).reshape(B * H, S, D) \
        .astype(jnp.float32)
    o = jnp.zeros_like(qf)
    m = jnp.full((B * H, S), -1e30, jnp.float32)
    l = jnp.zeros((B * H, S), jnp.float32)
    # the TRAINING path needs AD: the jnp fold is the kernel's math,
    # differentiable and XLA-fused (the Pallas kernel has no VJP)
    o, m, l = fold_jnp(qf, kf, vf, o, m, l, 1)
    o = o / jnp.where(l == 0.0, 1.0, l)[..., None]
    return jnp.transpose(o.reshape(B, H, S, D),
                         (0, 2, 1, 3)).astype(q.dtype)


def _attend(q, k, v, causal, cfg: Config,
            sp_comm: Optional[InGraphComm]):
    """The attention dispatch: ring attention over sp when sequence-
    parallel, flash kernel or dense softmax locally otherwise."""
    if sp_comm is not None:
        return ring_attention(q, k, v, sp_comm, causal=True)
    if cfg.use_flash:
        return _flash_causal(q, k, v, cfg)
    att = jnp.einsum("bshk,bthk->bhst", q, k) / jnp.sqrt(
        jnp.asarray(cfg.d_head, cfg.dtype))
    att = jnp.where(causal[None, None], att, -1e9)
    att = jax.nn.softmax(att.astype(jnp.float32), axis=-1).astype(
        cfg.dtype)
    return jnp.einsum("bhst,bthk->bshk", att, v)


def _mlp(x, lt: Dict, cfg: Config, tp_comm: Optional[InGraphComm],
         ep_comm: Optional[InGraphComm]):
    """The feed-forward dispatch: Switch MoE over the expert axis when
    configured, Megatron column/row pair otherwise. ``x`` is the
    ln2-normalized input (already copy_in'd for tp)."""
    if cfg.moe and ep_comm is not None:
        # the Megatron f operator over the EXPERT axis — identity
        # forward, psum backward. Each expert rank consumes only its
        # token shard (dynamic_slice below); without the backward psum
        # every upstream cotangent (ln/wqkv/wo/emb) would be a
        # per-rank partial and "replicated" params would silently
        # diverge — regardless of whether ep rides the tp axis
        x = ep_comm.copy_in(x)
        B, S, D = x.shape
        E = ep_comm._size
        assert cfg.moe_experts in (0, E), (
            f"moe_experts={cfg.moe_experts} != expert axis size {E}: "
            f"extra experts would be silently dead weights")
        T = B * S
        assert T % E == 0, "tokens must divide the expert axis"
        Tl = T // E
        r = ep_comm.rank()
        flat = x.reshape(T, D)
        # The expert axis rides the tp axis, where activations are
        # REPLICATED: each expert rank takes its own token shard
        # (token parallelism), runs the alltoall dispatch/combine, and
        # the shards reassemble with one psum — so the output is
        # replicated again for the row-parallel world downstream.
        shard = jax.lax.dynamic_slice_in_dim(flat, r * Tl, Tl, 0)
        # w1/w2 carry a leading expert axis sharded over the expert
        # ranks: inside shard_map the local shard is (1, D, F)
        w1, w2 = lt["w1"], lt["w2"]
        if w1.ndim == 3:
            w1, w2 = w1[0], w2[0]
        cap = cfg.moe_capacity or max(1, 2 * Tl // E)
        moe_params = {"gate": lt["gate"].astype(x.dtype),
                      "w1": w1.astype(x.dtype),
                      "w2": w2.astype(x.dtype)}
        out_shard = moe_apply(shard, moe_params, ep_comm,
                              capacity=cap)              # (Tl, D)
        full = jnp.zeros((T, D), out_shard.dtype)
        full = jax.lax.dynamic_update_slice_in_dim(
            full, out_shard, r * Tl, 0)
        return ep_comm.reduce_out(full).reshape(B, S, D)
    m = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x,
                               lt["w1"].astype(cfg.dtype)))
    m = jnp.einsum("bsf,fd->bsd", m, lt["w2"].astype(cfg.dtype))
    if tp_comm is not None:
        m = tp_comm.reduce_out(m)                      # row-parallel sum
    return m


def _layer(x, lr: Dict, lt: Dict, causal, cfg: Config,
           tp_comm: Optional[InGraphComm],
           sp_comm: Optional[InGraphComm],
           ep_comm: Optional[InGraphComm] = None):
    """One transformer block (attention + MLP/MoE with residuals)."""
    h = _rmsnorm(x, lr["ln1"])
    if tp_comm is not None:
        h = tp_comm.copy_in(h)
    qkv = jnp.einsum("bsd,dchk->bcshk", h,
                     lt["wqkv"].astype(cfg.dtype))      # (B,3,S,hl,dh)
    o = _attend(qkv[:, 0], qkv[:, 1], qkv[:, 2], causal, cfg, sp_comm)
    o = jnp.einsum("bshk,hkd->bsd", o, lt["wo"].astype(cfg.dtype))
    if tp_comm is not None:
        o = tp_comm.reduce_out(o)                      # row-parallel sum
    x = x + o
    h = _rmsnorm(x, lr["ln2"])
    if tp_comm is not None and not (cfg.moe and ep_comm is not None):
        # dense Megatron pair: f operator here, g (reduce_out) in _mlp.
        # The MoE branch applies its own f over the EP axis instead —
        # applying both on the same axis would double the backward psum
        h = tp_comm.copy_in(h)
    return x + _mlp(h, lt, cfg, tp_comm, ep_comm)


def forward(params: Dict, tokens, cfg: Config,
            tp_comm: Optional[InGraphComm] = None,
            sp_comm: Optional[InGraphComm] = None,
            ep_comm: Optional[InGraphComm] = None):
    """Causal LM forward. ``tp_comm`` set => heads/d_ff leaves are local
    tp shards and row-parallel outputs are psum'ed over the tp axis.
    ``sp_comm`` set => ``tokens`` is this rank's sequence block and
    attention runs as ring attention over the sp axis (K/V circulate by
    ppermute) — long-context via sequence parallelism. ``ep_comm`` set
    (with ``cfg.moe``) => MLPs are Switch MoE blocks with one expert
    per expert-axis rank."""
    rep, tpp = params["rep"], params["tp"]
    x = rep["emb"][tokens].astype(cfg.dtype)          # (B, S, D)
    B, S, D = x.shape
    causal = jnp.tril(jnp.ones((S, S), jnp.bool_))
    for li in range(cfg.n_layers):
        x = _layer(x, rep["layers"][li], tpp["layers"][li], causal,
                   cfg, tp_comm, sp_comm, ep_comm)
    x = _rmsnorm(x, rep["ln_f"])
    logits = jnp.einsum("bsd,vd->bsv", x.astype(jnp.float32), rep["emb"])
    return logits


def loss_fn(params, inputs, targets, cfg: Config,
            tp_comm: Optional[InGraphComm] = None,
            sp_comm: Optional[InGraphComm] = None):
    """Next-token cross-entropy (mean over the local batch/sequence
    shard). Callers pre-shift: inputs = tokens[:, :-1], targets =
    tokens[:, 1:] — pre-shifting keeps sequence-parallel blocks aligned
    (each sp rank's targets are its own block of the shifted stream)."""
    logits = forward(params, inputs, cfg, tp_comm, sp_comm)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return jnp.mean(nll)


def init_pp_params(key, cfg: Config, pp: int) -> Dict:
    """Flagship (pipelined) parameter layout: ``rep`` = {emb, ln_f}
    replicated everywhere; ``stage`` = a list of layers-per-stage
    slots, each leaf stacked on a LEADING pp axis (slot j's row s is
    global layer s*(L/pp)+j — stage s's j-th layer). Leaves are
    GLOBAL (full heads/d_ff/experts); shard stage leaves
    P("pp", <tp axis where applicable>) so each pipeline rank holds
    its stage and each tp rank its head/expert shard."""
    assert cfg.n_layers % pp == 0
    per = cfg.n_layers // pp
    base = init_params(key, cfg, tp=1)
    rep, tpl = base["rep"], base["tp"]["layers"]
    stage = []
    for j in range(per):
        rows = [dict(tpl[s * per + j],
                     ln1=rep["layers"][s * per + j]["ln1"],
                     ln2=rep["layers"][s * per + j]["ln2"])
                for s in range(pp)]
        stage.append({k: jnp.stack([r[k] for r in rows])
                      for k in rows[0]})
    return {"rep": {"emb": rep["emb"], "ln_f": rep["ln_f"]},
            "stage": stage}


def pp_train_step(params, batch, cfg: Config, lr: float, *,
                  pp_comm: InGraphComm, n_micro: int,
                  dp_comm: Optional[InGraphComm] = None,
                  tp_comm: Optional[InGraphComm] = None,
                  sp_comm: Optional[InGraphComm] = None,
                  ep_comm: Optional[InGraphComm] = None):
    """ONE combined dp x tp x sp x pp (x ep) training step — the
    flagship program. Runs inside shard_map on a 4-axis mesh.

    Params layout: ``rep`` (emb/ln_f) replicated across pp; ``stage``
    leaves carry a leading pp axis (this rank's slice arrives as
    (1, ...) — its stage's layers). The batch is microbatched and
    pipelined: activations ring-shift between stages inside a scan
    (pipeline_apply); backward is AD through the shifts, so each pp
    rank's stage gradients land on that rank.

    Gradient sync: stage grads pmean over dp+sp only (stage params
    live on one pp rank); rep grads additionally SUM over pp — each
    stage contributes a different piece (stage 0 the input embedding,
    the last stage ln_f and the logits weights)."""
    inputs, targets = batch
    n_pp = pp_comm._size
    r_pp = pp_comm.rank()
    B, S = inputs.shape
    assert B % n_micro == 0
    Bm = B // n_micro
    causal = jnp.tril(jnp.ones((S, S), jnp.bool_))

    def stage_fn(stage_params, a):
        for lay in stage_params:
            lr_ = {"ln1": lay["ln1"][0], "ln2": lay["ln2"][0]}
            lt_ = {k: v[0] for k, v in lay.items()
                   if k not in ("ln1", "ln2")}
            a = _layer(a, lr_, lt_, causal, cfg, tp_comm, sp_comm,
                       ep_comm)
        return a

    def compute_loss(p):
        x = p["rep"]["emb"][inputs].astype(cfg.dtype)  # (B, S, D)
        micro = x.reshape(n_micro, Bm, S, -1)
        y = pipeline_apply(stage_fn, p["stage"], micro, pp_comm)
        y = y.reshape(B, S, -1)
        h = _rmsnorm(y, p["rep"]["ln_f"])
        logits = jnp.einsum("bsd,vd->bsv", h.astype(jnp.float32),
                            p["rep"]["emb"])
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
        local = jnp.mean(nll)
        # only the LAST stage's outputs are real: its loss is the
        # job's loss; psum the masked value so every pp rank agrees
        return pp_comm.reduce_out(
            jnp.where(r_pp == n_pp - 1, local, 0.0))

    loss, grads = jax.value_and_grad(compute_loss)(params)
    for comm in (sp_comm, dp_comm):
        if comm is not None:
            grads = jax.tree_util.tree_map(comm.pmean, grads)
            loss = comm.pmean(loss)
    # rep params are replicated across pp but each stage contributes a
    # DIFFERENT gradient piece: sum them
    grads["rep"] = jax.tree_util.tree_map(pp_comm.reduce_out,
                                          grads["rep"])
    if tp_comm is not None:              # rep grads identical across
        grads["rep"] = jax.tree_util.tree_map(   # tp; mean is a no-op
            tp_comm.pmean, grads["rep"])         # that keeps them tied
    if cfg.moe and ep_comm is not None:
        # the gate is replicated across the expert axis but each rank
        # routed a DIFFERENT token shard: sum its gradient pieces
        for lay in grads["stage"]:
            lay["gate"] = ep_comm.reduce_out(lay["gate"])
    params = jax.tree_util.tree_map(lambda p, g: p - lr * g, params,
                                    grads)
    return params, loss


def sgd_train_step(params, batch, cfg: Config, lr: float,
                   dp_comm: Optional[InGraphComm] = None,
                   tp_comm: Optional[InGraphComm] = None,
                   sp_comm: Optional[InGraphComm] = None,
                   grad_sync: Optional["BucketedGradSync"] = None):
    """One DP x TP x SP training step. Gradient synchronization follows
    the strategy table (SURVEY.md §2.6): grads allreduced (mean) over dp
    and over sp (each sp rank saw 1/n of the sequence); tp correctness
    comes from the Megatron f/g operators inside ``forward``.
    ``batch`` = (inputs, targets), pre-shifted.

    ``grad_sync`` replaces the in-graph dp pmean with DDP-style
    bucketed persistent allreduces over the framework's communicator
    tier (one fused wire collective per gradient bucket instead of one
    collective per tensor — docs/PERSISTENT.md). On the stacked
    single-controller tier ``params`` and ``batch`` lead with the rank
    axis, one data-parallel replica per rank, and the returned loss is
    per rank."""
    inputs, targets = batch
    grad_fn = jax.value_and_grad(
        lambda p, i, t: loss_fn(p, i, t, cfg, tp_comm, sp_comm))
    if grad_sync is not None and grad_sync.stacked:
        grad_fn = jax.vmap(grad_fn)
    loss, grads = grad_fn(params, inputs, targets)
    for comm in (sp_comm, dp_comm if grad_sync is None else None):
        if comm is not None:
            grads = jax.tree_util.tree_map(lambda g: comm.pmean(g), grads)
            loss = comm.pmean(loss)
    if grad_sync is not None:
        grads = grad_sync(grads)
        loss = grad_sync.mean_scalar(loss)
    params = jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads)
    return params, loss


class BucketedGradSync:
    """DDP-style gradient synchronization over bucketed persistent
    allreduces (coll/persistent, docs/PERSISTENT.md).

    Built once per (comm, gradient tree shape): each leaf gets a
    pinned numpy staging buffer and a persistent allreduce plan
    (``comm.allreduce_init``), so every step is copy-in -> one
    ``Startall`` (buckets fuse into ceil(total/bucket_bytes) wire
    collectives when ``mpi_base_bucket`` is on; byte-identical
    per-leaf collectives when off) -> copy-out. Works on both
    communicator tiers: on a per-rank comm each leaf is this rank's
    local gradient; on the stacked single-controller comm each leaf
    carries the leading rank axis."""

    def __init__(self, comm, grads_example):
        import numpy as np
        from ompi_tpu.core import op as _op
        self.comm = comm
        self.n = comm.size
        self.stacked = not getattr(comm, "is_per_rank", False)
        leaves, self._treedef = jax.tree_util.tree_flatten(grads_example)
        self._stages = [np.zeros(tuple(g.shape),
                                 np.dtype(jnp.asarray(g).dtype))
                        for g in leaves]
        self._reqs = [comm.allreduce_init(s, _op.SUM)
                      for s in self._stages]
        self._scalar_req = None

    def __call__(self, grads):
        import numpy as np
        from ompi_tpu.core.request import startall
        leaves = jax.tree_util.tree_leaves(grads)
        for stage, g in zip(self._stages, leaves):
            np.copyto(stage, np.asarray(g))
        startall(self._reqs)
        out = [np.asarray(r.get()) / self.n for r in self._reqs]
        return jax.tree_util.tree_unflatten(self._treedef, out)

    def shrink(self, comm=None) -> "BucketedGradSync":
        """Elastic continuation (docs/RESILIENCE.md): after a data-
        parallel peer dies mid-training, rebind this synchronizer to
        the survivor communicator and keep stepping. ``comm`` is the
        already-shrunk comm (``MPIX_Comm_shrink``'s result); None
        shrinks ``self.comm`` here. The staging buffers and tree
        layout carry over unchanged — only the persistent plans
        rebind (they are comm-bound) and the mean divisor RESCALES to
        the survivor count, so the surviving ranks' gradients still
        average to an unbiased estimate (smaller effective batch, not
        a corrupted one). Returns self."""
        from ompi_tpu.core import op as _op
        if comm is None:
            comm = self.comm.shrink()
        self.comm = comm
        self.n = comm.size
        self._reqs = [comm.allreduce_init(s, _op.SUM)
                      for s in self._stages]
        self._scalar_req = None          # lazily rebuilt on new comm
        return self

    def mean_scalar(self, value):
        """Mean one scalar (the loss) over the comm — rides the same
        persistent machinery through a lazily-built 1-elem plan. On the
        stacked tier ``value`` is one scalar for every rank or a
        per-rank vector."""
        import numpy as np
        from ompi_tpu.core import op as _op
        if self._scalar_req is None:
            self._scalar_stage = np.zeros(
                (self.n,) if self.stacked else np.shape(value),
                np.float64)
            self._scalar_req = self.comm.allreduce_init(
                self._scalar_stage, _op.SUM)
        np.copyto(self._scalar_stage, np.asarray(value, np.float64))
        self._scalar_req.start()
        return np.asarray(self._scalar_req.get()) / self.n
