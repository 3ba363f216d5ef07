"""coll/xla — the TPU-native collective component.

This is the component the whole framework exists for: MPI collectives on
HBM-resident stacked buffers lower to XLA collective ops over the
communicator's private mesh axis, compiled once per
(collective, op, dtype, shape, root) and cached — the compiled-executable
cache plays the role the reference's per-communicator module state and
ob1 endpoint caches play (``SURVEY.md §5`` distributed-backend mapping).

Algorithm mapping (reference algorithm registry
``coll_base_functions.h:185-320`` -> XLA). Each collective's ``direct``
lowering is one fused XLA collective, whose ICI schedule XLA picks:

- allreduce      -> ``lax.psum``/``pmax``/``pmin`` (reduction order is
  fixed by XLA's deterministic schedule — the analogue of the
  reference's documented commutativity constraint,
  ``coll_base_allreduce.c:291-294``).
- allgather      -> ``lax.all_gather``
- reduce_scatter -> ``lax.psum_scatter(tiled)``; a SUM over 1-D blocks
  of whole 128-lane rows of a 32-bit type -> ``all_to_all`` + local sum.
- alltoall       -> ``lax.all_to_all``
- bcast          -> masked ``psum`` (arithmetic dtypes) or
  all_gather+select; root is a compile-time constant.
- scan/exscan    -> ``all_gather`` + on-device prefix
  (``cumsum``/``associative_scan``) + own-row slice.
- barrier        -> scalar ``psum`` + readiness.

Besides ``direct`` the module keeps only what a decision row selects or
a test uses as the reference: ``hier`` (multi-host), the root-targeted
reduce/gather/scatter schedules, and reduce's ``in_order_binary``.

Ops without a fused XLA collective (PROD, bitwise/logical, MINLOC/MAXLOC,
user ops) lower to ``all_gather`` + an on-device ordered fold
(``Op.reduce_tree``) — the general path the reference implements as
basic_linear, here fully on-device and XLA-fused.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ompi_tpu.core.communicator import AXIS
from ompi_tpu.coll import decision
from ompi_tpu.coll.framework import coll_framework
from ompi_tpu.mca import var
from ompi_tpu.mca.base import Component
from ompi_tpu.trace import core as _trace

P = jax.sharding.PartitionSpec

_ARITH_KINDS = frozenset("fiuc")        # dtypes psum/pmax/pmin accept


def _spec(ndim: int) -> P:
    return P(AXIS, *([None] * (ndim - 1)))


class _LruCache(OrderedDict):
    """Bounded compiled-executable cache. Keys are
    (collective, shape, dtype, op, epoch, ...) tuples, so a long-running
    workload with varying shapes would otherwise grow host + HBM memory
    monotonically; eviction drops the least-recently-used executable and
    lets XLA's own deallocation reclaim it. The cap is the
    ``coll_xla_cache_max_entries`` MCA var, read at insertion time so a
    running job can be re-bounded without restarting."""

    def __getitem__(self, key):
        val = super().__getitem__(key)
        self.move_to_end(key)
        return val

    def get(self, key, default=None):
        if key not in self:
            return default
        return self[key]

    def __setitem__(self, key, value):
        super().__setitem__(key, value)
        self.move_to_end(key)
        cap = max(1, int(var.var_get("coll_xla_cache_max_entries", 256)))
        while len(self) > cap:
            # evict via __delitem__, NOT popitem: popitem re-enters
            # the overridden __getitem__ mid-unlink on current
            # CPythons and its move_to_end raises KeyError
            del self[next(iter(self))]


def _launch(span: str, fn: Callable, x):
    """``fn(x)``, the compiled executable's call, as the span ``span``
    while the ring or the profiler records."""
    if not (_trace.active or _trace.recording()):
        return fn(x)
    tok = _trace.begin(span)
    try:
        return fn(x)
    finally:
        _trace.end(tok)


class XlaCollModule:
    def __init__(self, comm):
        self.comm = comm
        self._cache: Dict[Tuple, Callable] = _LruCache()
        self._fast: Dict[Tuple, Callable] = _LruCache()
        self._barrier_tokens: Dict[str, Tuple] = {}

    # -- executable cache ------------------------------------------------
    def _compiled(self, key: Tuple, build: Callable[[], Callable],
                  *lower_args) -> Callable:
        """Compiled-executable cache (the ob1-endpoint-cache role). When
        the call site provides example args the jitted function is
        AOT-lowered to a ``Compiled`` object whose ``__call__`` skips
        the jit wrapper's per-call signature dispatch (~25 us/call —
        measurable on the latency path; inputs are always normalized to
        the communicator sharding by ``_to_mesh`` first, so the
        compiled calling convention is stable)."""
        fn = self._cache.get(key)
        if fn is None:
            # compile misses dominate first-call latency; trace them as
            # their own spans (ring and profiler sink) so a timeline
            # distinguishes "the collective was slow" from "the
            # collective compiled"
            tok = (_trace.begin("xla_compile",
                                cid=getattr(self.comm, "cid", None),
                                key=str(key[0]))
                   if _trace.active or _trace.recording() else None)
            try:
                fn = build()
                if lower_args:
                    fn = fn.lower(*lower_args).compile()
            finally:
                if tok is not None:
                    _trace.end(tok)
            self._cache[key] = fn
        return fn

    def _smap(self, inner: Callable, ndim_in: int, ndim_out: int) -> Callable:
        f = jax.shard_map(inner, mesh=self.comm.mesh,
                          in_specs=_spec(ndim_in),
                          out_specs=_spec(ndim_out))
        return jax.jit(f)

    def _to_mesh(self, x):
        sh = self.comm.sharding
        if isinstance(x, jax.Array):
            try:
                xs = x.sharding
                if xs is sh:             # comm.put/alloc results and
                    return x             # prior outputs: ~0.3 us
                if xs.is_equivalent_to(sh, x.ndim):
                    return x
            except Exception:
                pass
            if not self.comm.is_multiprocess:
                return jax.device_put(x, sh)
            if not getattr(x, "is_fully_addressable", True):
                # Multi-controller: a global array on a *different*
                # sharding can be neither fetched nor device_put here.
                # Surface a clear error instead of jax's opaque
                # non-addressable RuntimeError.
                from ompi_tpu.core.errhandler import ERR_ARG, MPIError
                raise MPIError(
                    ERR_ARG,
                    "buffer is sharded over a different mesh than this "
                    "communicator's; in a multi-controller world pass "
                    "buffers created on this communicator (comm.put/"
                    "alloc/stack) or host arrays")
            # Fully-addressable local device array: fetch + replace.
        # Host arrays go through the communicator's placement helper
        # (multi-controller-safe).
        return self.comm.put(np.asarray(x))

    def _key(self, func: str, x, *extra) -> Tuple:
        # dtype objects hash/compare directly; str() was ~15 us/call
        return (func, x.shape, x.dtype, *extra)

    # -- algorithm selection (re-design of coll_base_functions.h:185-320
    # + tuned decision functions): the MCA var coll_xla_<func>_algorithm
    # or the decision table picks the lowering. 'direct' is one fused XLA
    # collective (XLA schedules its own ICI-optimal ring/tree). 'hier' is
    # the han-style two-level composition (coll_han.h:180-195):
    # reduce_scatter within a group, allreduce across groups, allgather
    # within — implemented with axis_index_groups so intra-group traffic
    # stays on the fast tier (ICI) and only the scattered chunk crosses
    # the slow tier (DCN), for multi-host meshes. A name a collective
    # does not implement runs its 'direct' lowering.
    def _multihost(self) -> bool:
        return self.comm.spans_processes

    def _algorithm(self, func: str = "allreduce", nbytes: int = 0,
                   commute: bool = True) -> str:
        """Per-collective algorithm selection: the explicit MCA var wins;
        ``auto`` consults the decision tables (coll/decision.py, the
        coll_tuned_decision_fixed role) plus the tuned dynamic-rules
        file. A schedule that reorders combines degrades to ``direct``
        for a non-commutative op, exactly as the reference's decision
        functions fall back to basic_linear."""
        alg = var.var_get(f"coll_xla_{func}_algorithm", "auto")
        if alg == "auto":
            from ompi_tpu.coll.tuned import _load_rules
            dyn = _load_rules(var.var_get("coll_tuned_dynamic_rules", ""))
            alg = decision.decide(
                func, self.comm.size, nbytes, self._multihost(), dyn,
                platform=getattr(self.comm.devices[0], "platform", ""))
        if alg in decision.REORDERING and not commute:
            return "direct"
        return alg

    def _groups(self):
        """(low, high) axis_index_groups: low = ranks sharing a process
        (ICI tier), high = one rank per process (DCN tier). Falls back to
        a balanced factorization on single-host meshes (for testing and
        for multi-NUMA boards)."""
        n = self.comm.size
        by_proc = {}
        for r, d in enumerate(self.comm.devices):
            by_proc.setdefault(getattr(d, "process_index", 0), []).append(r)
        groups = list(by_proc.values())
        if len(groups) == 1:
            g = 1
            for f in range(int(n ** 0.5), 0, -1):
                if n % f == 0:
                    g = f
                    break
            groups = [list(range(i, i + g)) for i in range(0, n, g)]
        size = len(groups[0])
        if any(len(gr) != size for gr in groups):
            return None, None            # ragged: hier not applicable
        low = groups
        high = [[gr[i] for gr in groups] for i in range(size)]
        return low, high

    def _hier_or_direct(self, alg: str):
        """``(low, high, alg)`` for a collective whose one schedule
        besides ``direct`` is ``hier``: any other name, and ``hier``
        over ragged groups, runs ``direct``."""
        if alg == "hier":
            low, high = self._groups()
            if low is not None:
                return low, high, "hier"
        return None, None, "direct"

    def _hier_allreduce_inner(self, op, low, high, codec=None):
        """han-style two-level: rs(low) -> ar(high) -> ag(low). Only the
        sum path uses psum_scatter; other ops go through the generic
        gather+fold on each tier.

        ``codec`` ((Codec, block), sum ops only — coll/compressed
        gates): the intra-group tiers stay full-width (ICI is the fast
        tier), and ONLY the scattered chunk crossing the slow tier is
        quantized — each position class all-gathers codes + scales over
        the high groups and dequant->reduces in fixed group order, so
        members of a class end bitwise identical and the DCN bytes drop
        to ~codes+scales (HiCCL's compression-on-the-slow-tier
        composition)."""
        glen = len(low[0])
        if codec is not None:
            cobj, cblock = codec
            H = len(high[0])

            def inner_q(b):              # block (1, *s); sum only
                x = b[0]
                shape = x.shape
                total = x.size
                chunk = -(-total // glen)
                flat = jnp.pad(x.reshape(-1), (0, glen * chunk - total))
                part = jax.lax.psum_scatter(
                    flat.reshape(glen, chunk), AXIS, scatter_dimension=0,
                    tiled=True, axis_index_groups=low)[0]
                qc, qs = cobj.jnp_quant(part, cblock)
                gc = jax.lax.all_gather(qc, AXIS, tiled=False,
                                        axis_index_groups=high)
                gs = jax.lax.all_gather(qs, AXIS, tiled=False,
                                        axis_index_groups=high)
                # fixed group order: every member of a position class
                # folds the same dequantized contributions identically
                acc = cobj.jnp_dequant(gc[0], gs[0], chunk, part.dtype,
                                       cblock)
                for h in range(1, H):
                    acc = op.fn(acc, cobj.jnp_dequant(
                        gc[h], gs[h], chunk, part.dtype, cblock))
                out = jax.lax.all_gather(acc, AXIS, tiled=True,
                                         axis_index_groups=low)
                return out.reshape(-1)[:total].reshape(shape)[None]
            return inner_q

        def inner(b):                    # block (1, *s)
            x = b[0]
            shape = x.shape
            total = x.size
            chunk = -(-total // glen)
            flat = jnp.pad(x.reshape(-1), (0, glen * chunk - total))
            if op.xla_prim == "sum":
                part = jax.lax.psum_scatter(
                    flat.reshape(glen, chunk), AXIS, scatter_dimension=0,
                    tiled=True, axis_index_groups=low)[0]
                # cross-tier allreduce of the scattered chunk as
                # redscat+allgather over the high groups (psum+groups
                # lacks a shard_map lowering; this moves 2*chunk*(H-1)/H
                # per DCN link instead of the round-2 gather+sum's
                # H*chunk — the 1/n traffic property han exists for)
                H = len(high[0])
                sub = -(-chunk // H)
                p_hi = jnp.pad(part, (0, H * sub - chunk))
                p2 = jax.lax.psum_scatter(
                    p_hi.reshape(H, sub), AXIS, scatter_dimension=0,
                    tiled=False, axis_index_groups=high)
                part = jax.lax.all_gather(
                    p2, AXIS, tiled=True,
                    axis_index_groups=high)[:chunk]
                out = jax.lax.all_gather(part, AXIS, tiled=True,
                                         axis_index_groups=low)
            else:
                g1 = jax.lax.all_gather(flat, AXIS,
                                        axis_index_groups=low)
                red = op.reduce_tree(g1, axis=0)
                g2 = jax.lax.all_gather(red, AXIS,
                                        axis_index_groups=high)
                out = op.reduce_tree(g2, axis=0)
            return out.reshape(-1)[:total].reshape(shape)[None]
        return inner

    def _hier_bcast_inner(self, root, low, high):
        """Two-tier bcast (coll_han.h:180-195): root's buffer reaches
        one member of every low group via a binomial ppermute chain
        over the high tier (log2(#groups) rounds; ppermute forbids
        multicast so the doubling tree is the minimal-round fan-out,
        exactly coll_base_bcast's binomial), then each group broadcasts
        internally over ICI (all_gather + select)."""
        n = self.comm.size
        g_root = next(g for g, gr in enumerate(low) if root in gr)
        pos_root = low[g_root].index(root)
        reps = [gr[pos_root] for gr in low]   # root's position-class
        ri = reps.index(root)
        order = reps[ri:] + reps[:ri]         # root first
        H = len(order)
        rounds = []
        have = np.zeros(n, bool)
        have[root] = True
        k = 1
        while k < H:
            pairs = [(order[i], order[i + k])
                     for i in range(k) if i + k < H]
            rounds.append((tuple(pairs), have.copy()))
            for (_, d) in pairs:
                have[d] = True
            k <<= 1

        def inner(b):                    # (1, *s) -> (1, *s)
            x = b[0]
            r = jax.lax.axis_index(AXIS)
            cur = x
            for pairs, have_mask in rounds:
                recvd = jax.lax.ppermute(cur, AXIS, perm=pairs)
                hm = jnp.asarray(have_mask)[r]
                cur = jnp.where(hm, cur, recvd)
            g2 = jax.lax.all_gather(cur, AXIS, tiled=False,
                                    axis_index_groups=low)
            return g2[pos_root][None]
        return inner

    def _hier_rsb_inner(self, low, high, shape):
        """Two-tier reduce_scatter_block (sum): chunks are pre-permuted
        so group-member k's block holds the chunks owned by the
        position-k ranks of every group; an intra-group psum_scatter
        (ICI) then a cross-group psum_scatter (DCN) leave each rank
        exactly its own globally-summed chunk — only chunk-sized
        traffic ever crosses the slow tier."""
        glen = len(low[0])
        H = len(high[0])

        def inner(b):                    # (1, N, *s) -> (1, *s)
            row = b[0]                   # (N, *s)
            # block k = chunks of [low[p][k] for p in range(H)]
            perm_idx = np.array([low[p][k] for k in range(glen)
                                 for p in range(H)])
            rp = row[jnp.asarray(perm_idx)]           # (N, *s)
            blocks = rp.reshape((glen, H) + row.shape[1:])
            part = jax.lax.psum_scatter(
                blocks, AXIS, scatter_dimension=0, tiled=False,
                axis_index_groups=low)                # (H, *s)
            out = jax.lax.psum_scatter(
                part, AXIS, scatter_dimension=0, tiled=False,
                axis_index_groups=high)               # (*s)
            return out[None]
        return inner

    def _hier_allgather_inner(self, low, high):
        """Two-tier allgather: gather position-peers over the high tier
        (each DCN link carries each remote group's chunk ONCE — group
        members share it over ICI), then gather bundles within the
        group and reassemble rank order with a static index map."""
        glen = len(low[0])
        H = len(high[0])
        n = glen * H
        # out[j] = bundle[pos_of_j][group_of_j]
        pos_of = np.zeros(n, np.int32)
        grp_of = np.zeros(n, np.int32)
        for g, gr in enumerate(low):
            for k, r in enumerate(gr):
                pos_of[r], grp_of[r] = k, g

        def inner(b):                    # (1, *s) -> (1, N, *s)
            x = b[0]
            g1 = jax.lax.all_gather(x, AXIS, tiled=False,
                                    axis_index_groups=high)  # (H, *s)
            g2 = jax.lax.all_gather(g1, AXIS, tiled=False,
                                    axis_index_groups=low)  # (glen,H,*s)
            out = g2[jnp.asarray(pos_of), jnp.asarray(grp_of)]
            return out[None]             # (1, N, *s)
        return inner

    def _hier_barrier_inner(self, low, high):
        """Two-tier barrier: members sync within the group, position
        classes sync across groups, groups re-sync — three chained
        stages whose data dependencies give transitive completion (the
        leader-barrier structure of coll_han / xhc ladders)."""
        def inner(b):                    # (1,) token
            t1 = jnp.sum(jax.lax.all_gather(
                b[0], AXIS, axis_index_groups=low))
            t2 = jnp.sum(jax.lax.all_gather(
                t1, AXIS, axis_index_groups=high))
            t3 = jnp.sum(jax.lax.all_gather(
                t2, AXIS, axis_index_groups=low))
            return t3[None]
        return inner

    def _in_order_binary_reduce_inner(self, op, n, root):
        """In-order binary-tree reduce (coll_base_functions.h:276,
        coll_base_reduce.c in_order_binary) — the ONE tree whose
        combine order equals rank order, so it is correct for
        NON-commutative (associative) operators: at distance d, rank r
        with r % 2d == 0 folds rank r+d's accumulator on its RIGHT
        (acc covers [r, r+d); the peer's covers [r+d, r+2d)). Any n;
        result lands on rank 0 and rides one ppermute to root."""
        def inner(b):                    # (1, *s) -> (1, *s) at root
            x = b
            r = jax.lax.axis_index(AXIS)
            acc = x
            d = 1
            while d < n:
                perm = [(i, (i - d) % n) for i in range(n)]
                recvd = jax.lax.ppermute(acc, AXIS, perm=perm)
                combine = (jnp.mod(r, 2 * d) == 0) & (r + d < n)
                acc = jnp.where(combine, op.fn(acc, recvd), acc)
                d *= 2
            if root != 0:
                moved = jax.lax.ppermute(acc, AXIS, perm=[(0, root)])
                acc = jnp.where(r == root, moved, acc)
            return acc
        return inner

    # -- root-targeted schedules --------------------
    # XLA's ppermute moves bytes only along the listed (src, dst) pairs,
    # so binomial trees rooted at `root` are expressible in-graph: wire
    # traffic is root-directed even though SPMD shapes stay uniform.
    # Specs: reduce redscat_gather (Rabenseifner-to-root) and binomial
    # gather/scatter in coll_base_functions.h:185-320.
    @staticmethod
    def _npad2(n: int) -> int:
        p = 1
        while p < n:
            p *= 2
        return p

    @staticmethod
    def _at(i, ndim: int) -> tuple:
        """Start index ``(i, 0, ..., 0)`` with every entry in ``i``'s
        dtype: under x64 a literal 0 is int64 beside axis_index's int32,
        and dynamic_(update_)slice refuses mixed index dtypes."""
        return (i,) + (jnp.zeros((), i.dtype),) * ndim

    def _rabenseifner_root_reduce_inner(self, n, root, shape):
        """reduce = psum_scatter (each rank reduces 1/n) + binomial
        collect of the reduced chunks into root: (n-1)/n of the buffer
        crosses the wire toward root — half an allreduce's traffic
        (spec: ompi_coll_base_reduce_intra_redscat_gather). SUM ONLY —
        psum_scatter is the reduction; the caller must gate on
        op.xla_prim == "sum". Output stacked (n, *s); only root's row
        is significant."""
        total = int(np.prod(shape))
        chunk = -(-total // n)
        npad = self._npad2(n)

        def inner(b):                    # (1, *s) -> (1, *s)
            x = b.reshape(-1)
            x = jnp.pad(x, (0, n * chunk - total)).reshape(n, chunk)
            # rank r's scattered chunk must be virtual-rank chunk
            # v = (r - root) mod n so the collect tree roots at vr 0
            x = jnp.roll(x, root, axis=0)
            part = jax.lax.psum_scatter(x, AXIS, scatter_dimension=0,
                                        tiled=True)        # (1, chunk)
            r = jax.lax.axis_index(AXIS)
            v = jnp.mod(r - root, n)
            buf = jnp.zeros((npad, chunk), part.dtype)
            buf = jax.lax.dynamic_update_slice(buf, part, self._at(v, 1))
            d = 1
            while d < npad:
                perm = [((vs + root) % n, (vs - d + root) % n)
                        for vs in range(d, n, 2 * d)]
                send = jax.lax.dynamic_slice(
                    buf, self._at(jnp.minimum(v, npad - d), 1), (d, chunk))
                recvd = jax.lax.ppermute(send, AXIS, perm=perm)
                upd = jax.lax.dynamic_update_slice(buf, recvd,
                                                   self._at(v + d, 1))
                buf = jnp.where(jnp.mod(v, 2 * d) == 0, upd, buf)
                d *= 2
            res = buf[:n].reshape(-1)[:total]
            out = jnp.where(r == root, res, jnp.zeros_like(res))
            return out.reshape(b.shape)
        return inner

    def _binomial_gather_inner(self, n, root):
        """Root-targeted binomial gather
        (ompi_coll_base_gather_intra_binomial): log2(n) rounds of
        block-doubling ppermute toward root. Aggregate wire bytes are
        (n-1) blocks — 1/n of the allgather alias round 1 used. Output
        stacked (n, n, *s); rows valid at root only."""
        npad = self._npad2(n)

        def inner(b):                    # (1, *s) -> (1, n, *s)
            x = b[0]
            r = jax.lax.axis_index(AXIS)
            v = jnp.mod(r - root, n)
            buf = jnp.zeros((npad,) + x.shape, x.dtype)
            buf = jax.lax.dynamic_update_slice(buf, x[None],
                                               self._at(v, x.ndim))
            d = 1
            while d < npad:
                perm = [((vs + root) % n, (vs - d + root) % n)
                        for vs in range(d, n, 2 * d)]
                send = jax.lax.dynamic_slice(
                    buf, self._at(jnp.minimum(v, npad - d), x.ndim),
                    (d,) + x.shape)
                recvd = jax.lax.ppermute(send, AXIS, perm=perm)
                upd = jax.lax.dynamic_update_slice(
                    buf, recvd, self._at(v + d, x.ndim))
                buf = jnp.where(jnp.mod(v, 2 * d) == 0, upd, buf)
                d *= 2
            idx = jnp.mod(jnp.arange(n) - root, n)    # vrank -> rank rows
            out = jnp.take(buf, idx, axis=0)
            out = jnp.where(r == root, out, jnp.zeros_like(out))
            return out[None]
        return inner

    def _binomial_scatter_inner(self, n, root):
        """Root-targeted binomial scatter
        (ompi_coll_base_scatter_intra_binomial): root's n blocks fan out
        in log2(n) block-halving rounds; (n-1) blocks total leave root's
        subtree vs the all_to_all lowering where every rank ships its
        (meaningless) full row."""
        npad = self._npad2(n)

        def inner(b):                    # (1, n, *s) -> (1, *s)
            x = b[0]                     # root's row of chunks
            s = x.shape[1:]
            r = jax.lax.axis_index(AXIS)
            v = jnp.mod(r - root, n)
            idx = jnp.mod(jnp.arange(npad) + root, n)  # rank -> vrank rows
            buf = jnp.take(x, idx, axis=0)
            buf = jnp.where(r == root, buf, jnp.zeros_like(buf))
            d = npad // 2
            while d >= 1:
                perm = [((vs + root) % n, (vs + d + root) % n)
                        for vs in range(0, n, 2 * d) if vs + d < n]
                send = jax.lax.dynamic_slice(
                    buf, self._at(jnp.minimum(v + d, npad - d), len(s)),
                    (d,) + s)
                recvd = jax.lax.ppermute(send, AXIS, perm=perm)
                upd = jax.lax.dynamic_update_slice(
                    buf, recvd, self._at(v, len(s)))
                buf = jnp.where(jnp.mod(v, 2 * d) == d, upd, buf)
                d //= 2
            own = jax.lax.dynamic_slice(buf, self._at(v, len(s)), (1,) + s)
            return own                   # (1, *s)
        return inner

    # -- collectives -----------------------------------------------------
    def bind_allreduce(self, example, op):
        """Pre-bound hot-path handle: warm the decision + compile for
        ``example``'s (shape, dtype, op), then return a callable that
        is the cached executable plus the sharding fast check — the
        module owns the memo key, so callers never duplicate it."""
        x = self._to_mesh(example)
        self.allreduce(x, op)            # warm: decide + compile + memo
        fn = self._fast[("allreduce", x.shape, x.dtype, op.uid)][1]
        return lambda buf: fn(self._to_mesh(buf))

    def allreduce(self, x, op):
        x = self._to_mesh(x)
        # Hot-path memo: everything below (decision tables, dynamic
        # rules, cache-key build) is a pure function of
        # (shape, dtype, op) and the var-store epoch; one dict probe
        # replaces it per call. Entries carry the epoch they were
        # decided at and are replaced in place on mismatch, so var_set
        # invalidates immediately without stranding old entries.
        fk = ("allreduce", x.shape, x.dtype, op.uid)
        ep = var.epoch()            # snapshot BEFORE the decision reads
        hit = self._fast.get(fk)
        if hit is not None and hit[0] == ep:
            return _launch(hit[2], hit[1], x)
        n = self.comm.size
        low, high, alg = self._hier_or_direct(
            self._algorithm("allreduce", x.nbytes // max(n, 1),
                            op.commute))

        def build():
            if alg == "hier":
                inner = self._hier_allreduce_inner(op, low, high)
            elif op.xla_prim == "sum":
                inner = lambda b: jax.lax.psum(b, AXIS)
            elif op.xla_prim == "max":
                inner = lambda b: jax.lax.pmax(b, AXIS)
            elif op.xla_prim == "min":
                inner = lambda b: jax.lax.pmin(b, AXIS)
            else:
                def inner(b):
                    g = jax.lax.all_gather(b, AXIS, axis=0, tiled=True)
                    return op.reduce_tree(g, axis=0)[None]
            return self._smap(inner, x.ndim, x.ndim)
        # 0 fills the key's segment slot: readers of the cache index
        # its fixed layout
        fn = self._compiled(
            self._key("allreduce", x, op.uid, n, alg, 0), build, x)
        # the launch span's name carries the algorithm that serves this
        # key, fixed here so the hit path builds no string
        span = f"coll.xla.launch:allreduce/{alg}"
        self._fast[fk] = (ep, fn, span)
        return _launch(span, fn, x)

    def allreduce_dtype(self, x, op, dt, count: int,
                        preserve_gaps: bool):
        """Derived-datatype allreduce as ONE compiled program:
        gather(significant) -> collective -> scatter(result) fused
        under a single shard_map, the datatype's index map baked in as
        a compile-time constant. Replaces the 3-dispatch
        pack/collective/unpack chain whose per-call index H2D and
        extra SPMD launches made a strided allreduce 6x the contiguous
        one. ``preserve_gaps``: scatter into the
        input (IN_PLACE recvbuf semantics) vs a zeroed image (the
        functional no-recvbuf contract). Reference for the semantics:
        opal_convertor.c:83-102 (only significant bytes travel)."""
        x = self._to_mesh(x)
        fk = ("allreduce_dt", x.shape, x.dtype, op.uid, dt.uid, count,
              preserve_gaps)
        ep = var.epoch()
        hit = self._fast.get(fk)
        if hit is not None and hit[0] == ep:
            return hit[1](x)
        idx_np = dt.flat_indices(count)

        def build():
            if op.xla_prim == "sum":
                red = lambda p: jax.lax.psum(p, AXIS)       # noqa: E731
            elif op.xla_prim == "max":
                red = lambda p: jax.lax.pmax(p, AXIS)       # noqa: E731
            elif op.xla_prim == "min":
                red = lambda p: jax.lax.pmin(p, AXIS)       # noqa: E731
            else:
                def red(p):
                    g = jax.lax.all_gather(p, AXIS, axis=0, tiled=True)
                    return op.reduce_tree(g, axis=0)[None]

            def inner(b):
                idx = jnp.asarray(idx_np)    # baked-in constant
                r = red(jnp.take(b, idx, axis=-1))
                base = b if preserve_gaps else jnp.zeros_like(b)
                return base.at[..., idx].set(r)
            return self._smap(inner, x.ndim, x.ndim)
        fn = self._compiled(
            self._key("allreduce_dt", x, op.uid, dt.uid, count,
                      preserve_gaps), build, x)
        self._fast[fk] = (ep, fn)
        return fn(x)

    def reduce(self, x, op, root: int):
        """Root-targeted reduce. ``rabenseifner_root`` halves the wire
        traffic of the round-1 allreduce alias; ``alias`` remains for
        non-sum ops (psum_scatter is sum-only), size-1 worlds, and the
        latency regime where one fused psum wins (decision table).
        Output stacked (n, *s); only root's row is significant."""
        x = self._to_mesh(x)
        n = self.comm.size
        fk = ("reduce", x.shape, x.dtype, op.uid, root)
        ep = var.epoch()            # snapshot BEFORE the decision reads
        hit = self._fast.get(fk)
        if hit is not None and hit[0] == ep:
            return hit[1](x)
        alg = self._algorithm("reduce", x.nbytes // max(n, 1), op.commute)
        # The root-targeted schedules are constrained (rabenseifner_root:
        # sum-only and commutative, the latter handled by REORDERING) and
        # meaningful only for n > 1; EVERY other selection outcome
        # (alias, a demotion to 'direct', an unknown dynamic-rules
        # name) delegates to allreduce, which honors the op.
        if alg == "in_order_binary" and n > 1:
            # the non-commutative-correct tree: no commute constraint
            def build():
                inner = self._in_order_binary_reduce_inner(op, n, root)
                return self._smap(inner, x.ndim, x.ndim)
            fn = self._compiled(
                self._key("reduce", x, op.uid, n, root, alg), build, x)
        elif alg != "rabenseifner_root" or op.xla_prim != "sum" or n == 1:
            fn = lambda xx, _op=op: self.allreduce(xx, _op)  # noqa: E731
        else:
            def build():
                inner = self._rabenseifner_root_reduce_inner(
                    n, root, x.shape[1:])
                return self._smap(inner, x.ndim, x.ndim)
            fn = self._compiled(
                self._key("reduce", x, op.uid, n, root, alg), build, x)
        self._fast[fk] = (ep, fn)
        return fn(x)

    def bcast(self, x, root: int):
        x = self._to_mesh(x)
        fk = ("bcast", x.shape, x.dtype, root)
        ep = var.epoch()            # snapshot BEFORE the decision reads
        hit = self._fast.get(fk)
        if hit is not None and hit[0] == ep:
            return _launch(hit[2], hit[1], x)
        n = self.comm.size
        arith = np.dtype(x.dtype).kind in _ARITH_KINDS
        low, high, alg = self._hier_or_direct(
            self._algorithm("bcast", x.nbytes // max(n, 1)))

        def build():
            if alg == "hier":
                inner = self._hier_bcast_inner(root, low, high)
            elif arith:
                def inner(b):
                    r = jax.lax.axis_index(AXIS)
                    masked = jnp.where(r == root, b, jnp.zeros_like(b))
                    return jax.lax.psum(masked, AXIS)
            else:
                def inner(b):
                    g = jax.lax.all_gather(b, AXIS, axis=0, tiled=True)
                    return jax.lax.dynamic_slice_in_dim(g, root, 1, 0)
            return self._smap(inner, x.ndim, x.ndim)
        # 0 fills the key's segment slot (fixed layout, as allreduce's)
        fn = self._compiled(self._key("bcast", x, root, alg, 0), build, x)
        span = f"coll.xla.launch:bcast/{alg}"
        self._fast[fk] = (ep, fn, span)
        return _launch(span, fn, x)

    def allgather(self, x):
        x = self._to_mesh(x)
        fk = ("allgather", x.shape, x.dtype)
        ep = var.epoch()            # snapshot BEFORE the decision reads
        hit = self._fast.get(fk)
        if hit is not None and hit[0] == ep:
            return _launch(hit[2], hit[1], x)
        n = self.comm.size
        low, high, alg = self._hier_or_direct(
            self._algorithm("allgather", x.nbytes // max(n, 1)))

        def build():
            if alg == "hier":
                inner = self._hier_allgather_inner(low, high)
            else:
                def inner(b):                   # (1, *s) -> (1, N, *s)
                    g = jax.lax.all_gather(b[0], AXIS, axis=0,
                                           tiled=False)
                    return g[None]
            return self._smap(inner, x.ndim, x.ndim + 1)
        fn = self._compiled(self._key("allgather", x, alg), build, x)
        span = f"coll.xla.launch:allgather/{alg}"
        self._fast[fk] = (ep, fn, span)
        return _launch(span, fn, x)

    def gather(self, x, root: int):
        """Root-targeted gather: binomial tree toward root (aggregate
        wire bytes 1/n of the allgather alias). ``allgather`` remains
        the latency-regime choice (one fused op; root semantics are a
        superset). Output (n, n, *s); rows valid at root only."""
        x = self._to_mesh(x)
        n = self.comm.size
        fk = ("gather", x.shape, x.dtype, root)
        ep = var.epoch()            # snapshot BEFORE the decision reads
        hit = self._fast.get(fk)
        if hit is not None and hit[0] == ep:
            return hit[1](x)
        alg = self._algorithm("gather", x.nbytes // max(n, 1))
        if alg != "binomial" or n == 1:
            fn = self.allgather          # alias (and any unknown name)
        else:
            def build():
                return self._smap(self._binomial_gather_inner(n, root),
                                  x.ndim, x.ndim + 1)
            fn = self._compiled(self._key("gather", x, n, root, alg),
                                build, x)
        self._fast[fk] = (ep, fn)
        return fn(x)

    def scatter(self, x, root: int):
        """Root-targeted scatter: binomial fan-out from root; the
        ``direct`` all_to_all lowering (every rank ships its row, only
        root's is meaningful) remains the latency-regime choice."""
        x = self._to_mesh(x)
        n = self.comm.size
        fk = ("scatter", x.shape, x.dtype, root)
        ep = var.epoch()            # snapshot BEFORE the decision reads
        hit = self._fast.get(fk)
        if hit is not None and hit[0] == ep:
            return hit[1](x)
        alg = self._algorithm("scatter", x.nbytes // max(n, 1))
        if alg == "binomial" and n == 1:
            alg = "direct"

        def build():
            if alg == "binomial":
                inner = self._binomial_scatter_inner(n, root)
            else:
                def inner(b):                   # (1, N, *s) -> (1, *s)
                    y = jax.lax.all_to_all(b[0], AXIS, split_axis=0,
                                           concat_axis=0, tiled=True)
                    return jax.lax.dynamic_slice_in_dim(y, root, 1, 0)
            return self._smap(inner, x.ndim, x.ndim - 1)
        fn = self._compiled(self._key("scatter", x, n, root, alg),
                            build, x)
        self._fast[fk] = (ep, fn)
        return fn(x)

    def alltoall(self, x):
        x = self._to_mesh(x)
        fk = ("alltoall", x.shape, x.dtype)
        ep = var.epoch()            # snapshot BEFORE the decision reads
        hit = self._fast.get(fk)
        if hit is not None and hit[0] == ep:
            return _launch(hit[2], hit[1], x)

        def build():
            def inner(b):                   # (1, N, *s) -> (1, N, *s)
                y = jax.lax.all_to_all(b[0], AXIS, split_axis=0,
                                       concat_axis=0, tiled=True)
                return y[None]
            return self._smap(inner, x.ndim, x.ndim)
        # 'direct' is alltoall's one lowering, named in key and span
        fn = self._compiled(self._key("alltoall", x, "direct"), build, x)
        span = "coll.xla.launch:alltoall/direct"
        self._fast[fk] = (ep, fn, span)
        return _launch(span, fn, x)

    def reduce_scatter_block(self, x, op):
        x = self._to_mesh(x)
        fk = ("reduce_scatter_block", x.shape, x.dtype, op.uid)
        ep = var.epoch()            # snapshot BEFORE the decision reads
        hit = self._fast.get(fk)
        if hit is not None and hit[0] == ep:
            return _launch(hit[2], hit[1], x)
        n = self.comm.size
        alg = self._algorithm("reduce_scatter_block",
                              x.nbytes // max(n, 1), op.commute)
        # hier rsb is the psum lowering: sum ops only
        low, high, alg = self._hier_or_direct(
            alg if op.xla_prim == "sum" else "direct")
        # A 1-D block (N, c) puts the scatter axis inside the TPU's
        # memory tile, where XLA serves psum_scatter as a whole
        # all-reduce plus a slice. all_to_all and a local sum move the
        # same (n-1)/n bytes: 4.67 device ms a call against 5.21 (and
        # 5.82 for the native reduce-scatter after its relayout) for f32
        # at 256 MiB per rank on a v5e 2x2. 8- and 16-bit types keep
        # psum_scatter, as their relayout into the all-to-all compiles
        # as straight-line code (5 min for 256 MiB of bf16); so do
        # ragged rows (unmeasured) and blocks of two or more dims (a
        # native reduce-scatter with no relayout).
        c = x.shape[-1]
        if (alg == "direct" and op.xla_prim == "sum" and x.ndim == 3
                and c > 0 and c % 128 == 0 and x.dtype.itemsize == 4):
            alg = "alltoall_sum"

        def build():
            if alg == "hier":
                inner = self._hier_rsb_inner(low, high, x.shape[2:])
            elif alg == "direct" and op.xla_prim == "sum":
                def inner(b):                   # (1, N, *s) -> (1, *s)
                    return jax.lax.psum_scatter(b[0], AXIS,
                                                scatter_dimension=0,
                                                tiled=True)
            else:
                def inner(b):
                    y = jax.lax.all_to_all(b[0], AXIS, split_axis=0,
                                           concat_axis=0, tiled=True)
                    # jnp.sum/prod widen 32-bit ints where x64 is on
                    return op.reduce_tree(y, axis=0)[None].astype(y.dtype)
            return self._smap(inner, x.ndim, x.ndim - 1)
        fn = self._compiled(
            self._key("reduce_scatter_block", x, op.uid, alg), build, x)
        span = f"coll.xla.launch:reduce_scatter_block/{alg}"
        self._fast[fk] = (ep, fn, span)
        return _launch(span, fn, x)

    def _prefix(self, g, op):
        # Fused prefix kernels only for the *predefined* ops: a user op
        # may legally reuse a predefined name but carry any combiner.
        if op.predefined:
            if op.name == "sum":
                return jnp.cumsum(g, axis=0)
            if op.name == "prod":
                return jnp.cumprod(g, axis=0)
            if op.name == "max":
                return jax.lax.cummax(g, axis=0)
            if op.name == "min":
                return jax.lax.cummin(g, axis=0)
        return jax.lax.associative_scan(op.fn, g, axis=0)

    # scan and exscan have one lowering each; their keys name it 'direct'
    def scan(self, x, op):
        x = self._to_mesh(x)

        def build():
            def inner(b):                       # (1, *s) -> (1, *s)
                g = jax.lax.all_gather(b[0], AXIS, axis=0, tiled=False)
                pre = self._prefix(g, op)
                idx = jax.lax.axis_index(AXIS)
                return jax.lax.dynamic_slice_in_dim(pre, idx, 1, 0)
            return self._smap(inner, x.ndim, x.ndim)
        return self._compiled(self._key("scan", x, op.uid, "direct"),
                              build, x)(x)

    def exscan(self, x, op):
        x = self._to_mesh(x)

        def build():
            def inner(b):
                g = jax.lax.all_gather(b[0], AXIS, axis=0, tiled=False)
                pre = self._prefix(g, op)
                idx = jax.lax.axis_index(AXIS)
                # Rank 0's recvbuf is undefined per MPI; clamp to row 0.
                row = jnp.maximum(idx - 1, 0)
                return jax.lax.dynamic_slice_in_dim(pre, row, 1, 0)
            return self._smap(inner, x.ndim, x.ndim)
        return self._compiled(self._key("exscan", x, op.uid, "direct"),
                              build, x)(x)

    def _barrier_arrays(self):
        # Engineered barrier (the fork's gba_barrier/switch_barrier
        # concern, coll_gba_barrier.h:20-21,56): everything a barrier
        # call needs — the token array AND the compiled executable — is
        # staged once per (communicator, algorithm) so the per-call cost
        # is one dispatch of a pre-compiled scalar collective. Round 1
        # allocated jnp.ones + device_put on every call, which put two
        # host->device transfers on the hot path.
        low, high, alg = self._hier_or_direct(
            self._algorithm("barrier", 4))
        st = self._barrier_tokens.get(alg)
        if st is None:
            n = self.comm.size

            def build():
                if alg == "hier":
                    return self._smap(
                        self._hier_barrier_inner(low, high), 1, 1)
                return self._smap(lambda b: jax.lax.psum(b, AXIS), 1, 1)
            fn = self._compiled(("barrier", n, alg), build)
            token = self._to_mesh(jnp.ones((n,), jnp.int32))
            fn(token)                    # warm: compile off the hot path
            st = (token, fn)
            self._barrier_tokens[alg] = st
        token, fn = st
        return [fn(token)]

    def barrier(self) -> None:
        jax.block_until_ready(self._barrier_arrays())

    def _ibarrier_arrays(self):
        # arrays backing an async barrier (the coll/nbc component owns
        # the schedule-based MPI_Ibarrier slot)
        return self._barrier_arrays()


class XlaCollComponent(Component):
    name = "xla"

    def register_params(self):
        var.var_register("coll", "xla", "priority", vtype="int", default=40,
                         help="Selection priority of the XLA-native "
                              "collective component")
        var.var_register(
            "coll", "xla", "cache_max_entries", vtype="int", default=256,
            help="Per-module cap on cached compiled executables "
                 "(each of the two caches); least-recently-used "
                 "entries evict beyond it, bounding HBM/host growth "
                 "under shape-varying workloads")
        var.var_register(
            "coll", "xla", "allreduce_algorithm", vtype="str",
            default="auto", enumerator=["auto", "direct", "hier"],
            help="Allreduce lowering: direct fused XLA collective or "
                 "han-style two-level hierarchy (auto: decision table)")
        var.var_register(
            "coll", "xla", "segsize", vtype="int", default=1 << 20,
            help="Segment size in bytes for the compressed allreduce's "
                 "segmented ring (the tuned segsize knob): the payload "
                 "splits into up to 8 independent quantized ring chains "
                 "XLA's async scheduler may overlap")
        var.var_register(
            "coll", "xla", "allgather_algorithm", vtype="str",
            default="auto", enumerator=["auto", "direct", "hier"],
            help="Allgather lowering: fused XLA all_gather or two-tier "
                 "hierarchy")
        var.var_register(
            "coll", "xla", "bcast_algorithm", vtype="str",
            default="auto", enumerator=["auto", "direct", "hier"],
            help="Bcast lowering: root-masked psum or two-tier "
                 "hierarchy")
        var.var_register(
            "coll", "xla", "reduce_algorithm", vtype="str",
            default="auto",
            enumerator=["auto", "alias", "rabenseifner_root",
                        "in_order_binary"],
            help="Reduce lowering: allreduce alias (one fused psum), "
                 "root-targeted redscat+binomial-collect (half the "
                 "alias's wire traffic; sum ops), or the in-order "
                 "binary tree (rank-ordered combines — correct for "
                 "non-commutative ops)")
        var.var_register(
            "coll", "xla", "gather_algorithm", vtype="str",
            default="auto", enumerator=["auto", "allgather", "binomial"],
            help="Gather lowering: allgather alias (one fused op) or "
                 "root-targeted binomial tree (1/n the wire bytes)")
        var.var_register(
            "coll", "xla", "scatter_algorithm", vtype="str",
            default="auto", enumerator=["auto", "direct", "binomial"],
            help="Scatter lowering: fused all_to_all or root-targeted "
                 "binomial fan-out")
        var.var_register(
            "coll", "xla", "reduce_scatter_block_algorithm", vtype="str",
            default="auto", enumerator=["auto", "direct", "hier"],
            help="Reduce_scatter_block lowering: fused psum_scatter or "
                 "two-tier hierarchy (sum ops)")
        var.var_register(
            "coll", "xla", "barrier_algorithm", vtype="str",
            default="auto", enumerator=["auto", "direct", "hier"],
            help="Barrier lowering: scalar psum or two-tier hierarchy")

    def comm_query(self, comm):
        if comm is None or not getattr(comm, "mesh", None):
            return None
        prio = var.var_get("coll_xla_priority", 40)
        return (prio, XlaCollModule(comm))


coll_framework.register(XlaCollComponent())
