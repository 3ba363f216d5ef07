"""RankCommunicator — the per-rank (multi-controller) execution model.

Behavioral spec: the textbook MPI model every reference binding serves —
``MPI_Comm_rank`` returns THIS process's rank
(``ompi/mpi/c/comm_rank.c.in``), point-to-point moves bytes between
processes (``ompi/mca/pml/ob1/pml_ob1_recvfrag.c:296-330`` matching),
collectives are called by every member and return each caller its local
result, and ``mpirun -n N`` launches N such processes
(``ompi/tools/mpirun/main.c:157-180``).

TPU-native re-design: one OS process == one MPI rank, bound 1:1 to the
JAX coordination service (``rank() == jax.process_index()``). Two data
planes, mirroring the reference's split between byte transports and
(here) the ICI fabric:

- **Host tier (btl/tcp)**: pt2pt and generic-object collectives run
  textbook algorithms (binomial bcast/reduce, dissemination barrier,
  pairwise alltoall — the coll/base registry,
  ``coll_base_functions.h:185-320``) over the framed TCP transport, with
  addresses modex'd through the coordination-service KV (the PMIx role).
- **Device tier (XLA/ICI)**: collectives on ``jax.Array`` buffers
  assemble a global array over the communicator's device mesh
  (one shard per rank via ``make_array_from_single_device_arrays``) and
  dispatch ONE compiled SPMD program using XLA collectives
  (psum/all_gather/all_to_all/psum_scatter under ``shard_map``) — every
  member calls the collective, which is exactly the multi-controller
  contract jit requires. No bytes touch the host tier.

Internal collective traffic rides a separate CID channel (``("c", cid)``)
so it can never cross-match user point-to-point tags — MPI's hidden
collective context id, re-created literally.

CID agreement: communicator creation is collective, so a deterministic
derivation (parent cid + per-parent creation sequence + color) gives
every member the same child CID with zero extra traffic — the property
the reference's iterative CID allreduce establishes
(``comm_cid.c:61-109``).
"""
from __future__ import annotations

import functools
import itertools
import queue
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ompi_tpu.compress import wire as _cwire
from ompi_tpu.core import op as op_mod
from ompi_tpu.core.errhandler import (ERR_ARG, ERR_COMM, ERR_COUNT, ERR_OP,
                                      ERR_RANK, ERR_REVOKED, ERR_ROOT,
                                      ERRORS_ARE_FATAL, Errhandler, MPIError)
from ompi_tpu.ft import inject as _inject
from ompi_tpu.core.group import Group, UNDEFINED
from ompi_tpu.core.info import Info
from ompi_tpu.core.request import Request, Status
from ompi_tpu.pml.perrank import (ANY_SOURCE, ANY_TAG, PROC_NULL,
                                  PerRankEngine, RankRequest, Router)
from ompi_tpu.runtime import spc
from ompi_tpu.utils import hooks as _hooks_mod

AXIS = "mpi_r"

# Compressed host-tier allreduce: worlds at or below this size use the
# direct code-exchange schedule (one parallel round, single quant
# error); larger worlds use the binomial reduce + code-forwarding
# bcast, whose per-rank wire bytes stay O(1) (docs/COMPRESSION.md).
_WIRE_DIRECT_MAX_RANKS = 4


class _HiddenChannel:
    """A hidden matching-channel view of a communicator: same ranks,
    separate CID, so internal/tool messages never match user receives.
    Channels: "c" collectives, "part" partitioned pt2pt, "sync"
    clock probes."""

    def __init__(self, comm: "RankCommunicator", prefix: str):
        self._comm = comm
        self.cid = (prefix, comm.cid)

    @property
    def size(self) -> int:
        return self._comm.size

    def rank(self) -> int:
        return self._comm.rank()

    def world_rank_of(self, local: int) -> int:
        return self._comm.world_rank_of(local)


class _CollChannel(_HiddenChannel):
    def __init__(self, comm: "RankCommunicator"):
        super().__init__(comm, "c")


def hidden_engine(comm: "RankCommunicator", prefix: str):
    """The lazily-created matching engine for one hidden channel of
    ``comm`` — created once (two engines on one CID would split
    matching state), closed with the communicator."""
    with comm._lock:
        eng = comm._aux_pmls.get(prefix)
        if eng is None:
            eng = PerRankEngine(_HiddenChannel(comm, prefix),
                                comm.router)
            comm._aux_pmls[prefix] = eng
    return eng


# thread-local CALL CONTEXT that must travel with a funneled body:
# layers above (the C ABI sets a reduction-datatype context on the
# caller thread before invoking blocking reductions) register a
# capture hook; _coll_serial snapshots every registered context at
# funnel time and applies/resets it around the body on the worker.
_TLS_PROPAGATORS: List[Callable[[], Tuple[Callable, Callable]]] = []


def register_tls_propagator(
        capture: Callable[[], Tuple[Callable, Callable]]) -> None:
    """``capture()`` runs on the funneling caller and returns
    ``(apply, reset)`` closures run on the worker around the body."""
    _TLS_PROPAGATORS.append(capture)


class _SlotRequest(Request):
    """A request completed by a posted CombineSlot (the persistent
    small-allreduce's Start residue): wait blocks on the slot's event,
    collects the rank-ordered fold, and retires the slot's tag."""

    __slots__ = ("_eng", "_tag_", "_slot", "_epilogue")

    def __init__(self, eng, tag: int, slot, epilogue):
        super().__init__(arrays=[])
        self._complete = False
        self._eng = eng
        self._tag_ = tag
        self._slot = slot
        self._epilogue = epilogue

    def _collect(self) -> None:
        try:
            out = self._slot.wait()      # set already: returns/raises
        finally:
            self._eng.end_combine(self._tag_)
            self._complete = True
        self._result = self._epilogue(out)

    def test(self):
        if not self._complete:
            if not self._slot._event.is_set():
                return False, None
            self._collect()
        return True, self.status

    def wait(self, timeout: Optional[float] = None):
        if not self._complete:
            try:
                out = self._slot.wait(
                    timeout if timeout is not None else 600)
            finally:
                self._eng.end_combine(self._tag_)
                self._complete = True
            self._result = self._epilogue(out)
        return self.status

    def get(self):
        self.wait()
        return self._result


def _serialized(fn):
    """Collective-execution serializer — applied to every public
    collective entry that (transitively) draws the comm's sequence
    tag. ``_tag()`` draws at EXECUTION time and its cross-rank
    agreement rests on one invariant: each rank executes the comm's
    collectives in issue order on a single thread at a time.
    Deferred i-collectives run on the comm's serial worker, so a
    blocking collective issued while any are pending must queue
    BEHIND them (two concurrent draws would order differently on
    different ranks and cross-match payloads — e.g. a barrier's
    round messages consumed as a scan's partial). With an idle
    worker the call runs inline: no thread hop on the latency path.
    This is the chokepoint the C ABI, the Python API, and internal
    collective users (window creation, file IO, dpm) all share."""
    @functools.wraps(fn)
    def entry(self, *a, **kw):
        return self._coll_serial(fn, self, *a, **kw)
    return entry


class RankCommunicator:
    """A communicator whose caller is exactly one rank."""

    is_per_rank = True

    def __init__(self, group: Group, my_world_rank: int, router: Router, *,
                 cid: Any = "w", name: str = "",
                 parent: Optional["RankCommunicator"] = None,
                 errhandler: Optional[Errhandler] = None,
                 info: Optional[Info] = None):
        self.group = group
        self.router = router
        self.cid = cid
        self.name = name or f"comm#{cid}"
        self.info = info.dup() if info else Info()
        self.errhandler = errhandler or (
            parent.errhandler if parent else ERRORS_ARE_FATAL)
        self.attributes: Dict[int, Any] = {}
        self.topo = None
        self._freed = False
        self._rank = group.rank_of(my_world_rank)
        if self._rank == UNDEFINED:
            raise MPIError(ERR_RANK,
                           f"process world rank {my_world_rank} is not a "
                           f"member of {self.name}")
        self._my_world = my_world_rank
        self._pml = PerRankEngine(self, router)
        self._coll_pml = PerRankEngine(_CollChannel(self), router)
        self._aux_pmls: Dict[str, PerRankEngine] = {}   # hidden_engine
        # ownership list (MPI-4 Sessions): a session-created comm
        # carries the session's comm list so DERIVED comms
        # (dup/split/cart/shrink) register too — finalize must quiesce
        # the whole family, not just the direct creations
        owners = getattr(parent, "_owner_list", None)
        if owners is not None:
            self._owner_list = owners
            owners.append(self)
        # the interposition tier of the coll framework (sync /
        # monitoring) applies to per-rank comms too — same MCA vars,
        # same boundary, wrapping the bound collective methods
        from ompi_tpu.coll.interpose_perrank import interpose
        interpose(self)
        self._seq = itertools.count(1)          # collective sequence
        self._create_seq = itertools.count(1)   # comm-creation sequence
        self._dev_fns: Dict[Any, Callable] = {}
        self._small_fold: Dict[Any, Callable] = {}  # op.uid -> combiner
        self._mesh_cache = None
        self._lock = threading.Lock()
        self._cq: Optional["queue.Queue"] = None   # serial collective
        self._cworker: Optional[threading.Thread] = None  # executor
        self._cclosed = False            # set by _coll_drain: no new
        # jobs may spawn a worker after teardown began
        # revoke plane (MPIX_Comm_revoke, docs/RESILIENCE.md): when the
        # router's reliable broadcast revokes this cid, every pending
        # operation on the comm completes with ERR_REVOKED
        router.register_revoke_cb(self.cid, self._on_revoked)

    # ------------------------------------------------------------------
    @property
    def size(self) -> int:
        return self.group.size

    def rank(self) -> int:
        """MPI_Comm_rank: this process's rank (comm_rank.c.in) — the
        round-2 gap closed: per-rank worlds no longer report 0
        everywhere."""
        return self._rank

    @property
    def is_multiprocess(self) -> bool:
        return True

    def world_rank_of(self, local: int) -> int:
        return self.group.world_ranks[local]

    def _err(self, error_class: int, msg: str = ""):
        return self.errhandler.invoke(self, error_class, msg)

    def _check(self) -> None:
        if self._freed:
            raise MPIError(ERR_COMM, "communicator has been freed")
        if self.router.is_revoked(self.cid):
            # ULFM: every operation on a revoked comm (except the
            # recovery surface — shrink/agree/get_failed/free, which
            # bypass _check) raises ERR_REVOKED (comm_revoke.c)
            raise MPIError(ERR_REVOKED,
                           f"{self.name} has been revoked")

    def _validate_root(self, root: int) -> int:
        if not (0 <= root < self.size):
            self._err(ERR_ROOT, f"root {root} out of range")
        return root

    def _validate_op(self, op) -> op_mod.Op:
        if not isinstance(op, op_mod.Op) or op.fn is None:
            self._err(ERR_OP, "invalid reduction op")
        return op

    # ==================================================================
    # Point-to-point (textbook signatures: caller IS the rank)
    # ==================================================================
    def send(self, data: Any, dest: int, tag: int = 0) -> None:
        self._check()
        spc.record("pml_send", 1)
        self._pml.send(data, dest, tag)

    def isend(self, data: Any, dest: int, tag: int = 0) -> Request:
        self._check()
        spc.record("pml_send", 1)
        return self._pml.send(data, dest, tag)

    def ssend(self, data: Any, dest: int, tag: int = 0) -> None:
        self._check()
        spc.record("pml_send", 1)
        self._pml.send(data, dest, tag, synchronous=True)

    def bsend(self, data: Any, dest: int, tag: int = 0) -> None:
        self.send(data, dest, tag)        # sends are always buffered

    def recv(self, source: int = ANY_SOURCE, tag: int = ANY_TAG
             ) -> Tuple[Any, Status]:
        self._check()
        spc.record("pml_recv", 1)
        return self._pml.recv(source, tag)

    def irecv(self, source: int = ANY_SOURCE,
              tag: int = ANY_TAG) -> RankRequest:
        self._check()
        spc.record("pml_recv", 1)
        return self._pml.irecv(source, tag)

    def sendrecv(self, senddata: Any, dest: int, source: int = ANY_SOURCE,
                 sendtag: int = 0, recvtag: int = ANY_TAG
                 ) -> Tuple[Any, Status]:
        """Deadlock-free by construction: the receive is posted before
        the (eager, buffered) send."""
        self._check()
        req = self._pml.irecv(source, recvtag)
        self._pml.send(senddata, dest, sendtag)
        st = req.wait()
        return req.get(), st

    def probe(self, source: int = ANY_SOURCE, tag: int = ANY_TAG) -> Status:
        self._check()
        return self._pml.probe(source, tag)

    def iprobe(self, source: int = ANY_SOURCE, tag: int = ANY_TAG):
        self._check()
        return self._pml.iprobe(source, tag)

    def mprobe(self, source: int = ANY_SOURCE, tag: int = ANY_TAG):
        self._check()
        return self._pml.mprobe(source, tag)

    def improbe(self, source: int = ANY_SOURCE, tag: int = ANY_TAG):
        self._check()
        flag, status = self._pml.iprobe(source, tag)
        if not flag:
            return False, None, None
        return True, self._pml.mprobe(source, tag), status

    def mrecv(self, message) -> Tuple[Any, Status]:
        return self._pml.mrecv(message)

    def send_init(self, data: Any, dest: int, tag: int = 0) -> Request:
        self._check()
        return Request(persistent_start=lambda: self._pml.send(
            data, dest, tag))

    def recv_init(self, source: int = ANY_SOURCE,
                  tag: int = ANY_TAG) -> Request:
        self._check()
        return Request(persistent_start=lambda: self._pml.irecv(
            source, tag))

    # ==================================================================
    # Collectives — host tier (textbook algorithms over btl/tcp)
    # ==================================================================
    def _tag(self) -> int:
        """Per-collective sequence tag: calls are collective, so every
        member draws the same value; successive collectives can never
        cross-match even under wildcard-free FIFO reordering."""
        return next(self._seq)

    def _csend(self, dest: int, tag: int, data: Any) -> None:
        self._coll_pml.send(data, dest, tag)

    def _crecv(self, src: int, tag: int) -> Any:
        data, _ = self._coll_pml.recv(src, tag)
        return data

    # -- staged device tier (the coll/accelerator bracket, inverted) ---
    # The reference stages device buffers OUT to run host algorithms
    # (coll_accelerator_allreduce.c:55-80); here host/C buffers above
    # coll_tuned_stage_min_bytes stage IN — one device shard per rank —
    # so the collective rides the fabric as one compiled XLA program
    # and the result copies back. This is the path that puts textbook
    # C programs (numpy buffers via api/cabi.py) on the TPU.
    def _stage_min(self, func: str) -> int:
        # one decision plane with the single-controller tier: the flat
        # MCA var plus the per-collective dynamic-rules override
        from ompi_tpu.coll.tuned import stage_min_for
        return stage_min_for(func)

    def _stageable(self, data: Any, op: Optional[op_mod.Op] = None,
                   nbytes: Optional[int] = None,
                   func: str = "allreduce") -> bool:
        """Local staging decision. Only called with arguments whose
        relevant properties (shape, dtype, size) are identical on every
        member by MPI semantics, so all ranks decide alike — the device
        dispatch below is collective and a split decision would hang
        the job. Asymmetric-argument collectives (bcast) must propagate
        one rank's decision instead. ``nbytes`` overrides the payload
        size for collectives whose full payload spans several chunks."""
        if not isinstance(data, np.ndarray):
            return False
        if data.dtype.kind not in "fiub":
            return False
        if (data.nbytes if nbytes is None else nbytes) \
                < self._stage_min(func):
            return False
        if data.dtype.itemsize == 8:
            import jax
            if not jax.config.jax_enable_x64:
                return False             # silent downcast would corrupt
        if op is not None:
            if op.is_loc or op.fn is None:
                return False             # pair ops stay on the host fold
            if getattr(op, "_c_callback", None) is not None:
                return False             # C fn pointers cannot trace
        return self._mesh() is not None

    @_serialized
    def barrier(self) -> None:
        """Dissemination barrier: ceil(log2 n) rounds
        (coll_base_barrier.c bruck/dissemination)."""
        self._check()
        spc.record("coll_barrier", 1)
        n, r, t = self.size, self._rank, self._tag()
        k = 1
        while k < n:
            self._csend((r + k) % n, t, None)
            self._crecv((r - k) % n, t)
            k <<= 1

    @_serialized
    def bcast(self, data: Any = None, root: int = 0) -> Any:
        """Binomial-tree bcast (coll_base_bcast.c binomial): non-root
        callers pass nothing and receive the root's value.

        Staged device tier (the coll/accelerator bracket inverted,
        ``coll_accelerator_allreduce.c:55-80``): bcast's args are
        asymmetric — non-root callers may hold nothing — so the root's
        staging decision travels first as a small host-tier metadata
        bcast, then every rank joins the one compiled device bcast
        with a right-shaped local buffer. Cost: log(n) tiny messages
        before a >=stage_min_bytes payload rides the fabric once."""
        self._check()
        self._validate_root(root)
        spc.record("coll_bcast", 1)
        if isinstance(data, _dev_array_type()) and self._mesh() is not None:
            return self._device_bcast(data, root)
        if self._mesh() is not None:
            # ONE binomial round carries (staging decision, payload):
            # staged -> (meta, None), the payload rides the device op;
            # not staged -> (None, data), the payload already arrived.
            if self._rank == root:
                if self._stageable(data, func="bcast"):
                    msg = (("stage", tuple(data.shape), data.dtype.str),
                           None)
                elif self._pipeline_bcast_ok(data):
                    msg = (("chain",), None)
                elif _cwire.eligible(data):
                    # quantize ONCE at the root; the binomial tree
                    # forwards the codes losslessly (one quantization
                    # error total, ~1/4 the bytes per hop)
                    msg = (None, _cwire.encode(data))
                else:
                    msg = (None, data)
            else:
                msg = None
            meta, payload = self._host_bcast(msg, root)
            if meta is not None and meta[0] == "stage":
                shape, dtstr = meta[1], meta[2]
                local = (np.ascontiguousarray(data) if self._rank == root
                         else np.empty(shape, np.dtype(dtstr)))
                spc.record("coll_staged_device", 1)
                res = self._device_bcast(local, root)
                # the root already holds the payload: participate in
                # the collective but skip the redundant D2H copy
                return data if self._rank == root else np.asarray(res)
            if meta is not None and meta[0] == "chain":
                return self._pipelined_chain_bcast(data, root)
            return data if self._rank == root \
                else _cwire.maybe_decode(payload)
        if self._rank == root and _cwire.eligible(data):
            self._host_bcast(_cwire.encode(data), root)
            return data
        return _cwire.maybe_decode(self._host_bcast(data, root))

    def _host_bcast(self, data: Any, root: int) -> Any:
        n, t = self.size, self._tag()
        vr = (self._rank - root) % n
        mask = 1
        while mask < n:                  # climb to my parent
            if vr & mask:
                data = self._crecv(((vr - mask) + root) % n, t)
                break
            mask <<= 1
        mask >>= 1
        while mask:                      # feed my subtree
            if vr + mask < n:
                self._csend(((vr + mask) + root) % n, t, data)
            mask >>= 1
        return data

    @_serialized
    def reduce(self, data: Any, op: op_mod.Op = op_mod.SUM,
               root: int = 0) -> Any:
        """Binomial reduce for commutative ops; linear ordered fold at
        root otherwise (the ordering constraint of
        coll_base_allreduce.c:291-294)."""
        self._check()
        self._validate_op(op)
        self._validate_root(root)
        spc.record("coll_reduce", 1)
        n, t = self.size, self._tag()
        if n == 1:
            return data
        if not op.commute:
            rows = self.gather(data, root)
            if self._rank != root:
                return None
            acc = rows[0]
            for x in rows[1:]:
                acc = _apply(op, acc, x)
            return acc
        if self._stageable(data, op, func="reduce"):
            spc.record("coll_staged_device", 1)
            y = self._device_allreduce(np.ascontiguousarray(data), op)
            # only the root pays the D2H copy; others just participate
            return np.asarray(y) if self._rank == root else None
        # compressed wire hops (docs/COMPRESSION.md): large float sum
        # payloads quantize per hop — decode, fold, re-encode at every
        # tree level (the EQuARX reduction-hop structure on the host
        # tier). The decision is a pure function of (shape, dtype,
        # nbytes, op), identical on every member by MPI semantics.
        use_wire = _cwire.eligible(data, op)
        vr = (self._rank - root) % n
        acc = data
        k = 1
        while k < n:
            if vr & k:
                self._csend(((vr - k) + root) % n, t,
                            _cwire.encode(acc) if use_wire else acc)
                return None
            if vr + k < n:
                acc = _apply(op, acc, _cwire.maybe_decode(
                    self._crecv(((vr + k) + root) % n, t)))
            k <<= 1
        return acc if self._rank == root else None

    def _small_allreduce(self, data: Any, op: op_mod.Op) -> Any:
        """Combined small-message allreduce: every
        rank eagerly sends its contribution to every peer ONCE; btl
        reader threads park arrivals straight into a combining slot
        (``btl_sendi`` role — no matching, no per-message request); the
        last arrival folds in deterministic rank order and wakes the
        caller exactly once. One message latency + one wakeup replaces
        the reduce-then-bcast chain's log(n) serialized round trips —
        the path that held 8 B latency at ~2.2 ms for two rounds.
        Rank-ordered folding keeps non-commutative ops and float
        reproducibility exact (same canonical order on every rank).

        Sub-eager dispatch cache (round 6): the fold combiner resolves
        ONCE per op to the dtype-preserving numpy kernel — the generic
        ``_apply`` boxed scalar contributions through the jnp combiner
        on the reader thread, a per-fold JAX dispatch that made the
        scalar 8 B row 8x the ndarray row on the round-5 record — and
        the outbound side multicasts one marshalled frame through the
        engine's cached header templates (``send_small``)."""
        n, r, t = self.size, self._rank, self._tag()
        eng = self._coll_pml
        fold = self._small_fold_for(op)
        slot = eng.post_combine(t, n, n - 1, fold, own=(r, data))
        try:
            eng.send_small(data, [(r + off) % n for off in range(1, n)],
                           t)
            out = slot.wait()
        finally:
            eng.end_combine(t)
        if not isinstance(data, np.ndarray) and (
                isinstance(out, np.generic)
                or (isinstance(out, np.ndarray) and out.ndim == 0)):
            out = out.item()             # scalar in, python scalar out
        return out

    def _small_fold_for(self, op: op_mod.Op) -> Callable:
        """The memoized deterministic rank-order fold for ``op`` (the
        sub-eager dispatch cache's combiner leg, shared by the one-shot
        small path and the persistent plan prebinding)."""
        fold = self._small_fold.get(op.uid)
        if fold is None:
            npfn = (op_mod.NP_COMBINERS.get(op.name)
                    if op.predefined and not op.is_loc else None)
            if npfn is not None:
                def fold(vals, _fn=npfn):
                    acc = vals[0]
                    for v in vals[1:]:
                        acc = _fn(acc, v)
                    return acc
            else:
                def fold(vals):
                    acc = vals[0]
                    for v in vals[1:]:
                        acc = _apply(op, acc, v)
                    return acc
            self._small_fold[op.uid] = fold
        return fold

    def bind_small_allreduce(self, data: Any, op: op_mod.Op) -> Callable:
        """Pre-bound persistent small-allreduce launcher
        (coll/persistent): the fold combiner, destination ring, and the
        engine's multicast template resolve ONCE here. The returned
        launcher is Start-only — it draws the sequence tag (through
        the serialized chokepoint so tag order can never race deferred
        i-collectives), posts the combining slot, and multicasts this
        rank's contribution; completion rides the slot through the
        returned request. N outstanding starts therefore PIPELINE:
        every contribution is on the wire before the first wait, and
        reader threads feed all N slots concurrently. ``data`` (the
        registered buffer, refilled by the app between rounds) is
        re-read at every Start."""
        n, r = self.size, self._rank
        fold = self._small_fold_for(op)
        dests = [(r + off) % n for off in range(1, n)]
        eng = self._coll_pml
        send = eng.bind_small_multicast(data, dests)
        scalar_in = not isinstance(data, np.ndarray)

        def epilogue(out):
            if scalar_in and (isinstance(out, np.generic)
                              or (isinstance(out, np.ndarray)
                                  and out.ndim == 0)):
                out = out.item()
            return out

        def post():
            spc.record("coll_allreduce", 1)
            spc.record("coll_small_combine", 1)
            t = self._tag()
            slot = eng.post_combine(t, n, n - 1, fold, own=(r, data))
            send(data, t)
            return t, slot

        def launch() -> Request:
            t, slot = self._coll_serial(post)
            return _SlotRequest(eng, t, slot, epilogue)
        return launch

    def _small_allreduce_ok(self, data: Any, op: op_mod.Op) -> bool:
        from ompi_tpu.coll.tuned import small_allreduce_limits
        max_bytes, max_ranks = small_allreduce_limits()
        if not (1 < self.size <= max_ranks):
            return False
        if isinstance(data, np.ndarray):
            return data.nbytes <= max_bytes
        return isinstance(data, (int, float, complex, np.generic))

    @_serialized
    def allreduce(self, data: Any, op: op_mod.Op = op_mod.SUM) -> Any:
        self._check()
        self._validate_op(op)
        if _inject.active:               # named kill site for the FT
            _inject.point("coll.allreduce")   # drill (ft/inject)
        spc.record("coll_allreduce", 1)
        if _hooks_mod._hooks:            # tool bound: fire the event
            _hooks_mod.fire("coll_allreduce", self,
                            {"value": int(getattr(data, "nbytes", 0)
                                          or 0)})
        if isinstance(data, _dev_array_type()) and self._mesh() is not None:
            return self._device_allreduce(data, op)
        if self._stageable(data, op):
            spc.record("coll_staged_device", 1)
            return np.asarray(self._device_allreduce(
                np.ascontiguousarray(data), op))
        if self._small_allreduce_ok(data, op):
            spc.record("coll_small_combine", 1)
            return self._small_allreduce(data, op)
        if _cwire.eligible(data, op) \
                and 1 < self.size <= _WIRE_DIRECT_MAX_RANKS:
            return self._wire_allreduce_direct(data, op)
        if self._shm_fold_ok(data, op):
            return self._shm_fold_allreduce(data, op)
        if self._pipeline_ring_ok(data, op):
            return self._pipelined_ring_allreduce(data, op)
        r = self.reduce(data, op, 0)
        if _cwire.eligible(data, op):
            # allreduce must return the SAME value on every rank: the
            # root broadcasts the wire form as an opaque payload and
            # every member (root included) decodes the same image —
            # root keeping its exact fold would diverge from the
            # quantized copies the peers receive.
            w = _cwire.encode(r) if self._rank == 0 else None
            return _cwire.maybe_decode(self.bcast(w, 0))
        return self.bcast(r, 0)

    def _wire_allreduce_direct(self, data, op):
        """Direct-exchange compressed allreduce (small worlds): every
        rank quantizes its contribution ONCE and multicasts the codes;
        every rank decodes all n images and folds them in rank order —
        one fully parallel round (no serialized tree levels), exactly
        one quantization error per contribution (lossless code
        forwarding), and bitwise-identical results everywhere (all
        ranks fold the same images in the same order). Wire cost is
        (n-1)*qbytes per rank vs the tree's ~2*qbytes, the winning
        trade while n is small — the tree path above takes over past
        _WIRE_DIRECT_MAX_RANKS."""
        n, r, t = self.size, self._rank, self._tag()
        spc.record("coll_compress_direct", 1)
        w = _cwire.encode(data)
        for off in range(1, n):
            self._csend((r + off) % n, t, w)
        parts: Dict[int, Any] = {r: w}
        for _ in range(n - 1):
            d, st = self._coll_pml.recv(ANY_SOURCE, t)
            parts[st.source] = d
        out = None
        for i in range(n):
            img = _cwire.maybe_decode(parts[i])
            out = img if out is None else _apply(op, out, img)
        return out

    # -- in-segment shared-memory fold (btl/shmseg, docs/LARGEMSG.md) --
    def _shm_fold_ok(self, data: Any, op: op_mod.Op) -> bool:
        """Rank-symmetric gate for the in-segment fold: every member
        must sit on this host (the fold IS the shared mapping), the
        payload must fit one fold workspace, the op must have a numpy
        kernel, and the coll/decision shm rows must select it.
        Commutativity is NOT required — each slice is folded once, in
        rank order, by exactly one rank."""
        if self.size < 2 or not isinstance(data, np.ndarray):
            return False
        if data.dtype.kind not in "fiu" or data.ndim == 0:
            return False
        if op.is_loc or not op.predefined:
            return False
        if op_mod.NP_COMBINERS.get(op.name) is None:
            return False
        plane = getattr(self.router.endpoint, "shm_seg", None)
        if plane is None or int(data.nbytes) > plane.slot_bytes:
            return False
        from ompi_tpu.coll import decision
        rules = decision.shm_rules().get("allreduce")
        if not rules:
            return False
        if decision._match(rules, self.size,
                           int(data.nbytes)) != "shm_fold":
            return False
        ep = self.router.endpoint
        return all(ep._is_same_host(self.world_rank_of(i))
                   for i in range(self.size) if i != self._rank)

    def _fold_barrier(self, t: int) -> None:
        """Dissemination barrier on a private tag — the fold's two
        phase fences (the public ``barrier`` is @_serialized and may
        not be re-entered from inside a collective)."""
        n, r = self.size, self._rank
        k = 1
        while k < n:
            self._csend((r + k) % n, t, None)
            self._crecv((r - k) % n, t)
            k <<= 1

    def _shm_fold_allreduce(self, data: np.ndarray,
                            op: op_mod.Op) -> np.ndarray:
        """In-segment node-local allreduce (btl/shmseg fold
        workspaces): every rank writes its contribution into its own
        per-comm shared segment ONCE, then — after a fence — folds its
        slice of the element range across ALL members' segments in
        rank order and writes the folded slice back into every
        segment (disjoint slices, so writers never race). After the
        second fence each rank reads the complete result out of its
        OWN segment. ~4 byte-touches per rank vs the ring schedule's
        ~2·P, and bitwise-identical results everywhere (each slice is
        folded exactly once, in rank order, and every rank reads the
        same bytes). No third fence is needed: a rank's next phase-0
        write to its own segment is self-serialized behind its own
        read-out, and partners touch it again only after the next
        collective's first fence — which requires this rank to have
        moved on already."""
        from ompi_tpu.btl import shmseg as _shmseg
        n, r = self.size, self._rank
        spc.record("coll_shm_fold", 1)
        plane = self.router.endpoint.shm_seg
        token = _shmseg.coll_token(self.cid)
        arr = np.ascontiguousarray(data)
        shape, dtype = arr.shape, arr.dtype
        flat = arr.reshape(-1)
        nbytes = int(arr.nbytes)
        ws = plane.coll_segment(token)
        ws.buf[0:nbytes] = memoryview(flat).cast("B")
        self._fold_barrier(self._tag())  # contributions visible
        views = [np.frombuffer(
            plane.coll_attach(token, self.world_rank_of(i)).buf,
            dtype=dtype, count=flat.size) for i in range(n)]
        bounds = [(flat.size * i) // n for i in range(n + 1)]
        lo, hi = bounds[r], bounds[r + 1]
        npfn = op_mod.NP_COMBINERS[op.name]
        if hi > lo:
            acc = views[0][lo:hi].copy()
            for k in range(1, n):
                acc = npfn(acc, views[k][lo:hi])
            for v in views:
                v[lo:hi] = acc
        self._fold_barrier(self._tag())  # folded slices visible
        out = views[r].copy()
        _shmseg.stats["folds"] += 1
        from ompi_tpu import telemetry as _telemetry_mod
        if _telemetry_mod.active:
            hist = _telemetry_mod.SHMSEG
            if hist is not None:
                hist.record(nbytes)
        return out.reshape(shape)

    # -- segment-pipelined host tier (docs/LARGEMSG.md) ----------------
    def _pipeline_ring_ok(self, data: Any, op: op_mod.Op) -> bool:
        """Rank-symmetric gate for the pipelined ring: the decision
        rows (coll/decision.pipeline_rules) select by size and bytes,
        and the fold must be a commutative predefined op with a numpy
        kernel — the ring reassociates chunk folds exactly like the
        other REORDERING schedules."""
        if self.size < 2 or not isinstance(data, np.ndarray):
            return False
        if data.dtype.kind not in "fiu" or data.ndim == 0:
            return False
        if not op.commute or op.is_loc or not op.predefined:
            return False
        if op_mod.NP_COMBINERS.get(op.name) is None:
            return False
        from ompi_tpu.coll import decision
        rules = decision.pipeline_rules().get("allreduce")
        if not rules:
            return False
        return decision._match(rules, self.size,
                               int(data.nbytes)) == "pipelined_ring"

    def _pipelined_ring_allreduce(self, data: np.ndarray,
                                  op: op_mod.Op) -> np.ndarray:
        """Segment-pipelined ring allreduce for the host tier — the
        compressed device ring's (``coll/compressed``) analogue over the
        byte transport (coll_base_allreduce.c ring: reduce-scatter
        ring then allgather ring). Each rank ends up computing ONE
        chunk's full fold and circulating it, so results are bitwise
        identical everywhere; every chunk hop is a large pt2pt send
        that rides the pml's segment-pipelined rendezvous (striped
        over mpi_base_btl_rails rails), and since all ranks send and
        receive concurrently the wire time per step is one chunk, not
        two. Wire bytes per rank: 2(n-1)/n payloads with overlap — vs
        the serial reduce-then-bcast fallback's 2 payloads with none."""
        n, r, t = self.size, self._rank, self._tag()
        spc.record("coll_pipelined_ring", 1)
        arr = np.ascontiguousarray(data)
        shape, flat = arr.shape, arr.reshape(-1)
        bounds = [(flat.size * i) // n for i in range(n + 1)]
        # views, not copies: sends pack straight from the source buffer
        # (pml/pipeline's zero-copy segments); the fold below replaces
        # each entry with a fresh array, so the input is never mutated
        chunks = [flat[bounds[i]:bounds[i + 1]] for i in range(n)]
        right, left = (r + 1) % n, (r - 1) % n
        npfn = op_mod.NP_COMBINERS[op.name]
        # reduce-scatter ring: at step s, send chunk (r-s), fold the
        # incoming chunk (r-s-1); after n-1 steps this rank holds the
        # complete fold of chunk (r+1) % n
        for s in range(n - 1):
            si = (r - s) % n
            ri = (r - s - 1) % n
            req = self._coll_pml.irecv(left, t)
            self._csend(right, t, chunks[si])
            req.wait()
            inc = req.get()
            chunks[ri] = npfn(chunks[ri],
                              np.asarray(inc).reshape(chunks[ri].shape))
        # allgather ring: circulate the n fully-folded chunks
        own = (r + 1) % n
        cur = chunks[own]
        for s in range(n - 1):
            req = self._coll_pml.irecv(left, t)
            self._csend(right, t, cur)
            req.wait()
            cur = np.asarray(req.get())
            idx = (own - 1 - s) % n
            chunks[idx] = cur.reshape(chunks[idx].shape)
        out = chunks[0] if n == 1 else np.concatenate(
            [np.asarray(c).reshape(-1) for c in chunks])
        return out.reshape(shape).astype(arr.dtype, copy=False)

    def _pipeline_bcast_ok(self, data: Any) -> bool:
        """Root-side gate for the pipelined chain bcast; the decision
        travels to the other ranks in the metadata round (bcast's args
        are asymmetric, so only the root can decide)."""
        if self.size < 2 or not isinstance(data, np.ndarray):
            return False
        if data.dtype.kind not in "fiub" or data.ndim == 0:
            return False
        from ompi_tpu.coll import decision
        rules = decision.pipeline_rules().get("bcast")
        if not rules:
            return False
        return decision._match(rules, self.size,
                               int(data.nbytes)) == "pipelined_chain"

    def _pipelined_chain_bcast(self, data: Any, root: int) -> Any:
        """Segment-pipelined chain bcast (coll_base_bcast.c
        pipeline/chain): ranks form a chain from the root; the payload
        moves as a train of chunks, and every intermediate rank
        forwards chunk c while its predecessor is already sending
        chunk c+1 — after the chain fills, every link streams
        concurrently, so wall time approaches one payload's wire time
        plus chain-depth chunk latencies instead of depth full
        payloads. Chunks large enough also ride the pml's segmented
        rendezvous inside each hop."""
        n, t = self.size, self._tag()
        vr = (self._rank - root) % n
        succ = ((vr + 1) + root) % n if vr + 1 < n else None
        pred = ((vr - 1) + root) % n
        spc.record("coll_pipelined_chain", 1)
        if vr == 0:
            arr = np.ascontiguousarray(data)
            flat = arr.reshape(-1)
            from ompi_tpu.pml import pipeline as _pl
            seg = _pl.segment_bytes_for(int(arr.nbytes),
                                        self.router.endpoint)
            # chunk = a few segments: big enough to pipeline inside
            # the hop, small enough that the chain fills quickly
            per = max(1, (seg * 4) // max(arr.dtype.itemsize, 1))
            k = max(1, -(-flat.size // per))
            if succ is not None:
                self._csend(succ, t, (k, tuple(arr.shape),
                                      arr.dtype.str))
                for c in range(k):
                    self._csend(succ, t, flat[c * per:(c + 1) * per])
            return data
        k, shape, dtstr = self._crecv(pred, t)
        if succ is not None:
            self._csend(succ, t, (k, shape, dtstr))
        parts: List[Any] = []
        for c in range(k):
            part = self._crecv(pred, t)
            if succ is not None:
                self._csend(succ, t, part)   # forward c while pred
            parts.append(part)               # streams c+1 behind it
        flat = np.asarray(parts[0]).reshape(-1) if k == 1 \
            else np.concatenate([np.asarray(p).reshape(-1)
                                 for p in parts])
        return flat.reshape(shape).astype(np.dtype(dtstr), copy=False)

    @_serialized
    def gather(self, data: Any, root: int = 0) -> Optional[List[Any]]:
        """Linear gather (coll/basic): returns the rank-ordered list at
        root, None elsewhere."""
        self._check()
        self._validate_root(root)
        spc.record("coll_gather", 1)
        n, t = self.size, self._tag()
        if self._rank != root:
            self._csend(root, t, data)
            return None
        out: List[Any] = [None] * n
        out[root] = data
        for s in range(n):
            if s != root:
                out[s] = self._crecv(s, t)
        return out

    @_serialized
    def scatter(self, chunks: Optional[Sequence[Any]] = None,
                root: int = 0) -> Any:
        """Linear scatter: root passes one chunk per rank; every caller
        gets its chunk."""
        self._check()
        self._validate_root(root)
        spc.record("coll_scatter", 1)
        n, t = self.size, self._tag()
        if self._rank == root:
            if chunks is None or len(chunks) != n:
                self._err(ERR_COUNT, "root must pass one chunk per rank")
            for d in range(n):
                if d != root:
                    self._csend(d, t, chunks[d])
            return chunks[root]
        return self._crecv(root, t)

    @_serialized
    def allgather(self, data: Any, *, uniform: bool = False) -> List[Any]:
        """Ring allgather (coll_base_allgather ring): n-1 rounds, each
        forwarding the chunk received last round.

        ``uniform=True`` asserts every caller passes one (shape, dtype)
        — the C `MPI_Allgather` signature guarantee — unlocking the
        staged device tier for large host buffers (see ``alltoall``:
        the staging decision must be rank-symmetric, and the generic
        host path legally carries ragged objects)."""
        self._check()
        spc.record("coll_allgather", 1)
        if isinstance(data, _dev_array_type()) and self._mesh() is not None:
            return self._device_allgather(data)
        if uniform and self._stageable(data, func="allgather"):
            spc.record("coll_staged_device", 1)
            return [np.asarray(g) for g in self._device_allgather(
                np.ascontiguousarray(data))]
        n, r, t = self.size, self._rank, self._tag()
        out: List[Any] = [None] * n
        out[r] = data
        cur = data
        right, left = (r + 1) % n, (r - 1) % n
        for s in range(n - 1):
            req = self._coll_pml.irecv(left, t)
            self._csend(right, t, cur)
            req.wait()
            cur = req.get()
            out[(r - 1 - s) % n] = cur
        return out

    @_serialized
    def alltoall(self, chunks: Sequence[Any], *,
                 uniform: bool = False) -> List[Any]:
        """Pairwise-exchange alltoall (coll_base_alltoall pairwise).

        ``uniform=True`` asserts that every CALLER passes chunks of one
        (shape, dtype) — the property the C `MPI_Alltoall` signature
        (one sendcount/sendtype) guarantees globally. Only then may
        large host chunks take the staged device tier: the staging
        decision must be identical on every rank (the device dispatch
        is collective), and chunk uniformity checked locally cannot
        prove anything about other ranks' generic-object chunks."""
        self._check()
        spc.record("coll_alltoall", 1)
        n, r, t = self.size, self._rank, self._tag()
        if len(chunks) != n:
            self._err(ERR_COUNT, "alltoall needs one chunk per peer")
        if all(isinstance(c, _dev_array_type()) for c in chunks) \
                and self._mesh() is not None and n > 1:
            return self._device_alltoall(chunks)
        if (uniform and n > 1 and chunks
                and all(isinstance(c, np.ndarray) for c in chunks)
                and len({(c.shape, c.dtype.str) for c in chunks}) == 1
                and self._stageable(chunks[0], nbytes=chunks[0].nbytes * n,
                                    func="alltoall")):
            spc.record("coll_staged_device", 1)
            return [np.asarray(g) for g in self._device_alltoall(
                [np.ascontiguousarray(c) for c in chunks])]
        out: List[Any] = [None] * n
        out[r] = chunks[r]
        for s in range(1, n):
            dest, src = (r + s) % n, (r - s) % n
            req = self._coll_pml.irecv(src, t)
            self._csend(dest, t, chunks[dest])
            req.wait()
            out[src] = req.get()
        return out

    @_serialized
    def scan(self, data: Any, op: op_mod.Op = op_mod.SUM) -> Any:
        """Linear scan: inclusive prefix over ranks 0..r."""
        self._check()
        self._validate_op(op)
        spc.record("coll_scan", 1)
        n, r, t = self.size, self._rank, self._tag()
        acc = data
        if r > 0:
            acc = _apply(op, self._crecv(r - 1, t), data)
        if r + 1 < n:
            self._csend(r + 1, t, acc)
        return acc

    @_serialized
    def exscan(self, data: Any, op: op_mod.Op = op_mod.SUM) -> Any:
        """Exclusive prefix: rank 0 gets None."""
        self._check()
        self._validate_op(op)
        spc.record("coll_exscan", 1)
        n, r, t = self.size, self._rank, self._tag()
        prev = None if r == 0 else self._crecv(r - 1, t)
        if r + 1 < n:
            nxt = data if prev is None else _apply(op, prev, data)
            self._csend(r + 1, t, nxt)
        return prev

    def reduce_scatter_block(self, chunks: Sequence[Any],
                             op: op_mod.Op = op_mod.SUM) -> Any:
        """chunks[j] is this rank's contribution for rank j; returns the
        reduction of everyone's chunk for me."""
        self._check()
        self._validate_op(op)
        spc.record("coll_reduce_scatter_block", 1)
        if len(chunks) != self.size:
            self._err(ERR_COUNT, "need one chunk per rank")
        mine = self.alltoall(list(chunks))
        acc = mine[0]
        for x in mine[1:]:
            acc = _apply(op, acc, x)
        return acc

    # -- nonblocking collectives (async over a worker thread) ----------
    def _coll_worker_loop(self, q: "queue.Queue") -> None:
        # ONE worker per comm runs every deferred collective and any
        # funneled blocking body. It must never fire the coll
        # interposition hooks: blocking entries fire them on the
        # CALLER thread before funneling, i-slots are interposition-
        # exempt by contract (like the stacked coll/sync component),
        # and a fresh thread-local depth would let sync's op counter
        # race across threads and desynchronize injected barriers
        # between ranks.
        from ompi_tpu.coll.interpose_perrank import _tls as _itls
        _itls.sync_depth = 1
        _itls.mon_depth = 1
        while True:
            item = q.get()
            if item is None:
                q.task_done()
                return
            try:
                item()
            except BaseException:        # noqa: BLE001
                # runners report their own errors through their
                # completion boxes; anything escaping here (a broken
                # propagator, an OOM in the plumbing) must not kill
                # the worker — that would wedge every later collective
                # on this comm behind a queue nobody drains
                import traceback
                traceback.print_exc()
            finally:
                q.task_done()            # unfinished_tasks is the
                # _coll_serial busy signal: queued + in-flight jobs

    def _coll_submit(self, runner: Callable) -> None:
        with self._lock:
            if self._cclosed:
                raise MPIError(ERR_COMM,
                               "communicator has been freed")
            q = self._cq
            if q is None:
                q = self._cq = queue.Queue()
                self._cworker = threading.Thread(
                    target=self._coll_worker_loop, args=(q,),
                    daemon=True, name=f"coll-worker-{self.name}")
                self._cworker.start()
            # enqueue under the lock: a concurrent drain's sentinel
            # must not overtake this job
            q.put(runner)

    def _coll_serial(self, fn: Callable, *a, **kw):
        """Execute a collective body on the comm's single collective-
        execution context (see _serialized). Reentrant: a body already
        on the worker runs directly."""
        w = self._cworker
        if w is not None and threading.current_thread() is w:
            return fn(*a, **kw)
        box: Dict[str, Any] = {}
        ev: Optional[threading.Event] = None
        with self._lock:
            q = self._cq
            if q is not None and q.unfinished_tasks > 0:
                ev = threading.Event()
                # a funneled body must see the caller's interposition
                # depths (a collective entry arrives with its hook
                # already fired and depth incremented — nested calls
                # stay uncounted; a file/window op arrives at depth 0
                # — its nested collectives count as app ops), exactly
                # as an inline run would: a rank whose worker happens
                # to be idle runs inline, and hook counts must not
                # depend on that race or coll/sync's injected
                # barriers desync across ranks
                from ompi_tpu.coll.interpose_perrank import \
                    _tls as _itls
                sd = getattr(_itls, "sync_depth", 0)
                md = getattr(_itls, "mon_depth", 0)
                props = [cap() for cap in _TLS_PROPAGATORS]

                def runner():
                    _itls.sync_depth = sd
                    _itls.mon_depth = md
                    applied = []
                    # apply() runs INSIDE the try: a raising propagator
                    # must surface at the caller's wait like any body
                    # error — not escape the runner, leave ev unset,
                    # and hang the funneling caller forever
                    try:
                        for apply, reset in props:
                            apply()
                            applied.append(reset)
                        box["res"] = fn(*a, **kw)
                    except BaseException as e:  # noqa: BLE001
                        box["err"] = e
                    finally:
                        for reset in applied:
                            try:
                                reset()
                            except BaseException:  # noqa: BLE001
                                pass
                        _itls.sync_depth = 1    # the worker default:
                        _itls.mon_depth = 1     # i-jobs are exempt
                        ev.set()
                q.put(runner)
        if ev is None:                   # worker idle: inline
            return fn(*a, **kw)
        ev.wait()
        if "err" in box:
            raise box["err"]
        return box["res"]

    def _coll_drain(self) -> None:
        """Retire the comm's worker, draining pending jobs first
        (MPI-3.1 6.4.3: deallocation only after pending operations
        complete). _cclosed is set under the same lock hold as the
        sentinel, so no concurrent submit can spawn a SECOND worker
        while the old one still runs queued jobs (two executors would
        break the single-tag-draw-thread invariant); late submits get
        a clean freed-comm error instead."""
        with self._lock:
            q, t = self._cq, self._cworker
            self._cq = self._cworker = None
            self._cclosed = True
            if q is not None:
                q.put(None)              # queues behind pending jobs
        if t is not None:
            t.join()

    def _nb(self, fn: Callable, *args) -> Request:
        req = RankRequest(ANY_SOURCE, ANY_TAG)
        req._error: Optional[BaseException] = None
        orig_wait = req.wait

        def wait(timeout=None):
            st = orig_wait(timeout)
            if req._error is not None:           # surfaced at wait()
                raise req._error
            return st
        req.wait = wait

        def run():
            from ompi_tpu.pml.perrank import _Msg
            try:
                req._deliver(_Msg(self._rank, 0, fn(*args)))
            except BaseException as e:
                req._error = e
                req._complete = True
                req._event.set()
        self._coll_submit(run)
        return req

    # The i-variants run the CLASS-level implementations, bypassing any
    # interposition rebindings (coll/interpose_perrank): the stacked
    # coll/sync component excludes i-slots for the same reason — the
    # worker thread's fresh thread-local depth would race the sync op
    # counter across ranks and desynchronize injected barriers.
    def ibarrier(self) -> Request:
        return self._nb(RankCommunicator.barrier, self)

    def ibcast(self, data: Any = None, root: int = 0) -> Request:
        return self._nb(RankCommunicator.bcast, self, data, root)

    def iallreduce(self, data: Any, op: op_mod.Op = op_mod.SUM) -> Request:
        from ompi_tpu.coll import persistent as _pcoll
        if _pcoll.bucket_enabled():
            # DDP-style bucket fusion (docs/PERSISTENT.md): concurrent
            # small iallreduces on one (op, dtype) ride a single fused
            # wire collective; flush points are deterministic program
            # points so every rank fuses the identical bucket
            r = _pcoll.maybe_bucket_iallreduce(self, data, op)
            if r is not None:
                return r
        return self._nb(RankCommunicator.allreduce, self, data, op)

    def iallgather(self, data: Any) -> Request:
        return self._nb(RankCommunicator.allgather, self, data)

    def ireduce(self, data: Any, op: op_mod.Op = op_mod.SUM,
                root: int = 0) -> Request:
        return self._nb(RankCommunicator.reduce, self, data, op, root)

    # -- persistent collectives (MPI-4 *_init; coll/persistent) --------
    # The plan — route decision, fold combiner, multicast template,
    # staged-device executable, codec gates — binds once at init;
    # Start is launch-only and bucketable starts fuse (Startall).
    def allreduce_init(self, data: Any,
                       op: op_mod.Op = op_mod.SUM) -> Request:
        self._check()
        from ompi_tpu.coll import persistent as _pcoll
        return _pcoll.coll_init(self, "allreduce", data, op)

    def bcast_init(self, data: Any = None, root: int = 0) -> Request:
        self._check()
        from ompi_tpu.coll import persistent as _pcoll
        return _pcoll.coll_init(self, "bcast", data, root)

    def allgather_init(self, data: Any) -> Request:
        self._check()
        from ompi_tpu.coll import persistent as _pcoll
        return _pcoll.coll_init(self, "allgather", data)

    def reduce_scatter_block_init(self, chunks: Sequence[Any],
                                  op: op_mod.Op = op_mod.SUM) -> Request:
        self._check()
        from ompi_tpu.coll import persistent as _pcoll
        return _pcoll.coll_init(self, "reduce_scatter_block", chunks, op)

    def barrier_init(self) -> Request:
        self._check()
        from ompi_tpu.coll import persistent as _pcoll
        return _pcoll.coll_init(self, "barrier")

    # ==================================================================
    # Collectives — device tier (XLA over the global mesh)
    # ==================================================================
    def _mesh(self):
        """Mesh over one device per member rank (rank -> the first
        device of that rank's process). None when some member has no
        visible device (host tier handles it)."""
        if self._mesh_cache is not None:
            return self._mesh_cache or None
        import jax
        from jax.sharding import Mesh
        by_proc: Dict[int, Any] = {}
        for d in jax.devices():
            by_proc.setdefault(getattr(d, "process_index", 0), d)
        devs = []
        for w in self.group.world_ranks:
            d = by_proc.get(w)
            if d is None:
                self._mesh_cache = False
                return None
            devs.append(d)
        self._mesh_cache = Mesh(np.array(devs, dtype=object), (AXIS,))
        return self._mesh_cache

    def _global(self, x):
        """Assemble the (n, *local) global array from my local shard."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = self._mesh()
        sh = NamedSharding(mesh, P(AXIS))
        local = jax.device_put(x, mesh.devices[self._rank])
        return jax.make_array_from_single_device_arrays(
            (self.size,) + tuple(x.shape), sh,
            [local.reshape((1,) + tuple(x.shape))])

    def _local(self, garr):
        """My shard of a mesh-sharded result, squeezed."""
        shard = garr.addressable_shards[0].data
        return shard[0]

    def _dev_fn(self, key, builder):
        fn = self._dev_fns.get(key)
        if fn is None:
            fn = self._dev_fns[key] = builder()
        return fn

    def _device_allreduce(self, x, op: op_mod.Op):
        import jax
        from jax.sharding import PartitionSpec as P
        mesh = self._mesh()

        def build():
            def inner(s):
                if op.xla_prim == "sum":
                    return jax.lax.psum(s, AXIS)
                if op.xla_prim == "max":
                    return jax.lax.pmax(s, AXIS)
                if op.xla_prim == "min":
                    return jax.lax.pmin(s, AXIS)
                g = jax.lax.all_gather(s, AXIS, axis=0, tiled=True)
                return op.reduce_tree(g, axis=0)[None]
            return jax.jit(jax.shard_map(
                inner, mesh=mesh, in_specs=P(AXIS), out_specs=P(AXIS)))
        fn = self._dev_fn(("ar", op.uid), build)
        return self._local(fn(self._global(x)))

    def _device_bcast(self, x, root: int):
        import jax
        from jax.sharding import PartitionSpec as P
        mesh = self._mesh()

        def build():
            def inner(s):
                g = jax.lax.all_gather(s, AXIS, axis=0, tiled=True)
                return jax.lax.dynamic_slice_in_dim(g, root, 1, 0)
            return jax.jit(jax.shard_map(
                inner, mesh=mesh, in_specs=P(AXIS), out_specs=P(AXIS)))
        fn = self._dev_fn(("bc", root), build)
        return self._local(fn(self._global(x)))

    def _device_allgather(self, x) -> List[Any]:
        import jax
        from jax.sharding import PartitionSpec as P
        mesh = self._mesh()

        def build():
            def inner(s):
                return jax.lax.all_gather(s, AXIS, axis=0, tiled=True)[None]
            return jax.jit(jax.shard_map(
                inner, mesh=mesh, in_specs=P(AXIS), out_specs=P(AXIS)))
        fn = self._dev_fn(("ag",), build)
        g = self._local(fn(self._global(x)))           # (n, *local)
        return [g[i] for i in range(self.size)]

    def _device_alltoall(self, chunks: Sequence[Any]) -> List[Any]:
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        mesh = self._mesh()

        def build():
            def inner(s):                  # s: (1, n, *c)
                # split the peer axis, land chunk-from-rank-i at row i,
                # then restore the (1, n, *c) local block layout
                return jnp.moveaxis(
                    jax.lax.all_to_all(s, AXIS, split_axis=1,
                                       concat_axis=0), 0, 1)
            return jax.jit(jax.shard_map(
                inner, mesh=mesh, in_specs=P(AXIS), out_specs=P(AXIS)))
        fn = self._dev_fn(("a2a",), build)
        x = jnp.stack(list(chunks))                    # (n, *c)
        g = self._local(fn(self._global(x)))           # (n, *c) received
        return [g[i] for i in range(self.size)]

    # ==================================================================
    # Communicator algebra (collective; deterministic CIDs)
    # ==================================================================
    def split(self, color: int, key: int = 0
              ) -> Optional["RankCommunicator"]:
        """MPI_Comm_split (comm.c:749), textbook signature: each caller
        passes ITS color/key and receives its child (or None)."""
        self._check()
        seq = next(self._create_seq)
        rows = self.allgather((color, key))
        if color == UNDEFINED:
            return None
        members = sorted((r for r in range(self.size)
                          if rows[r][0] == color),
                         key=lambda r: (rows[r][1], r))
        g = Group([self.group.world_ranks[r] for r in members])
        return RankCommunicator(
            g, self._my_world, self.router,
            cid=("s", self.cid, seq, color),
            name=f"{self.name}.split({color})", parent=self,
            errhandler=self.errhandler)

    def split_type(self, split_type: int, key: int = 0):
        if split_type == UNDEFINED:
            return None
        if split_type == 2:                 # COMM_TYPE_HWTHREAD
            color = self._rank
        elif split_type in (1, 3):          # SHARED / NUMA: same host
            import socket
            names = self.allgather(socket.gethostname())
            color = names.index(names[self._rank])
        else:                               # match Communicator's
            self._err(ERR_ARG,              # validation, not a silent
                      f"unknown split_type {split_type}")  # SHARED
            return None
        return self.split(color, key)

    def dup(self, info: Optional[Info] = None) -> "RankCommunicator":
        self._check()
        seq = next(self._create_seq)
        self.barrier()                      # dup is collective
        c = RankCommunicator(
            Group(self.group.world_ranks), self._my_world, self.router,
            cid=("d", self.cid, seq), name=f"{self.name}.dup",
            parent=self, errhandler=self.errhandler,
            info=info or self.info)
        from ompi_tpu.core.communicator import propagate_attrs
        try:
            propagate_attrs(self, c)
        except BaseException:
            c.free()                     # no half-built comm leaks
            raise
        return c

    # -- process topologies (textbook cart surface) --------------------
    def create_cart(self, dims: Sequence[int],
                    periods: Optional[Sequence[bool]] = None,
                    reorder: bool = False
                    ) -> Optional["RankCommunicator"]:
        """MPI_Cart_create, textbook signature: callers beyond the cart
        size get None (MPI_COMM_NULL)."""
        import math
        from ompi_tpu.topo import CartTopology
        dims = list(dims)
        n = math.prod(dims)
        if n > self.size:
            self._err(ERR_ARG, f"cart size {n} exceeds comm size")
        sub = self.split(0 if self._rank < n else UNDEFINED)
        if sub is None:
            return None
        sub.topo = CartTopology(dims, list(periods) if periods
                                else [False] * len(dims))
        sub.name = f"{self.name}.cart"
        return sub

    def create_graph(self, index: Sequence[int], edges: Sequence[int],
                     reorder: bool = False
                     ) -> Optional["RankCommunicator"]:
        """MPI_Graph_create, textbook signature: callers beyond the
        graph size get None. ``reorder`` is accepted but placement is
        identity in the per-rank world — process binding is fixed at
        launch (the single-controller path runs the treematch
        permutation instead)."""
        from ompi_tpu.topo import GraphTopology
        topo = GraphTopology(index, edges)
        if topo.size > self.size:
            self._err(ERR_ARG, "graph larger than communicator")
        sub = self.split(0 if self._rank < topo.size else UNDEFINED)
        if sub is None:
            return None
        sub.topo = topo
        sub.name = f"{self.name}.graph"
        return sub

    def create_dist_graph_adjacent(self, sources: Sequence[int],
                                   destinations: Sequence[int]
                                   ) -> "RankCommunicator":
        """MPI_Dist_graph_create_adjacent, textbook signature: THIS
        rank's in/out neighbor lists; the full per-rank table is
        assembled collectively (the modex the reference does through
        its topo machinery)."""
        from ompi_tpu.topo import DistGraphTopology
        rows = self.allgather(([int(s) for s in sources],
                               [int(d) for d in destinations]))
        c = self.dup()
        c.topo = DistGraphTopology([r[0] for r in rows],
                                   [r[1] for r in rows])
        c.name = f"{self.name}.dist_graph"
        return c

    def _cart(self):
        from ompi_tpu.topo import CartTopology
        if not isinstance(self.topo, CartTopology):
            from ompi_tpu.core.errhandler import ERR_TOPOLOGY
            self._err(ERR_TOPOLOGY,
                      "communicator has no cartesian topology")
        return self.topo

    def cart_coords(self, rank: Optional[int] = None):
        return self._cart().coords(self._rank if rank is None else rank)

    def cart_rank(self, coords: Sequence[int]) -> int:
        return self._cart().rank(coords)

    def cart_shift(self, direction: int, disp: int = 1):
        """MPI_Cart_shift for THIS rank: (source, dest)."""
        return self._cart().shift(self._rank, direction, disp)

    @_serialized
    def neighbor_allgather(self, data: Any) -> List[Any]:
        """MPI_Neighbor_allgather, textbook: exchange ``data`` with each
        topology neighbor; returns received buffers in neighbor order
        (None at invalid slots — alignment is never shifted). Balanced
        eager sendrecv per slot: every edge endpoint sends once and
        receives once per slot pair, FIFO keeps duplicate edges
        ordered."""
        self._check()
        if self.topo is None:
            from ompi_tpu.core.errhandler import ERR_TOPOLOGY
            self._err(ERR_TOPOLOGY, "no topology attached")
        # post ALL receives, then send ALL, then wait — a sequential
        # per-slot wait deadlocks on periodic rings of size >= 3 (each
        # rank's slot-0 wait needs a frame its neighbor only sends
        # after ITS slot-0 wait: a cycle)
        # directed topologies (dist_graph): receive from IN-neighbors,
        # send to OUT-neighbors (MPI_Neighbor_* on a dist graph)
        nbrs = list(self.topo.neighbors(self._rank))
        outs = (list(self.topo.out_neighbors(self._rank))
                if hasattr(self.topo, "out_neighbors") else nbrs)
        t = self._tag()
        reqs = [self._coll_pml.irecv(nb, t)
                if 0 <= nb < self.size else None for nb in nbrs]
        for nb in outs:
            if 0 <= nb < self.size:
                self._coll_pml.send(data, nb, t)
        out: List[Any] = []
        for q in reqs:
            if q is None:
                out.append(None)
            else:
                q.wait()
                out.append(q.get())
        return out

    @_serialized
    def neighbor_alltoall(self, chunks: Sequence[Any]) -> List[Any]:
        """MPI_Neighbor_alltoall, textbook: chunk j goes to my j-th
        neighbor; returns one buffer per neighbor slot (None at invalid
        slots)."""
        self._check()
        if self.topo is None:
            from ompi_tpu.core.errhandler import ERR_TOPOLOGY
            self._err(ERR_TOPOLOGY, "no topology attached")
        nbrs = list(self.topo.neighbors(self._rank))
        outs = (list(self.topo.out_neighbors(self._rank))
                if hasattr(self.topo, "out_neighbors") else nbrs)
        if len(chunks) != len(outs):
            self._err(ERR_COUNT, "need one chunk per neighbor slot")
        t = self._tag()
        reqs: List[Optional[RankRequest]] = []
        for nb in nbrs:
            reqs.append(self._coll_pml.irecv(nb, t)
                        if 0 <= nb < self.size else None)
        for nb, c in zip(outs, chunks):
            if 0 <= nb < self.size:
                self._coll_pml.send(c, nb, t)
        out: List[Any] = []
        for q in reqs:
            if q is None:
                out.append(None)
            else:
                q.wait()
                out.append(q.get())
        return out

    def create(self, group: Group) -> Optional["RankCommunicator"]:
        self._check()
        seq = next(self._create_seq)
        self.barrier()
        if group.rank_of(self._my_world) == UNDEFINED:
            return None
        return RankCommunicator(
            group, self._my_world, self.router,
            cid=("g", self.cid, seq, tuple(group.world_ranks)),
            name=f"{self.name}.create", parent=self,
            errhandler=self.errhandler)

    # -- ULFM over real process death (mpiext/ftmpi semantics) ---------
    # The failure detector is the btl/tcp connection monitor (an
    # identified peer's EOF == PMIx failure event); these methods are
    # the MPIX_Comm_* recovery surface for the per-rank world.
    def get_failed(self) -> List[int]:
        """MPIX_Comm_get_failed: comm-local ranks known dead."""
        from ompi_tpu.runtime import ft
        return [r for r in range(self.size)
                if ft.is_failed(self.group.world_ranks[r])]

    def revoke(self) -> None:
        """MPIX_Comm_revoke: non-collective — ONE caller poisons the
        communicator everywhere. The router floods a reliable
        ``revoke`` ctl broadcast (every first receipt re-forwards, the
        revoked-set test terminates it — coll_base_revoke_local.c);
        locally and on every receiver the pending operations complete
        with ERR_REVOKED and new ones refuse in ``_check``. The
        recovery surface (shrink/agree/get_failed/free) keeps
        working."""
        self.router.revoke(self.cid)

    def is_revoked(self) -> bool:
        """MPIX_Comm_is_revoked (local, non-collective)."""
        return self.router.is_revoked(self.cid)

    def _on_revoked(self) -> None:
        """Router revoke callback: flush every pending operation —
        wildcards included (unlike a single peer death, a revoked comm
        can never match ANYTHING again, req_ft.c's revocation
        branch)."""
        def err():
            return MPIError(ERR_REVOKED,
                            f"{self.name} has been revoked")
        for eng in (self._pml, self._coll_pml,
                    *list(self._aux_pmls.values())):
            try:
                eng._flush_all(err)
            except Exception:            # noqa: BLE001
                pass

    def agree(self, flag: int = 1, timeout: float = 20) -> int:
        """MPIX_Comm_agree: fault-tolerant agreement — AND-folds the
        integer ``flag`` over the SURVIVING members and returns the
        agreed value on all of them, completing even with failed (or
        failing) participants. Runs on a revoked comm — it is the
        recovery path. The early-returning protocol lives in
        coll/ftagree (known-dead ranks are excluded up front, only a
        rank dying DURING the agreement costs a timeout)."""
        from ompi_tpu.coll import ftagree
        value, _failed = ftagree.perrank_agree(self, int(flag),
                                               timeout=timeout)
        return value

    def shrink(self, timeout: float = 20) -> "RankCommunicator":
        """MPIX_Comm_shrink: survivors agree on the failed set through
        coll/ftagree's early-returning agreement (a silent rank is
        itself suspected into the set — the ftagree suspicion rule)
        and build the survivor communicator through the NORMAL
        RankCommunicator construction, i.e. normal coll selection.
        Collective among survivors; works on a revoked comm. Retried
        when a survivor's stale failure view elected a dead leader
        (detection is asynchronous; the failed first exchange itself
        surfaces the death, and the retry settles)."""
        last: Optional[BaseException] = None
        for _ in range(3):
            try:
                return self._shrink_once(timeout)
            except (MPIError, OSError) as e:
                # OSError: a send raced the detector onto a just-dead
                # leader's broken socket (EPIPE beats the EOF callback)
                last = e
                import time
                time.sleep(0.2)          # let the detector settle
        raise last

    def _shrink_once(self, timeout: float) -> "RankCommunicator":
        # NO draw from _create_seq here: ranks may take different
        # numbers of retry attempts, and divergent draws would desync
        # every later dup/split cid. The child cid derives from the
        # AGREED failed set instead (same on every survivor, distinct
        # per failure epoch).
        from ompi_tpu.coll import ftagree
        _value, final = ftagree.perrank_agree(self, 1, timeout=timeout)
        survivors = [r for r in range(self.size) if r not in final]
        g = Group([self.group.world_ranks[r] for r in survivors])
        child = RankCommunicator(
            g, self._my_world, self.router,
            cid=("shrink", self.cid, tuple(final)),
            name=f"{self.name}.shrink", parent=self,
            errhandler=self.errhandler)
        # parent stays alive after a shrink, but its per-comm
        # instruments describe the dead-rank era — retire them so later
        # reads (trace_skew_c<cid>, tele_coll_*) can't report keys from
        # before the failure epoch
        from ompi_tpu import telemetry as _telemetry
        _telemetry.retire_comm(self.cid)
        return child

    def free(self) -> None:
        # delete callbacks fire FIRST (attribute.c free path): a
        # failing callback aborts the free with the comm fully intact
        # — worker alive, engines open — so the caller's "free did
        # not happen, comm stays valid" contract holds (MPI-3.1
        # 6.7.2)
        from ompi_tpu.core.communicator import fire_delete_attrs
        fire_delete_attrs(self)
        self.router.unregister_revoke_cb(self.cid)
        self._coll_drain()               # pending deferred collectives
        # complete against the live comm before teardown (MPI-3.1
        # 6.4.3)
        self._pml.close()
        self._coll_pml.close()
        for eng in self._aux_pmls.values():   # hidden channels too —
            eng.close()                       # a leaked registration
        self._aux_pmls.clear()                # would outlive the comm
        self._freed = True
        # pvar session semantics: per-comm instruments (telemetry
        # histograms, trace_skew_c<cid>) retire with the comm
        from ompi_tpu import telemetry as _telemetry
        _telemetry.retire_comm(self.cid)

    # -- attributes / naming -------------------------------------------
    def set_attr(self, keyval: int, value: Any) -> None:
        self.attributes[keyval] = value

    def get_attr(self, keyval: int) -> Tuple[bool, Any]:
        if keyval in self.attributes:
            return True, self.attributes[keyval]
        return False, None

    def delete_attr(self, keyval: int) -> None:
        from ompi_tpu.core import communicator as core_comm
        val = self.attributes.pop(keyval, None)
        cb = core_comm._keyvals.get(keyval)
        if cb and cb[1] and val is not None:
            cb[1](self, keyval, val)

    def set_errhandler(self, errh: Errhandler) -> None:
        self.errhandler = errh

    def get_errhandler(self) -> Errhandler:
        return self.errhandler

    def set_name(self, name: str) -> None:
        self.name = name

    def get_name(self) -> str:
        return self.name

    def abort(self, errorcode: int = 1):
        import os
        import sys
        sys.stderr.write(f"MPI_Abort on {self.name} "
                         f"errorcode={errorcode}\n")
        sys.stderr.flush()
        os._exit(errorcode)

    def __repr__(self):
        return (f"RankCommunicator({self.name}, rank={self._rank}/"
                f"{self.size}, cid={self.cid!r})")


def _apply(op: op_mod.Op, a: Any, b: Any) -> Any:
    """Apply a reduction combiner on the host tier: numpy in, numpy out.
    Predefined ops take the C++ SIMD kernel table (the op/avx role) or
    a dtype-preserving numpy ufunc — never the jnp combiner, which
    would silently downcast 64-bit numpy operands to 32-bit whenever
    jax runs without x64 (the per-rank default)."""
    if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        if op.predefined:
            an, bn = np.asarray(a), np.asarray(b)
            from ompi_tpu.native import native_reduce_local
            out = native_reduce_local(op.name, an, bn)
            if out is not None:
                return np.asarray(out)
            npfn = op_mod.NP_COMBINERS.get(op.name)
            if npfn is not None:
                return np.asarray(npfn(an, bn))
        return np.asarray(op.fn(a, b))
    if (op.predefined and not op.is_loc
            and isinstance(a, np.generic) and isinstance(b, np.generic)):
        # scalar fast path: the numpy kernel both preserves 64-bit
        # dtypes (the jnp combiner below silently downcasts without
        # x64) and skips a per-call JAX dispatch — this fold runs on
        # btl reader threads inside the sub-eager collective path
        npfn = op_mod.NP_COMBINERS.get(op.name)
        if npfn is not None:
            return npfn(a, b).item()
    try:
        import jax
        if isinstance(a, jax.Array):
            return op.fn(a, b)
    except Exception:
        pass
    r = op.fn(np.asarray(a), np.asarray(b))
    r = np.asarray(r)
    return r.item() if r.ndim == 0 else r


def _dev_array_type():
    import jax
    return jax.Array
