"""Communicators — rank groups bound to device-mesh subsets.

Behavioral spec: ``ompi/communicator`` — ``ompi_communicator_t`` holds a
group, a CID, and the ``c_coll`` vtable of selected collective modules;
``ompi_comm_split`` (``comm.c:749``), split_type, dup; CID allocation is a
distributed agreement (``comm_cid.c:61-109``).

TPU-native re-design (single-controller SPMD): an MPI rank is a coordinate
on a ``jax.sharding.Mesh``. A communicator of size N owns N devices and a
private 1-D mesh over them (axis ``"mpi_r"``); a rank's local buffer is
one shard of a stacked ``jax.Array`` of shape ``(N, *local)`` sharded on
axis 0. ``MPI_Comm_split`` therefore *is* mesh subsetting: the child
communicator's mesh is built from the parent devices of its members, so
collectives on sub-communicators ride the same ICI links with no
re-wiring. CID agreement collapses to a deterministic controller-side
counter (every rank observes the same allocation order by construction —
the property the reference's iterative allreduce establishes).

Collectives here are the *framework-level* entry points: argument/locus
validation, datatype pack/unpack around the wire format, errhandler
invocation, SPC counters — then dispatch through the per-communicator
``c_coll`` vtable populated by priority selection
(``coll_base_comm_select.c:234-273``).
"""
from __future__ import annotations

import functools
import itertools
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ompi_tpu.accelerator import LOCUS_DEVICE, LOCUS_HOST, check_addr, to_device
from ompi_tpu.core import convertor
from ompi_tpu.core import op as op_mod
from ompi_tpu.core.datatype import Datatype, from_numpy_dtype
from ompi_tpu.core.errhandler import (ERR_ARG, ERR_COMM, ERR_COUNT, ERR_OP,
                                      ERR_RANK, ERR_ROOT, ERR_TYPE,
                                      ERRORS_ARE_FATAL, Errhandler, MPIError)
from ompi_tpu.core.group import Group, UNDEFINED
from ompi_tpu.core.info import Info
from ompi_tpu.core.request import Request, Status
from ompi_tpu.runtime import ft, spc
from ompi_tpu.trace import core as _trace
from ompi_tpu.utils import hooks

AXIS = "mpi_r"          # the private mesh axis name every communicator uses

# Sentinel mirroring MPI_IN_PLACE: "sendbuf is recvbuf".
class _InPlaceType:
    def __repr__(self):
        return "MPI_IN_PLACE"


IN_PLACE = _InPlaceType()

_cid_lock = threading.Lock()
_cid_counter = itertools.count(0)


def _next_cid() -> int:
    """CID agreement (comm_cid.c:61-109). Single-controller: allocation
    order is globally observed by construction, so the iterative
    allreduce over available CIDs reduces to a monotone counter."""
    with _cid_lock:
        return next(_cid_counter)


def _layer_span(name: str):
    """The communicator layer's span ``name`` (ring and profiler sink)
    around a collective method, entry to return on every path; one
    check when neither is on."""
    def wrap(method):
        @functools.wraps(method)
        def spanned(*args, **kwargs):
            if not (_trace.active or _trace.recording()):
                return method(*args, **kwargs)
            tok = _trace.begin(name)
            try:
                return method(*args, **kwargs)
            finally:
                _trace.end(tok)
        return spanned
    return wrap


class Communicator:
    def __init__(self, group: Group, devices: Sequence[Any], *,
                 name: str = "", parent: Optional["Communicator"] = None,
                 info: Optional[Info] = None,
                 errhandler: Optional[Errhandler] = None):
        if len(devices) != group.size:
            raise MPIError(ERR_ARG, "devices must match group size")
        self.group = group
        self.devices = tuple(devices)
        self.cid = self._alloc_cid()
        self.name = name or f"comm#{self.cid}"
        self.info = info.dup() if info else Info()
        self.errhandler = errhandler or parent_errh(parent)
        self.attributes: Dict[int, Any] = {}
        self.topo = None               # set by topo layer (cart/graph)
        self._freed = False
        self._multiproc: Optional[bool] = None
        self._revoked = False          # ULFM
        self._acked_failures: frozenset = frozenset()  # ULFM failure_ack
        # Failure-knowledge domain: the process-wide default registry,
        # or (MPI-4 Sessions) the owning session's private registry —
        # inherited through parent so sub-communicators stay in their
        # instance's domain (instance.c per-instance state).
        self._ft = parent._ft if parent is not None else (
            ft.default_registry())
        # The communicator's data plane: a private 1-D mesh over its
        # devices. Stacked rank buffers shard along this axis.
        self.mesh = Mesh(np.array(self.devices, dtype=object), (AXIS,))
        self.sharding = NamedSharding(self.mesh, P(AXIS))
        self.c_coll: Dict[str, Any] = {}
        # sub-eager dispatch cache: per-(shape, dtype, op) resolution
        # of the hottest allreduce call shape straight to the selected
        # module's entry point — validation and wire-form decisions are
        # pure functions of the key and run once (the small-message
        # control-plane overhaul's single-controller leg)
        self._subeager: Dict[tuple, Any] = {}
        self._select_coll()

    def _alloc_cid(self) -> int:
        """CID allocation hook: the process-wide space by default;
        MPI-4 Sessions override to draw from the instance's own space
        (comm_cid.c allocates within the instance namespace)."""
        return _next_cid()

    # ------------------------------------------------------------------
    @property
    def size(self) -> int:
        return self.group.size

    def rank(self) -> int:
        """Single-controller: the controller drives all ranks; per-rank
        identity lives in the stacked axis. Returns 0 for API parity."""
        return 0

    def _select_coll(self) -> None:
        from ompi_tpu.coll.framework import comm_select_coll
        self.c_coll = comm_select_coll(self)
        from ompi_tpu.tools import comm_method
        comm_method.maybe_display(self)

    def _err(self, error_class: int, msg: str = ""):
        return self.errhandler.invoke(self, error_class, msg)

    def _check(self) -> None:
        if self._freed:
            raise MPIError(ERR_COMM, "communicator has been freed")
        if self._revoked:
            from ompi_tpu.core.errhandler import ERR_REVOKED
            raise MPIError(ERR_REVOKED, "communicator has been revoked")

    # -- buffer helpers -------------------------------------------------
    @property
    def is_multiprocess(self) -> bool:
        """True when any of this communicator's devices is not
        addressable from THIS controller (multi-controller SPMD: every
        controller runs the same program; each addresses only its local
        shards). Governs buffer placement/readback strategy."""
        if self._multiproc is None:
            pi = jax.process_index()
            self._multiproc = any(
                getattr(d, "process_index", 0) != pi for d in self.devices)
        return self._multiproc

    @property
    def spans_processes(self) -> bool:
        """True when the devices live on more than one controller
        process — the topology fact (distinct from addressability)
        that gates the hier/DCN two-tier algorithm path."""
        return len({getattr(d, "process_index", 0)
                    for d in self.devices}) > 1

    def put(self, host_array) -> Any:
        """Place a host array onto this communicator's mesh (stacked
        wire layout). Multi-controller: ``device_put`` cannot target
        non-addressable devices, so build the global array from each
        controller's local shards (the jax.make_array_from_callback
        path — every controller computes the same host value, the
        modex-like property PMIx establishes in the reference,
        ``instance.c:547-569``)."""
        arr = np.asarray(host_array)
        if not self.is_multiprocess:
            return jax.device_put(arr, self.sharding)
        return jax.make_array_from_callback(
            arr.shape, self.sharding, lambda idx: arr[idx])

    def alloc(self, local_shape: Tuple[int, ...], dtype=np.float32,
              fill: Optional[float] = None):
        """Allocate a stacked device buffer (size, *local_shape) sharded
        one-shard-per-rank over this communicator's mesh."""
        shape = (self.size,) + tuple(local_shape)
        if self.is_multiprocess:
            fill_v = 0.0 if fill is None else fill

            def _shard(idx):
                sshape = tuple(len(range(*sl.indices(dim)))
                               for sl, dim in zip(idx, shape))
                return np.full(sshape, fill_v, dtype=dtype)
            return jax.make_array_from_callback(shape, self.sharding,
                                                _shard)
        if fill is None:
            arr = jax.numpy.zeros(shape, dtype=dtype)
        else:
            arr = jax.numpy.full(shape, fill, dtype=dtype)
        return jax.device_put(arr, self.sharding)

    def stack(self, per_rank: Sequence[Any]):
        """Build a stacked device buffer from per-rank host/device arrays."""
        if len(per_rank) != self.size:
            self._err(ERR_COUNT, "need one array per rank")
        arr = np.stack([np.asarray(a) for a in per_rank])
        return self.put(arr)

    def shard(self, stacked, rank: int):
        """Rank ``rank``'s view of a stacked buffer (host copy). In a
        multi-controller world only locally-addressable ranks can be
        read; reading a remote rank raises (fetch it with a collective
        instead — gather/allgather — exactly as real MPI requires)."""
        if isinstance(stacked, jax.Array) and self.is_multiprocess:
            for s in stacked.addressable_shards:
                idx0 = s.index[0] if s.index else slice(None)
                if idx0.start is not None and idx0.start == rank:
                    return np.asarray(s.data)[0]
                if idx0.start is None:
                    # fully-replicated shard (index slice(None)): every
                    # rank's row is locally readable
                    return np.asarray(s.data)[rank]
            self._err(ERR_RANK,
                      f"rank {rank}'s shard is not addressable from "
                      f"process {jax.process_index()}")
        return np.asarray(stacked[rank])

    # -- validation + dispatch -----------------------------------------
    def _coll(self, func: str):
        self._check()
        self._check_ft_coll()
        m = self.c_coll.get(func)
        if m is None:
            self._err(ERR_ARG, f"no coll component provides {func} "
                               f"for {self.name}")
        spc.record(f"coll_{func}", 1)
        hooks.fire(f"coll_{func}", self, {})
        return m

    def _validate_op(self, op, pair_expected: bool = False):
        if not isinstance(op, op_mod.Op) or op.fn is None:
            self._err(ERR_OP, "invalid reduction op")
        return op

    def _validate_root(self, root: int):
        if not (0 <= root < self.size):
            self._err(ERR_ROOT, f"root {root} out of range")
        return root

    def _validate_stacked(self, buf, lead: int = 1):
        if check_addr(buf) is None:
            self._err(ERR_ARG, "buffer must be a jax or numpy array")
        if buf.ndim < lead or buf.shape[0] != self.size:
            self._err(ERR_COUNT,
                      f"stacked buffer must have leading axis {self.size}, "
                      f"got {getattr(buf, 'shape', None)}")
        return buf

    def _wire(self, buf, datatype: Optional[Datatype], count: Optional[int]):
        """Pack a stacked buffer to wire (contiguous) form; return
        (packed, unpack_fn)."""
        if datatype is None or datatype.is_contiguous:
            return buf, (lambda y, out=None: y)
        if count is None:
            count = buf.shape[-1] // max(datatype.extent, 1)
        packed = convertor.pack(buf, datatype, count)

        def unpack_fn(y, out=None):
            if out is None:
                if check_addr(y) == LOCUS_DEVICE:
                    out = jax.numpy.zeros(y.shape[:-1]
                                          + (count * datatype.extent,),
                                          dtype=y.dtype)
                else:
                    out = np.zeros(y.shape[:-1]
                                   + (count * datatype.extent,), dtype=y.dtype)
            return convertor.unpack(out, y, datatype, count)
        return packed, unpack_fn

    # ==================================================================
    # Collectives (blocking). Stacked-array functional API:
    # input leading axis = rank, result returned (device path is purely
    # functional; MPI_IN_PLACE is expressed by passing recvbuf as input).
    # ==================================================================
    @_layer_span("comm.allreduce")
    def allreduce(self, sendbuf, op=op_mod.SUM, *,
                  datatype: Optional[Datatype] = None,
                  count: Optional[int] = None, recvbuf=None):
        in_place = sendbuf is IN_PLACE
        if in_place:
            sendbuf = recvbuf   # MPI_IN_PLACE (allreduce.c.in:54,78-79)
        # sub-eager fast path: contiguous device buffer, no recvbuf —
        # shape/dtype/op were validated when the key was filled
        # (validity is a pure function of the key), so a repeat call
        # is one dict probe plus the selected module's own memo. The
        # freed-op and ft checks stay per-call; the module re-checks
        # the var epoch itself.
        if (datatype is None and recvbuf is None
                and getattr(op, "fn", None) is not None
                and check_addr(sendbuf) == LOCUS_DEVICE):
            key = (sendbuf.shape, sendbuf.dtype.name, op.uid)
            fn = self._subeager.get(key)
            if fn is None:
                self._validate_stacked(sendbuf)
                self._validate_op(op)
                fn = self._subeager[key] = getattr(
                    self._coll("allreduce"), "allreduce")
                return fn(sendbuf, op)
            self._check()
            self._check_ft_coll()
            spc.record("coll_allreduce", 1)
            hooks.fire("coll_allreduce", self, {})
            return fn(sendbuf, op)
        self._validate_stacked(sendbuf)
        self._validate_op(op)
        # Fused derived-datatype fast path: one
        # compiled gather->collective->scatter program instead of the
        # pack/collective/unpack dispatch chain. Device buffers only
        # (host buffers keep the convertor path); a DISTINCT recvbuf's
        # gaps cannot come from sendbuf, so that case keeps the
        # overlay path too.
        if (datatype is not None and not datatype.is_contiguous
                and not datatype.pair and op.fn is not None
                and not getattr(op, "is_loc", False)
                and (recvbuf is None or in_place)
                and check_addr(sendbuf) == LOCUS_DEVICE):
            mod = self._coll("allreduce")
            fd = getattr(mod, "allreduce_dtype", None)
            cnt = (count if count is not None else
                   sendbuf.shape[-1] // max(datatype.extent, 1))
            # shape contract: the fused program returns sendbuf's own
            # shape, so it may only serve exact-fit buffers (last dim
            # == count*extent) — otherwise the convertor path's
            # truncated image is the documented result
            if (fd is not None
                    and sendbuf.shape[-1] == cnt * datatype.extent):
                return fd(sendbuf, op, datatype, cnt, in_place)
        x, unpack_fn = self._wire(sendbuf, datatype, count)
        y = self._coll("allreduce").allreduce(x, op)
        # Unpack into recvbuf (even for IN_PLACE, where recvbuf is the
        # send buffer): MPI guarantees gap elements outside the
        # datatype's map are left untouched.
        return unpack_fn(y, recvbuf)

    def reduce(self, sendbuf, op=op_mod.SUM, root: int = 0, *,
               datatype: Optional[Datatype] = None,
               count: Optional[int] = None, recvbuf=None):
        if sendbuf is IN_PLACE:
            sendbuf = recvbuf
        self._validate_stacked(sendbuf)
        self._validate_op(op)
        self._validate_root(root)
        x, unpack_fn = self._wire(sendbuf, datatype, count)
        y = self._coll("reduce").reduce(x, op, root)
        return unpack_fn(y, recvbuf)

    @_layer_span("comm.bcast")
    def bcast(self, buf, root: int = 0, *,
              datatype: Optional[Datatype] = None,
              count: Optional[int] = None):
        self._validate_stacked(buf)
        self._validate_root(root)
        x, unpack_fn = self._wire(buf, datatype, count)
        y = self._coll("bcast").bcast(x, root)
        return unpack_fn(y)

    @_layer_span("comm.allgather")
    def allgather(self, sendbuf, *, datatype: Optional[Datatype] = None,
                  count: Optional[int] = None):
        """in (N, *s) -> out (N, N, *s): out[r, j] = rank j's sendbuf."""
        self._validate_stacked(sendbuf)
        x, _ = self._wire(sendbuf, datatype, count)
        return self._coll("allgather").allgather(x)

    def gather(self, sendbuf, root: int = 0, *,
               datatype: Optional[Datatype] = None,
               count: Optional[int] = None):
        """in (N, *s) -> out (N, N, *s), rows valid at root only."""
        self._validate_stacked(sendbuf)
        self._validate_root(root)
        x, _ = self._wire(sendbuf, datatype, count)
        return self._coll("gather").gather(x, root)

    def scatter(self, sendbuf, root: int = 0, *,
                datatype: Optional[Datatype] = None,
                count: Optional[int] = None):
        """in (N, N, *s) (root's row of chunks) -> out (N, *s)."""
        self._validate_stacked(sendbuf, lead=2)
        self._validate_root(root)
        x, _ = self._wire(sendbuf, datatype, count)
        return self._coll("scatter").scatter(x, root)

    def gather_root(self, sendbuf, root: int = 0):
        """Memory-optimal root-targeted gather (framework extension,
        the stacked API's analogue of MPI's root-only recvbuf): returns
        rank root's recvbuf, an (N, *local) array resident ONLY on
        root's device. Non-root devices allocate nothing — vs the
        in-graph gather, whose uniform SPMD output holds N rows on
        every device (the round-1 n-times-memory cost).
        The collect is a runtime D2D transfer over ICI: PJRT moves each
        shard straight to root (the binomial-gather role,
        coll_base_functions.h:185-320, with the tree supplied by the
        interconnect). Multi-controller worlds fall back to the
        in-graph gather and return its stacked result."""
        self._validate_stacked(sendbuf)
        self._validate_root(root)
        if self.is_multiprocess:
            return self.gather(sendbuf, root)   # does its own checks/SPC
        self._coll("gather")             # state checks + SPC/hooks
        sd = jax.sharding.SingleDeviceSharding(self.devices[root])
        return jax.device_put(sendbuf, sd)

    def scatter_root(self, chunks, root: int = 0):
        """Root-targeted scatter companion of :meth:`gather_root`:
        ``chunks`` is root's (N, *local) send buffer (host array or
        root-resident device array); returns the standard stacked
        (N, *local) buffer, one shard per rank. The fan-out is a
        runtime placement (device_put / comm.put) over ICI.

        Multi-controller: SPMD single-program semantics require every
        controller to pass the same host value (the controller-
        replicated convention every stacked builder uses — comm.put's
        modex property); device arrays are rejected there because a
        root-resident array is unreadable from the other controllers.
        """
        self._validate_root(root)
        if check_addr(chunks) is None:
            self._err(ERR_ARG, "chunks must be a jax or numpy array")
        if chunks.ndim < 1 or chunks.shape[0] != self.size:
            self._err(ERR_COUNT,
                      f"chunks must have leading axis {self.size}")
        self._coll("scatter")            # state checks + SPC/hooks
        if self.is_multiprocess:
            if isinstance(chunks, jax.Array):
                self._err(ERR_ARG,
                          "multi-controller scatter_root needs a host "
                          "array replicated on every controller (a "
                          "root-resident device array cannot be read "
                          "from the other controllers); use scatter() "
                          "with the stacked sendbuf instead")
            return self.put(np.asarray(chunks))
        return jax.device_put(chunks, self.sharding)

    @_layer_span("comm.alltoall")
    def alltoall(self, sendbuf, *, datatype: Optional[Datatype] = None,
                 count: Optional[int] = None):
        """in (N, N, *s) -> out (N, N, *s): out[j, i] = in[i, j]."""
        self._validate_stacked(sendbuf, lead=2)
        if sendbuf.shape[1] != self.size:
            self._err(ERR_COUNT, "alltoall needs one chunk per peer")
        x, _ = self._wire(sendbuf, datatype, count)
        return self._coll("alltoall").alltoall(x)

    @_layer_span("comm.reduce_scatter_block")
    def reduce_scatter_block(self, sendbuf, op=op_mod.SUM, *,
                             datatype: Optional[Datatype] = None,
                             count: Optional[int] = None):
        """in (N, N, *s) -> out (N, *s): out[r] = reduce_i in[i, r]."""
        self._validate_stacked(sendbuf, lead=2)
        self._validate_op(op)
        x, _ = self._wire(sendbuf, datatype, count)
        return self._coll("reduce_scatter_block").reduce_scatter_block(x, op)

    def reduce_scatter(self, sendbuf, recvcounts: Sequence[int],
                       op=op_mod.SUM):
        """MPI_Reduce_scatter with per-rank counts. in (N, total) where
        total = sum(recvcounts); returns a list of per-rank DEVICE
        arrays (the variable-length result cannot be one stacked
        array).

        Round-2 lowering: segments are padded to the
        max count with ONE device gather (a static index map built from
        the counts), then ride ``reduce_scatter_block`` — psum_scatter
        on the device path — so the wire moves ~N*max(counts) elements
        instead of the round-1 full allreduce's total-everywhere, and
        nothing round-trips through the host."""
        self._validate_stacked(sendbuf)
        self._validate_op(op)
        self._require_local_views("reduce_scatter")
        if len(recvcounts) != self.size:
            self._err(ERR_COUNT, "recvcounts must have comm-size entries")
        total = int(sum(recvcounts))
        if sendbuf.shape[-1] != total:
            self._err(ERR_COUNT, f"sendbuf last axis must be {total}")
        n = self.size
        m = max(recvcounts) if recvcounts else 0
        if m == 0:
            return [sendbuf[r, ..., 0:0] for r in range(n)]
        # Static (n, m) index map: segment j's element k sits at
        # offset_j + k; entries past counts[j] are masked to zero.
        offs = np.concatenate([[0], np.cumsum(recvcounts)[:-1]])
        idx = np.minimum(offs[:, None] + np.arange(m)[None, :],
                         total - 1).astype(np.int32)
        mask = (np.arange(m)[None, :] <
                np.asarray(recvcounts)[:, None])
        if check_addr(sendbuf) == LOCUS_DEVICE:
            xs = jax.numpy.take(sendbuf, jax.numpy.asarray(idx.ravel()),
                                axis=-1)
            xs = xs.reshape(sendbuf.shape[:-1] + (n, m))
            xs = jax.numpy.where(jax.numpy.asarray(mask), xs, 0)
            # wire layout (N, N, m): chunk axis before payload axes
            xs = jax.numpy.moveaxis(xs, -2, 1)
        else:
            xs = np.take(np.asarray(sendbuf), idx.ravel(), axis=-1)
            xs = xs.reshape(sendbuf.shape[:-1] + (n, m))
            xs = np.where(mask, xs, 0)
            xs = np.moveaxis(xs, -2, 1)
        red = self.reduce_scatter_block(xs, op)        # (N, ..., m)
        return [red[r, ..., :recvcounts[r]] for r in range(n)]

    def scan(self, sendbuf, op=op_mod.SUM):
        self._validate_stacked(sendbuf)
        self._validate_op(op)
        return self._coll("scan").scan(sendbuf, op)

    def exscan(self, sendbuf, op=op_mod.SUM):
        self._validate_stacked(sendbuf)
        self._validate_op(op)
        return self._coll("exscan").exscan(sendbuf, op)

    def barrier(self) -> None:
        self._coll("barrier").barrier()

    # -- v-variants (variable counts): pad to max, run fixed, slice ----
    # The wire strategy for every *v collective is the same: pad ragged
    # per-peer chunks to the max count, ride the fixed-count device
    # collective over ICI, slice the valid prefixes off on the way out —
    # the TPU analogue of the reference's per-peer count headers
    # (ompi/mca/coll/base alltoallv/allgatherv pairwise exchanges).
    # Round 2: device inputs are padded ON DEVICE and
    # results come back as device arrays (lazy slices of the collective
    # output) — the round-1 implementation round-tripped everything
    # through NumPy, the opposite of the framework's thesis.
    def _ragged(self, per_rank: Sequence[Any], what: str):
        self._require_local_views(what)
        if len(per_rank) != self.size:
            self._err(ERR_COUNT, f"{what} needs one entry per rank")
        if all(check_addr(a) == LOCUS_DEVICE for a in per_rank):
            arrs = [jax.numpy.ravel(a) for a in per_rank]
        else:
            arrs = [np.asarray(a).ravel() for a in per_rank]
        return arrs, [a.size for a in arrs]

    def _require_local_views(self, what: str) -> None:
        """The v-/neighbor-collectives return per-rank slices of the
        stacked result; on a multi-controller communicator the result is
        a non-fully-addressable global array those slices cannot read.
        Raise the same clean guard the coll path uses (_to_mesh) instead
        of jax's opaque non-addressable error."""
        if self.is_multiprocess:
            from ompi_tpu.core.errhandler import ERR_INTERN
            self._err(ERR_INTERN,
                      f"{what} returns per-rank views of the stacked "
                      f"result, which a multi-controller world cannot "
                      f"address; use fixed-count collectives, or the "
                      f"per-rank execution model (mpirun --per-rank)")

    def _pad_stack(self, arrs, counts, m):
        """(N, m) padded stack; device-side when the inputs are device
        arrays. Single-controller only — every v-collective entry point
        guards with _require_local_views first (the output side slices
        per-rank views a multi-controller world cannot read)."""
        if arrs and isinstance(arrs[0], jax.Array):
            segs = [jax.numpy.pad(a, (0, m - a.size)) for a in arrs]
            stacked = jax.numpy.stack(segs)
            if self.is_multiprocess:
                return self.put(np.asarray(stacked))   # local -> global
            return jax.device_put(stacked, self.sharding)
        padded = np.zeros((self.size, m), dtype=arrs[0].dtype)
        for i, a in enumerate(arrs):
            padded[i, :a.size] = a
        return self.put(padded)

    def allgatherv(self, per_rank: Sequence[Any]):
        """Takes per-rank arrays (ragged); returns a per-rank list of
        DEVICE arrays = the concatenation every rank receives. Pads to
        max count on the wire (the TPU analogue of the reference's
        per-peer count headers)."""
        arrs, counts = self._ragged(per_rank, "allgatherv")
        m = max(counts) if counts else 0
        if m == 0:
            return [a for a in arrs]
        g = self.allgather(self._pad_stack(arrs, counts, m))
        # per-rank device concat of the valid prefixes (lazy slices —
        # no host transfer)
        return [jax.numpy.concatenate(
                    [g[r, j, :counts[j]] for j in range(self.size)])
                for r in range(self.size)]

    def gatherv(self, per_rank: Sequence[Any], root: int = 0):
        """MPI_Gatherv: ragged per-rank contributions; returns the
        concatenation (a device array, valid at root)."""
        self._validate_root(root)
        arrs, counts = self._ragged(per_rank, "gatherv")
        m = max(counts) if counts else 0
        if m == 0:
            return arrs[0]
        g = self.gather(self._pad_stack(arrs, counts, m), root)
        return jax.numpy.concatenate(
            [g[root, j, :counts[j]] for j in range(self.size)])

    def scatterv(self, chunks: Sequence[Any], root: int = 0):
        """MPI_Scatterv: ``chunks`` is root's ragged per-destination list;
        returns a per-rank list of DEVICE arrays."""
        self._validate_root(root)
        arrs, counts = self._ragged(chunks, "scatterv")
        m = max(counts) if counts else 0
        if m == 0:
            return [a for a in arrs]
        row = self._pad_stack(arrs, counts, m)         # (N, m)
        if isinstance(row, jax.Array) and not self.is_multiprocess:
            # root-targeted runtime fan-out: no (N, N, m) stack needed
            s = self.scatter_root(row, root)
        else:
            padded = np.zeros((self.size, self.size, m),
                              dtype=np.asarray(row).dtype)
            padded[root] = np.asarray(row)
            s = self.scatter(self.put(padded), root)
        return [s[r, :counts[r]] for r in range(self.size)]

    def alltoallv(self, send_chunks: Sequence[Sequence[Any]]):
        """MPI_Alltoallv: ``send_chunks[i][j]`` is rank i's (ragged)
        chunk for rank j; returns ``recv`` with ``recv[j][i]`` = the
        chunk i sent to j (per-rank lists of DEVICE arrays)."""
        self._require_local_views("alltoallv")
        if len(send_chunks) != self.size:
            self._err(ERR_COUNT, "alltoallv needs one row per rank")
        device_in = all(check_addr(c) == LOCUS_DEVICE
                        for row in send_chunks for c in row)
        if device_in:
            rows = [[jax.numpy.ravel(c) for c in row]
                    for row in send_chunks]
        else:
            rows = [[np.asarray(c).ravel() for c in row]
                    for row in send_chunks]
        for row in rows:
            if len(row) != self.size:
                self._err(ERR_COUNT, "alltoallv needs one chunk per peer")
        counts = [[c.size for c in row] for row in rows]
        m = max((c for row in counts for c in row), default=0)
        if m == 0:
            return [[rows[i][j] for i in range(self.size)]
                    for j in range(self.size)]
        if device_in:
            padded = jax.numpy.stack(
                [jax.numpy.stack([jax.numpy.pad(c, (0, m - c.size))
                                  for c in row]) for row in rows])
            padded = (self.put(np.asarray(padded)) if self.is_multiprocess
                      else jax.device_put(padded, self.sharding))
        else:
            dt = rows[0][0].dtype
            host = np.zeros((self.size, self.size, m), dtype=dt)
            for i, row in enumerate(rows):
                for j, c in enumerate(row):
                    host[i, j, :c.size] = c
            padded = self.put(host)
        t = self.alltoall(padded)
        # out[j, i] = in[i, j]; slice each to the sender's count — lazy
        # device slices, no host round-trip.
        return [[t[j, i, :counts[i][j]] for i in range(self.size)]
                for j in range(self.size)]

    def alltoallw(self, send_chunks: Sequence[Sequence[Any]],
                  send_types: Sequence[Sequence[Optional[Datatype]]],
                  send_counts: Optional[Sequence[Sequence[int]]] = None):
        """MPI_Alltoallw: per-(src,dst) datatypes. Each chunk is packed
        with its own datatype before the exchange (host pack — the w
        variant's per-pair layouts preclude one device index map), then
        rides the padded alltoall. ``send_counts[i][j]`` is the instance
        count (MPI's explicit count argument); when omitted, the maximal
        count that fits the chunk is used — MPI buffer-length rule: the
        last instance needs only the type's true extent."""
        packed = []
        for i, (row, trow) in enumerate(zip(send_chunks, send_types)):
            prow = []
            for j, (c, t) in enumerate(zip(row, trow)):
                a = np.asarray(c)
                if t is not None and not t.is_contiguous:
                    extent = max(t.extent, 1)
                    lo, rng = t.get_true_extent()
                    if send_counts is not None:
                        cnt = send_counts[i][j]
                    elif a.shape[-1] < lo + rng:
                        cnt = 0
                    else:
                        cnt = 1 + (a.shape[-1] - lo - rng) // extent
                    if a.shape[-1] < ((cnt - 1) * extent + lo + rng
                                      if cnt else 0):
                        self._err(ERR_COUNT,
                                  f"alltoallw chunk length {a.shape[-1]} "
                                  f"cannot hold {cnt} instances "
                                  f"(extent {extent}, true extent "
                                  f"{lo + rng})")
                    a = (np.asarray(convertor.pack(a, t, cnt)) if cnt
                         else np.empty((0,), a.dtype))
                prow.append(a.ravel())
            packed.append(prow)
        return self.alltoallv(packed)

    # ==================================================================
    # Nonblocking variants: JAX async dispatch makes these natural — the
    # compiled collective is enqueued and a Request wraps the output.
    # ==================================================================
    def _nb(self, fn: Callable, *args, **kw) -> Request:
        out = fn(*args, **kw)
        arrays = [a for a in jax.tree_util.tree_leaves(out)
                  if isinstance(a, jax.Array)]
        return Request(result=out, arrays=arrays or None)

    def _isched(self, func: str):
        """The i-collective's vtable slot when a schedule component
        (coll/nbc) won it; None routes through async dispatch (_nb).
        Contiguous-buffer calls only — datatype/count kwargs take the
        blocking path, whose convertor handles packing. Runs the same
        entry checks/counters as _coll so state errors, FT, SPC and
        hooks behave identically on both paths."""
        return self._coll(func) if func in self.c_coll else None

    def iallreduce(self, sendbuf, op=op_mod.SUM, **kw) -> Request:
        if not kw:
            from ompi_tpu.coll import persistent as _pcoll
            if _pcoll.bucket_enabled():
                # DDP-style bucket fusion: concurrent small
                # iallreduces on the same (op, dtype) coalesce into
                # one flattened wire collective (docs/PERSISTENT.md)
                self._validate_stacked(sendbuf)
                self._validate_op(op)
                r = _pcoll.maybe_bucket_iallreduce(self, sendbuf, op)
                if r is not None:
                    return r
            m = self._isched("iallreduce")
            if m is not None:
                self._validate_stacked(sendbuf)
                self._validate_op(op)
                return m.iallreduce(sendbuf, op)
        return self._nb(self.allreduce, sendbuf, op, **kw)

    def ibcast(self, buf, root: int = 0, **kw) -> Request:
        if not kw:
            m = self._isched("ibcast")
            if m is not None:
                self._validate_stacked(buf)
                self._validate_root(root)
                return m.ibcast(buf, root)
        return self._nb(self.bcast, buf, root, **kw)

    def ireduce(self, sendbuf, op=op_mod.SUM, root: int = 0, **kw) -> Request:
        return self._nb(self.reduce, sendbuf, op, root, **kw)

    def iallgather(self, sendbuf, **kw) -> Request:
        if not kw:
            m = self._isched("iallgather")
            if m is not None:
                self._validate_stacked(sendbuf)
                return m.iallgather(sendbuf)
        return self._nb(self.allgather, sendbuf, **kw)

    def igather(self, sendbuf, root: int = 0, **kw) -> Request:
        return self._nb(self.gather, sendbuf, root, **kw)

    def iscatter(self, sendbuf, root: int = 0, **kw) -> Request:
        return self._nb(self.scatter, sendbuf, root, **kw)

    def ialltoall(self, sendbuf, **kw) -> Request:
        return self._nb(self.alltoall, sendbuf, **kw)

    def ireduce_scatter_block(self, sendbuf, op=op_mod.SUM, **kw) -> Request:
        return self._nb(self.reduce_scatter_block, sendbuf, op, **kw)

    def iscan(self, sendbuf, op=op_mod.SUM) -> Request:
        return self._nb(self.scan, sendbuf, op)

    def iexscan(self, sendbuf, op=op_mod.SUM) -> Request:
        return self._nb(self.exscan, sendbuf, op)

    def iallgatherv(self, per_rank: Sequence[Any]) -> Request:
        return self._nb(self.allgatherv, per_rank)

    def igatherv(self, per_rank: Sequence[Any], root: int = 0) -> Request:
        return self._nb(self.gatherv, per_rank, root)

    def iscatterv(self, chunks: Sequence[Any], root: int = 0) -> Request:
        return self._nb(self.scatterv, chunks, root)

    def ialltoallv(self, send_chunks: Sequence[Sequence[Any]]) -> Request:
        return self._nb(self.alltoallv, send_chunks)

    def ibarrier(self) -> Request:
        ms = self._isched("ibarrier")
        if ms is not None:
            return ms.ibarrier()
        m = self._coll("barrier")
        if hasattr(m, "ibarrier"):       # e.g. the monitoring shim
            return m.ibarrier()
        fn = getattr(m, "_ibarrier_arrays", None)
        if fn is not None:
            return Request(arrays=fn())
        # winner has no async form at all: a completed synchronous
        # barrier is still a correct MPI_Ibarrier
        m.barrier()
        return Request.completed()

    # -- persistent collectives (MPI-4 MPI_Allreduce_init etc.) --------
    # Contiguous-buffer inits build a pre-bound plan (coll/persistent:
    # algorithm decided, executable compiled, codec gates evaluated at
    # init; Start is launch-only, and bucketable starts fuse). The
    # datatype/count forms keep the generic re-dispatch marshaller.
    def allreduce_init(self, sendbuf, op=op_mod.SUM, **kw) -> Request:
        if not kw:
            from ompi_tpu.coll import persistent as _pcoll
            return _pcoll.coll_init(self, "allreduce", sendbuf, op)
        return Request(persistent_start=lambda: self.iallreduce(
            sendbuf, op, **kw))

    def allreduce_bind(self, example, op=op_mod.SUM) -> Callable:
        """Pre-bound hot-path handle — the TPU-native payoff of MPI-4
        persistent collectives (``MPI_Allreduce_init``'s entire purpose
        is to hoist per-call setup out of the loop): validation,
        decision tables, SPC/hook accounting and cache probes run ONCE
        here; the returned callable is the cached compiled executable
        plus a sharding identity check (~0.3 us). Buffers must have
        this communicator's stacked layout (comm.put/alloc results or
        prior outputs). Per-call cost is jax's compiled dispatch alone
        — the floor the framework cannot go below."""
        self._validate_stacked(example)
        self._validate_op(op)
        mod = self._coll("allreduce")
        dev = getattr(mod, "device", mod)
        bind = getattr(dev, "bind_allreduce", None)
        if bind is None:                 # host module won selection
            return lambda buf: mod.allreduce(buf, op)
        return bind(example, op)

    def bcast_init(self, buf, root: int = 0, **kw) -> Request:
        if not kw:
            from ompi_tpu.coll import persistent as _pcoll
            return _pcoll.coll_init(self, "bcast", buf, root)
        return Request(persistent_start=lambda: self.ibcast(buf, root, **kw))

    def allgather_init(self, sendbuf) -> Request:
        from ompi_tpu.coll import persistent as _pcoll
        return _pcoll.coll_init(self, "allgather", sendbuf)

    def reduce_scatter_block_init(self, sendbuf,
                                  op=op_mod.SUM) -> Request:
        from ompi_tpu.coll import persistent as _pcoll
        return _pcoll.coll_init(self, "reduce_scatter_block", sendbuf, op)

    def barrier_init(self) -> Request:
        from ompi_tpu.coll import persistent as _pcoll
        return _pcoll.coll_init(self, "barrier")

    # ==================================================================
    # Point-to-point (pml framework; matching spec pml_ob1_recvfrag.c)
    # ==================================================================
    @property
    def _pml(self):
        eng = getattr(self, "_pml_engine", None)
        if eng is None:
            if self.is_multiprocess:
                # The stacked matching engine is controller-local dict
                # handoff; in a multi-controller world a peer's shard
                # lives on another process and the handoff would be
                # silently wrong. Same clean guard the collectives path
                # raises (coll/xla._to_mesh). Genuine cross-process
                # pt2pt lives in the per-rank model (pml/perrank over
                # btl/tcp) — launch via mpirun --per-rank.
                from ompi_tpu.core.errhandler import ERR_INTERN
                raise MPIError(
                    ERR_INTERN,
                    "stacked pt2pt is single-controller only: this "
                    "communicator spans processes whose shards are not "
                    "addressable here. Use the per-rank execution "
                    "model (mpirun --per-rank) for cross-process "
                    "send/recv, or collectives on this communicator.")
            from ompi_tpu.mca import var
            from ompi_tpu.pml import vprotocol  # registers pml_v_protocol
            from ompi_tpu.pml.stacked import MatchingEngine
            if var.var_get("pml_v_protocol", "none") == "pessimist":
                eng = self._pml_engine = vprotocol.PessimistEngine(self)
            else:
                eng = self._pml_engine = MatchingEngine(self)
        return eng

    def _record_pml(self, event: str) -> None:
        from ompi_tpu.runtime import spc
        from ompi_tpu.utils import hooks
        spc.record(event, 1)
        hooks.fire(event, self, {})

    def send(self, data, src: int, dest: int, tag: int = 0) -> None:
        """MPI_Send from rank ``src`` to ``dest`` (single-controller: the
        sender rank is explicit; ``data`` is that rank's local buffer)."""
        self._check()
        self._check_peer_ft(dest)
        self._record_pml("pml_send")
        self._pml.send(data, src, dest, tag)

    def isend(self, data, src: int, dest: int, tag: int = 0) -> Request:
        self._check()
        self._check_peer_ft(dest)
        self._record_pml("pml_send")
        return self._pml.send(data, src, dest, tag)

    def ssend(self, data, src: int, dest: int, tag: int = 0) -> None:
        """MPI_Ssend: completes only if the receive has started; raises
        the deadlock otherwise (single-controller semantics)."""
        self._check()
        self._check_peer_ft(dest)
        self._record_pml("pml_send")
        self._pml.send(data, src, dest, tag, synchronous=True)

    def bsend(self, data, src: int, dest: int, tag: int = 0) -> None:
        """MPI_Bsend: the payload is buffered (copied) at send time."""
        self._check()
        self._check_peer_ft(dest)
        self._record_pml("pml_send")
        self._pml.send(data, src, dest, tag)

    def recv(self, source: int, tag: int = -1, *, dst: int = 0):
        """MPI_Recv executed by rank ``dst``: returns (data, Status).
        Raises instead of deadlocking if no matching send was posted."""
        self._check()
        if source == -1:  # ANY_SOURCE
            self._check_anysource_ft()
        else:
            self._check_peer_ft(source)
        self._record_pml("pml_recv")
        return self._pml.recv(dst, source, tag)

    def irecv(self, source: int, tag: int = -1, *, dst: int = 0) -> Request:
        # ULFM (req_ft.c): a *nonblocking* wildcard receive posts
        # normally even with unacknowledged failures — a live sender may
        # still match it; the pending error surfaces at test/wait
        # (PtpRequest._check_ft). Only blocking recv raises at entry.
        self._check()
        if source != -1:  # named peer: fail fast, as the reference does
            self._check_peer_ft(source)
        self._record_pml("pml_recv")
        return self._pml.irecv(dst, source, tag)

    def sendrecv(self, senddata, src: int, dest: int, recvsource: int,
                 sendtag: int = 0, recvtag: int = -1):
        """MPI_Sendrecv executed by rank ``src``: post the send, then
        receive (deadlock-free by construction, as in the reference)."""
        self._check()
        self._check_peer_ft(dest)
        if recvsource == -1:  # ANY_SOURCE
            self._check_anysource_ft()
        else:
            self._check_peer_ft(recvsource)
        self._record_pml("pml_send")
        self._record_pml("pml_recv")
        self._pml.send(senddata, src, dest, sendtag)
        return self._pml.recv(src, recvsource, recvtag)

    def probe(self, source: int, tag: int = -1, *, dst: int = 0) -> Status:
        self._check()
        return self._pml.probe(dst, source, tag)

    def iprobe(self, source: int, tag: int = -1, *, dst: int = 0):
        self._check()
        return self._pml.iprobe(dst, source, tag)

    def mprobe(self, source: int, tag: int = -1, *, dst: int = 0):
        self._check()
        return self._pml.mprobe(dst, source, tag)

    def improbe(self, source: int, tag: int = -1, *, dst: int = 0):
        """MPI_Improbe: nonblocking matched probe — (flag, message,
        Status); on no match returns (False, None, None)."""
        self._check()
        flag, status = self._pml.iprobe(dst, source, tag)
        if not flag:
            return False, None, None
        return True, self._pml.mprobe(dst, source, tag), status

    def mrecv(self, message):
        self._check()
        return self._pml.mrecv(message)

    def send_init(self, data, src: int, dest: int, tag: int = 0) -> Request:
        """MPI_Send_init (persistent)."""
        self._check()
        return Request(persistent_start=lambda: self._pml.send(
            data, src, dest, tag))

    def recv_init(self, source: int, tag: int = -1, *,
                  dst: int = 0) -> Request:
        self._check()
        return Request(persistent_start=lambda: self._pml.irecv(
            dst, source, tag))

    # -- partitioned pt2pt (MPI-4, mirrors ompi/mca/part/persist) ------
    def psend_init(self, parts: Sequence[Any], dest: int, tag: int = 0,
                   src: int = 0):
        """MPI_Psend_init: ``parts`` is the partition list; ``pready(i)``
        marks partition i; the message is sent when all are ready."""
        self._check()
        from ompi_tpu.pml.partitioned import PartitionedSend
        return PartitionedSend(self, parts, src, dest, tag)

    def precv_init(self, source: int, tag: int = 0, partitions: int = 1,
                   *, dst: int = 0):
        self._check()
        from ompi_tpu.pml.partitioned import PartitionedRecv
        return PartitionedRecv(self, source, tag, partitions, dst=dst)

    # ==================================================================
    # Communicator algebra
    # ==================================================================
    def dup(self, info: Optional[Info] = None) -> "Communicator":
        self._check()
        c = self.__class__(Group(self.group.world_ranks), self.devices,
                           name=f"{self.name}.dup", parent=self,
                         info=info or self.info,
                         errhandler=self.errhandler)
        try:
            propagate_attrs(self, c)
        except BaseException:
            c.free()                     # no half-built comm leaks
            raise
        return c

    def split(self, colors: Sequence[int], keys: Optional[Sequence[int]] = None
              ) -> List[Optional["Communicator"]]:
        """MPI_Comm_split (comm.c:749). ``colors[r]``/``keys[r]`` are rank
        r's arguments; returns one entry per rank — the new communicator
        containing that rank (shared object) or None (MPI_COMM_NULL) for
        color == UNDEFINED. Children's meshes are parent-device subsets."""
        self._check()
        if keys is None:
            keys = [0] * self.size
        if len(colors) != self.size or len(keys) != self.size:
            self._err(ERR_ARG, "need color/key per rank")
        by_color: Dict[int, List[int]] = {}
        for r, c in enumerate(colors):
            if c != UNDEFINED:
                by_color.setdefault(c, []).append(r)
        out: List[Optional[Communicator]] = [None] * self.size
        # Deterministic order over colors = identical CID allocation on
        # every rank (the agreement property of comm_cid.c).
        for c in sorted(by_color):
            members = sorted(by_color[c], key=lambda r: (keys[r], r))
            g = Group([self.group.world_ranks[r] for r in members])
            devs = [self.devices[r] for r in members]
            newc = self.__class__(
                g, devs, name=f"{self.name}.split({c})",
                parent=self, errhandler=self.errhandler)
            for r in members:
                out[r] = newc
        return out

    def split_type(self, split_type: int,
                   keys: Optional[Sequence[int]] = None):
        """MPI_Comm_split_type: group ranks by hardware locality. TPU
        concretization: COMM_TYPE_SHARED groups ranks whose devices share
        a host process (``device.process_index``); COMM_TYPE_NUMA uses
        the device's NUMA/slice index when exposed (falls back to the
        process); COMM_TYPE_HWTHREAD is one rank = one device, so every
        rank gets its own communicator; UNDEFINED yields MPI_COMM_NULL
        everywhere."""
        if split_type == UNDEFINED:
            return [None] * self.size
        if split_type == 2:           # COMM_TYPE_HWTHREAD
            colors = list(range(self.size))
        elif split_type == 3:         # COMM_TYPE_NUMA
            colors = [int(getattr(d, "numa_node",
                                  getattr(d, "process_index", 0)) or 0)
                      for d in self.devices]
        elif split_type == 1:         # COMM_TYPE_SHARED
            colors = [int(getattr(d, "process_index", 0))
                      for d in self.devices]
        else:
            self._err(ERR_ARG, f"unknown split_type {split_type}")
            return [None] * self.size
        return self.split(colors, keys)

    def create(self, group: Group) -> Optional["Communicator"]:
        """MPI_Comm_create: new communicator over a subgroup."""
        self._check()
        ranks = []
        for wr in group.world_ranks:
            lr = self.group.rank_of(wr)
            if lr == UNDEFINED:
                self._err(ERR_RANK, "group not a subset of communicator")
            ranks.append(lr)
        devs = [self.devices[r] for r in ranks]
        return self.__class__(group, devs, name=f"{self.name}.create",
                              parent=self, errhandler=self.errhandler)

    def compare(self, other: "Communicator") -> int:
        from ompi_tpu.core.group import CONGRUENT, IDENT, SIMILAR, UNEQUAL
        if self is other:
            return IDENT
        g = self.group.compare(other.group)
        if g == IDENT:
            return CONGRUENT
        return SIMILAR if g == SIMILAR else UNEQUAL

    def free(self) -> None:
        fire_delete_attrs(self)
        self._freed = True
        # pvar session semantics: instruments owned by this cid
        # (telemetry histograms, trace_skew_c<cid>) retire with it — a
        # later pvar read must not report a freed comm's keys
        from ompi_tpu import telemetry as _telemetry
        _telemetry.retire_comm(self.cid)

    # -- process topologies (topo framework) ---------------------------
    def create_cart(self, dims: Sequence[int],
                    periods: Optional[Sequence[bool]] = None,
                    reorder: bool = False) -> "Communicator":
        """MPI_Cart_create. ``reorder=True`` maps logical cart coords to
        physical device coords when the backend exposes them (the ICI
        mesh), so cart neighbors are physical neighbors — the TPU
        re-design of topo/treematch rank reordering."""
        import math
        from ompi_tpu.topo import CartTopology
        dims = list(dims)
        if periods is None:
            periods = [False] * len(dims)
        n = math.prod(dims)
        if n > self.size:
            self._err(ERR_ARG, f"cart size {n} exceeds comm size")
        devices = list(self.devices[:n])
        ranks = list(range(n))
        if reorder:
            def devkey(i):
                d = self.devices[i]
                return tuple(getattr(d, "coords", None) or (d.id,))
            ranks = sorted(range(n), key=devkey)
            devices = [self.devices[r] for r in ranks]
        g = Group([self.group.world_ranks[r] for r in ranks])
        c = self.__class__(g, devices, name=f"{self.name}.cart",
                           parent=self, errhandler=self.errhandler)
        c.topo = CartTopology(dims, periods)
        return c

    def _cart(self):
        from ompi_tpu.topo import CartTopology
        if not isinstance(self.topo, CartTopology):
            from ompi_tpu.core.errhandler import ERR_TOPOLOGY
            self._err(ERR_TOPOLOGY, "communicator has no cartesian topology")
        return self.topo

    def cart_rank(self, coords: Sequence[int]) -> int:
        return self._cart().rank(coords)

    def cart_coords(self, rank: int) -> Tuple[int, ...]:
        return self._cart().coords(rank)

    def cart_shift(self, rank: int, direction: int,
                   disp: int = 1) -> Tuple[int, int]:
        return self._cart().shift(rank, direction, disp)

    def cart_sub(self, remain: Sequence[bool]) -> List["Communicator"]:
        """MPI_Cart_sub: split into sub-cart communicators along kept
        dims; returns one entry per rank."""
        topo = self._cart()
        colors, new_topo = topo.sub_keep(remain)
        subs = self.split(colors)
        for s in subs:
            if s is not None and s.topo is None:
                from ompi_tpu.topo import CartTopology
                s.topo = CartTopology(new_topo.dims, new_topo.periods)
        return subs

    def create_graph(self, index: Sequence[int], edges: Sequence[int],
                     reorder: bool = False) -> "Communicator":
        """MPI_Graph_create. ``reorder=True`` runs the treematch
        placement: rank r is bound to the device whose ICI position
        minimizes the graph's weighted hop count (topo/treematch)."""
        from ompi_tpu.topo import GraphTopology
        topo = GraphTopology(index, edges)
        if topo.size > self.size:
            self._err(ERR_ARG, "graph larger than communicator")
        devices = list(self.devices[:topo.size])
        if reorder and topo.size > 1:
            from ompi_tpu.topo import treematch as tm
            cm = tm.comm_matrix_from_graph(index, edges)
            hw = tm.hardware_distance(devices)
            perm = tm.treematch_permutation(cm, hw)
            devices = [devices[perm[r]] for r in range(topo.size)]
        g = Group(self.group.world_ranks[:topo.size])
        c = self.__class__(g, devices,
                           name=f"{self.name}.graph", parent=self,
                           errhandler=self.errhandler)
        c.topo = topo
        return c

    def create_dist_graph_adjacent(self, sources, destinations
                                   ) -> "Communicator":
        from ompi_tpu.topo import DistGraphTopology
        c = self.dup()
        c.topo = DistGraphTopology(sources, destinations)
        c.name = f"{self.name}.dist_graph"
        return c

    def graph_neighbors(self, rank: int) -> List[int]:
        if self.topo is None:
            from ompi_tpu.core.errhandler import ERR_TOPOLOGY
            self._err(ERR_TOPOLOGY, "no topology attached")
        return self.topo.neighbors(rank)

    def neighbor_allgather(self, sendbuf) -> List[Any]:
        """MPI_Neighbor_allgather: each rank receives its neighbors'
        buffers (in neighbor order). Device inputs stay on device: the
        exchange lowers to edge-colored ppermute waves over the mesh
        (topo/neighbor.py — a cart halo exchange is 2 collective-
        permutes per dimension); host inputs take the NumPy path."""
        self._validate_stacked(sendbuf)
        if self.topo is None:
            from ompi_tpu.core.errhandler import ERR_TOPOLOGY
            self._err(ERR_TOPOLOGY, "no topology attached")
        self._require_local_views("neighbor_allgather")
        if isinstance(sendbuf, jax.Array):
            from ompi_tpu.topo import neighbor as nbr
            return nbr.device_neighbor_allgather(self, sendbuf)
        host = np.asarray(sendbuf)
        out = []
        for r in range(self.size):
            nb = [n for n in self.topo.neighbors(r) if n >= 0]
            out.append(np.stack([host[n] for n in nb])
                       if nb else np.empty((0,) + host.shape[1:],
                                           host.dtype))
        return out

    def neighbor_alltoall(self, sendbuf) -> List[Any]:
        """MPI_Neighbor_alltoall: sendbuf (N, max_out_deg, *s); rank r's
        j-th chunk goes to its j-th out-neighbor; each rank receives one
        chunk per in-neighbor (in neighbor order). Device inputs ride
        the ppermute-wave lowering (topo/neighbor.py), host inputs the
        NumPy path."""
        self._validate_stacked(sendbuf, lead=2)
        if self.topo is None:
            from ompi_tpu.core.errhandler import ERR_TOPOLOGY
            self._err(ERR_TOPOLOGY, "no topology attached")
        self._require_local_views("neighbor_alltoall")
        if isinstance(sendbuf, jax.Array):
            from ompi_tpu.topo import neighbor as nbr
            return nbr.device_neighbor_alltoall(self, sendbuf)
        from collections import deque
        host = np.asarray(sendbuf)
        out_nb = getattr(self.topo, "out_neighbors", self.topo.neighbors)
        in_nb = self.topo.neighbors
        # chunk sent from s to its j-th out-neighbor d lands at d at the
        # position of the matching occurrence of s in d's in-neighbor
        # list; FIFO per (sender, receiver) pair handles duplicate edges
        # (periodic dims of size <= 2, multigraph dist-graphs).
        recv = {}
        for s in range(self.size):
            for j, d in enumerate(out_nb(s)):
                if 0 <= d < self.size:
                    recv.setdefault((d, s), deque()).append(host[s, j])
        out = []
        for r in range(self.size):
            chunks = []
            for n in in_nb(r):
                if n < 0:
                    continue
                q = recv.get((r, n))
                chunks.append(q.popleft() if q
                              else np.zeros(host.shape[2:], host.dtype))
            out.append(np.stack(chunks) if chunks
                       else np.empty((0,) + host.shape[2:], host.dtype))
        return out

    def neighbor_allgatherv(self, per_rank: Sequence[Any]) -> List[Any]:
        """MPI_Neighbor_allgatherv: ragged contributions; rank r receives
        the concatenation of its neighbors' (variable-size) buffers in
        neighbor order."""
        if self.topo is None:
            from ompi_tpu.core.errhandler import ERR_TOPOLOGY
            self._err(ERR_TOPOLOGY, "no topology attached")
        arrs, counts = self._ragged(per_rank, "neighbor_allgatherv")
        if arrs and isinstance(arrs[0], jax.Array):
            # pad-to-max wire + ppermute waves, slice valid prefixes
            # back off (the v-collectives' device convention)
            from ompi_tpu.topo import neighbor as nbr
            m = max(counts) if counts else 0
            if m:
                padded = self._pad_stack(arrs, counts, m)
                res = nbr.device_neighbor_allgather(self, padded)
                out = []
                for r in range(self.size):
                    nb = [n for n in self.topo.neighbors(r)
                          if 0 <= n < self.size]
                    out.append(jax.numpy.concatenate(
                        [res[r][k][:counts[n]]
                         for k, n in enumerate(nb)]) if nb
                        else jax.numpy.empty((0,), arrs[0].dtype))
                return out
        out = []
        for r in range(self.size):
            nb = [n for n in self.topo.neighbors(r) if n >= 0]
            out.append(np.concatenate([np.asarray(arrs[n]) for n in nb])
                       if nb else np.empty((0,), arrs[0].dtype))
        return out

    def neighbor_alltoallv(self, send_chunks: Sequence[Sequence[Any]]
                           ) -> List[List[Any]]:
        """MPI_Neighbor_alltoallv: ``send_chunks[r][j]`` is rank r's
        ragged chunk for its j-th out-neighbor; rank r receives one chunk
        per in-neighbor, as a list aligned with its in-neighbor order
        (empty array where the sender provided no chunk — alignment is
        never silently shifted)."""
        if self.topo is None:
            from ompi_tpu.core.errhandler import ERR_TOPOLOGY
            self._err(ERR_TOPOLOGY, "no topology attached")
        if len(send_chunks) != self.size:
            self._err(ERR_COUNT, "need one chunk row per rank")
        self._require_local_views("neighbor_alltoallv")
        if all(isinstance(c, jax.Array)
               for row in send_chunks for c in row) and \
                any(len(row) for row in send_chunks):
            return self._neighbor_alltoallv_device(send_chunks)
        from collections import deque
        out_nb = getattr(self.topo, "out_neighbors", self.topo.neighbors)
        recv: Dict[Tuple[int, int], Any] = {}
        for s in range(self.size):
            for j, d in enumerate(out_nb(s)):
                if 0 <= d < self.size and j < len(send_chunks[s]):
                    recv.setdefault((d, s), deque()).append(
                        np.asarray(send_chunks[s][j]).ravel())
        empty = np.empty((0,), np.float32)
        out: List[List[Any]] = []
        for r in range(self.size):
            chunks = []
            for n in self.topo.neighbors(r):
                if n < 0:
                    chunks.append(empty)
                    continue
                q = recv.get((r, n))
                chunks.append(q.popleft() if q else empty)
            out.append(chunks)
        return out

    def _neighbor_alltoallv_device(self, send_chunks) -> List[List[Any]]:
        """Device lowering of neighbor_alltoallv: pad ragged chunks to
        the max count, ride the ppermute-wave alltoall, slice each
        received chunk back to its sender's length (counts resolved
        through the plan's FIFO edge pairing)."""
        from ompi_tpu.topo import neighbor as nbr
        plan = nbr._plan(self)
        rows = [[jax.numpy.ravel(c) for c in row] for row in send_chunks]
        counts = [[int(c.size) for c in row] for row in rows]
        m = max((c for row in counts for c in row), default=0)
        d_out = max(plan.max_out, 1)
        # dtype from the first actual chunk anywhere (an empty first row
        # must not promote integer payloads to float32)
        dt = next((c.dtype for row in rows for c in row),
                  jax.numpy.float32)
        if m == 0:
            empty = jax.numpy.empty((0,), dt)
            return [[empty for _ in plan.in_lists[r]]
                    for r in range(self.size)]
        padded = jax.numpy.stack([
            jax.numpy.stack(
                [jax.numpy.pad(row[j], (0, m - row[j].size))
                 if j < len(row)
                 else jax.numpy.zeros((m,), dt)
                 for j in range(d_out)])
            for row in rows])                       # (N, D_out, m)
        padded = jax.device_put(padded, NamedSharding(
            self.mesh, P(AXIS)))
        res = nbr.device_neighbor_alltoall(self, padded)
        # per-edge received length: the sender's chunk size for the
        # paired out slot (zero-length when the sender sent nothing)
        length = {}
        for (s, d, j, i) in plan.edges:
            if j is not None and j < len(counts[s]):
                length[(d, i)] = counts[s][j]
            else:
                length[(d, i)] = 0
        # row alignment matches the host path: one entry per in-slot,
        # empty where the slot is invalid (never silently shifted)
        out: List[List[Any]] = []
        empty = jax.numpy.empty((0,), dt)
        for r in range(self.size):
            vs = plan.valid_slots[r]
            row = []
            for i in range(len(plan.in_lists[r])):
                if i not in vs:
                    row.append(empty)
                else:
                    row.append(res[r][vs.index(i)]
                               [:length.get((r, i), 0)])
            out.append(row)
        return out

    # -- attributes (keyvals) ------------------------------------------
    def set_attr(self, keyval: int, value: Any) -> None:
        self.attributes[keyval] = value

    def get_attr(self, keyval: int) -> Tuple[bool, Any]:
        if keyval in self.attributes:
            return True, self.attributes[keyval]
        return False, None

    def delete_attr(self, keyval: int) -> None:
        val = self.attributes.pop(keyval, None)
        cb = _keyvals.get(keyval)
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
        import sys
        sys.stderr.write(f"MPI_Abort on {self.name} errorcode={errorcode}\n")
        raise SystemExit(errorcode)

    # -- ULFM (mpiext/ftmpi semantics; docs/features/ulfm.rst) ---------
    # The failure registry (runtime/ft.py) is the PMIx-event-stream
    # equivalent; these methods implement the MPIX_Comm_* surface over
    # it. Per ULFM, agree/shrink/failure_ack remain usable on revoked
    # communicators — they bypass _check().
    def _failed_local(self) -> List[int]:
        return [r for r, w in enumerate(self.group.world_ranks)
                if self._ft.is_failed(w)]

    def _check_ft_coll(self) -> None:
        """Collectives must not silently complete across a failure
        (ompi/request/req_ft.c behavior: ops involving failed procs
        raise MPIX_ERR_PROC_FAILED until the comm is shrunk)."""
        if not self._ft.any_failed():        # hot path: nothing has failed
            return
        failed = self._failed_local()
        if failed:
            from ompi_tpu.core.errhandler import ERR_PROC_FAILED
            self._err(ERR_PROC_FAILED,
                      f"rank(s) {failed} of {self.name} have failed "
                      f"(shrink or agree to continue)")

    def _check_peer_ft(self, peer: int) -> None:
        if peer is None or not (0 <= peer < self.size):
            return
        if self._ft.is_failed(self.group.world_ranks[peer]):
            from ompi_tpu.core.errhandler import ERR_PROC_FAILED
            self._err(ERR_PROC_FAILED, f"peer rank {peer} has failed")

    def _check_anysource_ft(self) -> None:
        """A wildcard receive with un-acknowledged failures raises
        MPIX_ERR_PROC_FAILED_PENDING semantics: the matching send might
        have come from the dead peer. failure_ack() re-arms wildcards."""
        unacked = [r for r in self._failed_local()
                   if self.group.world_ranks[r] not in self._acked_failures]
        if unacked:
            from ompi_tpu.core.errhandler import ERR_PROC_FAILED
            self._err(ERR_PROC_FAILED,
                      f"ANY_SOURCE receive with unacknowledged failed "
                      f"rank(s) {unacked}; call failure_ack() first")

    def revoke(self) -> None:
        """MPIX_Comm_revoke. Single-controller: the comm object is the
        shared state all ranks observe, so setting the flag *is* the
        reliable revocation broadcast (coll_base_revoke_local.c's job);
        pending pt2pt requests observe it at completion (pml.h:244
        revoke_comm hook ≈ the matching engine consulting the flag)."""
        self._revoked = True

    def is_revoked(self) -> bool:
        return self._revoked

    def shrink(self, failed_ranks: Optional[Sequence[int]] = None
               ) -> "Communicator":
        """MPIX_Comm_shrink: agree on the failed set, return a new
        communicator over the survivors. Works on revoked comms."""
        if self._freed:
            raise MPIError(ERR_COMM, "communicator has been freed")
        failed = set(failed_ranks or ())
        failed.update(self._failed_local())
        # Agreement on the failed set: encode each rank's view as a
        # bitmask and AND-agree (the ftagree pass the reference's shrink
        # performs to reach a uniform survivor list).
        mask = ~sum(1 << r for r in failed)
        agreed, _ = self._agree_module().agree([mask] * self.size)
        alive = [r for r in range(self.size)
                 if (agreed >> r) & 1 and r not in failed]
        g = Group([self.group.world_ranks[r] for r in alive])
        devs = [self.devices[r] for r in alive]
        child = self.__class__(g, devs, name=f"{self.name}.shrink",
                               parent=self, errhandler=self.errhandler)
        # the parent keeps living (ULFM shrink does not free it), but
        # its per-comm instruments describe the dead-rank era — retire
        # them so reads after the shrink start from the survivor set
        from ompi_tpu import telemetry as _telemetry
        _telemetry.retire_comm(self.cid)
        return child

    def ishrink(self):
        from ompi_tpu.core.request import Request
        return Request.completed(self.shrink())

    def _agree_module(self):
        m = self.c_coll.get("agree")
        if m is None:
            from ompi_tpu.coll.ftagree import FtAgreeModule
            return FtAgreeModule(self)
        return m

    def agree(self, flags: Sequence[int]) -> int:
        """MPIX_Comm_agree: uniform bitwise-AND agreement via
        coll/ftagree. Raises MPIX_ERR_PROC_FAILED (carrying the agreed
        value in ``.agreed_value``) when a participant failed and was not
        acknowledged — the ULFM contract: agreement is still reached."""
        if self._freed:
            raise MPIError(ERR_COMM, "communicator has been freed")
        value, failed = self._agree_module().agree(flags)
        unacked = [r for r in failed
                   if self.group.world_ranks[r] not in self._acked_failures]
        if unacked:
            from ompi_tpu.core.errhandler import ERR_PROC_FAILED
            err = MPIError(ERR_PROC_FAILED,
                           f"agreement reached over failed rank(s) "
                           f"{unacked}")
            err.agreed_value = value
            raise err
        return value

    def iagree(self, flags: Sequence[int]):
        from ompi_tpu.core.request import Request
        return Request.completed(self.agree(flags))

    def failure_ack(self) -> None:
        """MPIX_Comm_failure_ack: acknowledge all currently-known
        failures, re-arming ANY_SOURCE receives and quieting agree()."""
        self._acked_failures = frozenset(self._acked_failures | {
            w for w in self.group.world_ranks
            if self._ft.is_failed(w)})

    def failure_get_acked(self) -> Group:
        """MPIX_Comm_failure_get_acked: group of acknowledged failed
        processes."""
        return Group([w for w in self.group.world_ranks
                      if w in self._acked_failures])

    def get_failed(self) -> Group:
        """MPIX_Comm_get_failed (MPI-5 FT): all known-failed members."""
        return Group([w for w in self.group.world_ranks
                      if self._ft.is_failed(w)])

    def ack_failed(self, num_to_ack: Optional[int] = None) -> Group:
        """MPIX_Comm_ack_failed (MPI-5 FT): acknowledge the first
        ``num_to_ack`` failed members (all, when None); returns the
        acked group."""
        failed = [w for w in self.group.world_ranks if self._ft.is_failed(w)]
        if num_to_ack is not None:
            failed = failed[:num_to_ack]
        self._acked_failures = frozenset(self._acked_failures | set(failed))
        return Group(sorted(self._acked_failures))

    def __repr__(self):
        return (f"Communicator({self.name}, size={self.size}, "
                f"cid={self.cid})")


def parent_errh(parent: Optional[Communicator]) -> Errhandler:
    return parent.errhandler if parent is not None else ERRORS_ARE_FATAL


# -- keyval registry (MPI_Comm_create_keyval) ------------------------------
_keyvals: Dict[int, Tuple[Optional[Callable], Optional[Callable]]] = {}
_keyval_counter = itertools.count(100)


def create_keyval(copy_fn: Optional[Callable] = None,
                  delete_fn: Optional[Callable] = None) -> int:
    """MPI_Comm_create_keyval. ``copy_fn(comm, keyval, value) ->
    (keep: bool, new_value)`` runs at Comm_dup (no copy_fn => the
    attribute is not propagated, per MPI); ``delete_fn(comm, keyval,
    value)`` runs at attribute deletion / communicator free."""
    kv = next(_keyval_counter)
    _keyvals[kv] = (copy_fn, delete_fn)
    return kv


def free_keyval(keyval: int) -> None:
    _keyvals.pop(keyval, None)


def propagate_attrs(src, dst) -> None:
    """MPI attribute-copy semantics at Comm_dup (attribute.c:349-384):
    an attribute propagates only through its keyval's copy callback,
    which may veto or transform the value. Shared by both communicator
    classes — one copy of the semantics."""
    for kv, val in src.attributes.items():
        cb = _keyvals.get(kv)
        copy_fn = cb[0] if cb else None
        if copy_fn is None:
            continue
        keep, newval = copy_fn(src, kv, val)
        if keep:
            dst.attributes[kv] = newval


def fire_delete_attrs(comm) -> None:
    """Delete callbacks at communicator free (attribute.c free path).
    A raising callback propagates (MPI_Comm_free must report it)."""
    for kv, val in list(comm.attributes.items()):
        cb = _keyvals.get(kv)
        if cb and cb[1]:
            cb[1](comm, kv, val)
    comm.attributes.clear()
