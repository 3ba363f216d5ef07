"""MPI-4 Sessions — mirrors ``ompi/instance`` (``ompi_instance_t``,
refcounted bring-up, ``instance.c:825`` / common path ``:361-720``).

A Session is an independent handle onto the runtime: it exposes process
sets ("mpi://WORLD", "mpi://SELF", plus one per shared-memory domain),
builds Groups from psets, and creates communicators from groups without
touching COMM_WORLD — the World Process Model (``Init``/``Finalize``) is
layered on top of this, as in the reference.

Round-3 isolation (the 70-LoC enumerator shared
every piece of global state): each Session now owns, per
``instance.c:361-720``'s per-instance bootstrap,

- a private **MCA var scope** (:class:`ompi_tpu.mca.var.VarScope`):
  ``session.var_set`` overrides are visible only inside this session's
  communicator creation and collective dispatch — two concurrent
  sessions can select different coll components/algorithms without
  bleeding into each other or the global store;
- a private **CID space**: session communicators draw from the
  session's counter (the reference allocates CIDs within the instance's
  communicator namespace, ``comm_cid.c``);
- a private **failure registry** (:class:`ompi_tpu.runtime.ft.Registry`):
  failures injected/observed in one session never poison another's
  collectives;
- a refcount on the shared runtime bring-up (``instance.c:825``
  ``ompi_mpi_instance_retain``), released at ``finalize``.
"""
from __future__ import annotations

import itertools
import os
import threading
from typing import Any, Dict, List, Optional

from ompi_tpu.core.communicator import Communicator
from ompi_tpu.core.errhandler import ERR_ARG, ERR_OTHER, MPIError
from ompi_tpu.core.group import Group
from ompi_tpu.core.info import Info
from ompi_tpu.mca import var
from ompi_tpu.runtime import ft

_instance_lock = threading.Lock()
_instance_refcount = 0

# Per-rank comm_create_from_group call ordinals, keyed (tag, group):
# process-global (NOT per-session) because the CID they feed must
# agree across processes regardless of how many local Session objects
# exist. SPMD collective-call order keeps the counters aligned.
_pr_seq_lock = threading.Lock()
_pr_create_seq: Dict[Any, int] = {}


def _instance_retain() -> None:
    global _instance_refcount
    with _instance_lock:
        _instance_refcount += 1


def _instance_release() -> None:
    global _instance_refcount
    with _instance_lock:
        _instance_refcount = max(0, _instance_refcount - 1)


def instance_refcount() -> int:
    return _instance_refcount


class SessionCommunicator(Communicator):
    """A communicator owned by a Session: every public operation runs
    inside the session's var scope (so decision layers and component
    selection read the session's overrides), draws CIDs from the
    session's space, and consults the session's failure registry.
    Children (split/dup/cart/...) inherit all of it through ``parent``."""

    def __init__(self, group, devices, *, session: "Session" = None,
                 parent: Optional[Communicator] = None, **kw):
        sess = session or getattr(parent, "_session", None)
        if sess is None:
            raise MPIError(ERR_ARG,
                           "SessionCommunicator needs a session or a "
                           "session-owned parent")
        self._session = sess
        with var.scope(sess.scope):
            super().__init__(group, devices, parent=parent, **kw)
        self._ft = sess.ft_registry
        # every session communicator — including dup/split/cart/shrink
        # children — registers with its instance so finalize quiesces
        # all of them (instance.c: instance teardown frees its comms)
        sess._comms.append(self)

    def _alloc_cid(self) -> int:
        # set before super().__init__ runs (attribute assignment order
        # in __init__), so the session is always bound here
        return self._session._next_cid()


def _scoped(name: str):
    base = getattr(Communicator, name)

    def wrapper(self, *args, **kw):
        with var.scope(self._session.scope):
            return base(self, *args, **kw)
    wrapper.__name__ = name
    wrapper.__doc__ = base.__doc__
    return wrapper


# Public operations whose behavior can depend on MCA vars (algorithm
# decisions, staging thresholds, schedule knobs, component priorities in
# child-communicator creation).
for _name in ("allreduce", "reduce", "bcast", "allgather", "gather",
              "scatter", "gather_root", "scatter_root", "alltoall",
              "reduce_scatter_block", "reduce_scatter", "scan", "exscan",
              "barrier", "allgatherv", "gatherv", "scatterv", "alltoallv",
              "alltoallw", "iallreduce", "ibcast", "ireduce",
              "iallgather", "igather", "iscatter", "ialltoall",
              "ibarrier", "dup", "split", "split_type", "create",
              "create_cart", "create_graph", "shrink",
              "allreduce_bind", "allreduce_init", "bcast_init"):
    setattr(SessionCommunicator, _name, _scoped(_name))


_session_names = itertools.count(0)


class Session:
    def __init__(self, info: Optional[Info] = None,
                 errhandler=None):
        # the Init-free tier (MPI-4 Sessions) touches the backend
        # first here — same platform re-assert as world init
        from ompi_tpu.runtime.init import assert_platform_pin
        assert_platform_pin()
        import jax
        self.info = info or Info()
        self.errhandler = errhandler
        self.devices = list(jax.devices())
        self._finalized = False
        self.name = f"session#{next(_session_names)}"
        # -- per-instance state (instance.c:361-720) -------------------
        self.scope = var.VarScope()
        self.ft_registry = ft.Registry()
        self._cids = itertools.count(0)
        self._cid_lock = threading.Lock()
        self._comms: List[Communicator] = []
        _instance_retain()
        # Per-rank world (one OS process == one rank): psets enumerate
        # PROCESSES, and session communicators are RankCommunicators
        # drawing CIDs from this session's private space. The router
        # (endpoints, modex) is the shared instance state the refcount
        # guards — exactly the reference's instance-owned RTE.
        from ompi_tpu.runtime import init as _rt
        self._router = _rt._state.get("router")
        if self._router is None and os.environ.get(
                "OMPI_TPU_MCA_mpi_base_per_rank"):
            # A per-rank process without a live router: falling back
            # to the device-pset path would build in-process comms
            # whose "collectives" silently see only local data. The
            # full Init-free instance bootstrap is not implemented —
            # fail loudly instead of wrong answers.
            raise MPIError(ERR_OTHER,
                           "Session in a per-rank job requires the "
                           "runtime to be up (call Init first; "
                           "Init-free session bootstrap is not yet "
                           "supported)")
        if self._router is not None:
            import jax as _jax
            n = _jax.process_count()
            self._my_world = _jax.process_index()
            self._psets: Dict[str, List[int]] = {
                "mpi://WORLD": list(range(n)),
                "mpi://SELF": [self._my_world],
            }
            return
        self._my_world = None
        self._psets = {
            "mpi://WORLD": list(range(len(self.devices))),
            "mpi://SELF": [0],
        }
        # one pset per shared-memory domain (host process), the
        # reference's mpix:// locality psets
        by_proc: Dict[int, List[int]] = {}
        for i, d in enumerate(self.devices):
            by_proc.setdefault(getattr(d, "process_index", 0),
                               []).append(i)
        if len(by_proc) > 1:
            for pi, ranks in sorted(by_proc.items()):
                self._psets[f"mpix://shared/{pi}"] = ranks

    def _check(self) -> None:
        if self._finalized:
            raise MPIError(ERR_OTHER, "session has been finalized")

    def _next_cid(self) -> int:
        with self._cid_lock:
            return next(self._cids)

    # -- per-session config (the instance's MCA scope) -----------------
    def var_set(self, full: str, value: Any) -> None:
        """Override an MCA var for THIS session only."""
        self._check()
        self.scope.set(full, value)

    def var_get(self, full: str, default: Any = None) -> Any:
        if full in self.scope.values:
            return self.scope.values[full]
        return var.var_get(full, default)

    # -- pset enumeration ----------------------------------------------
    def get_num_psets(self) -> int:
        return len(self._psets)

    def get_nth_pset(self, n: int) -> str:
        return list(self._psets.keys())[n]

    def get_pset_info(self, name: str) -> Info:
        if name not in self._psets:
            raise MPIError(ERR_ARG, f"unknown pset {name}")
        i = Info()
        i.set("size", str(len(self._psets[name])))
        return i

    # -- group / communicator construction -----------------------------
    def group_from_pset(self, name: str) -> Group:
        self._check()
        if name not in self._psets:
            raise MPIError(ERR_ARG, f"unknown pset {name}")
        return Group(self._psets[name])

    def comm_create_from_group(self, group: Group,
                               tag: str = "",
                               info: Optional[Info] = None) -> Communicator:
        self._check()
        if self._router is not None:
            # Per-rank world: the CID must AGREE across processes, and
            # sessions are process-local objects (a rank may create
            # extra ones), so session identity CANNOT be part of it.
            # MPI-4's own matching rule for comm_create_from_group is
            # (group, tag) in collective-call order — we stamp
            # ("s", tag, group, per-(tag, group) call ordinal), which
            # every participant derives identically because the call
            # is collective over the group. Sequential same-tag calls
            # therefore get distinct channels too.
            from ompi_tpu.core.rankcomm import RankCommunicator
            if self._my_world not in group.world_ranks:
                return None
            gkey = tuple(group.world_ranks)
            with _pr_seq_lock:
                ordinal = _pr_create_seq.get((tag, gkey), 0)
                _pr_create_seq[(tag, gkey)] = ordinal + 1
            c = RankCommunicator(
                group, self._my_world, self._router,
                cid=("s", tag, gkey, ordinal),
                name=tag or f"{self.name}.comm", info=info,
                errhandler=self.errhandler)
            # the ownership list rides parent linkage: derived comms
            # (dup/split/cart/shrink) self-register so finalize
            # quiesces the whole family
            c._owner_list = self._comms
            self._comms.append(c)
            return c
        devs = [self.devices[r] for r in group.world_ranks]
        return SessionCommunicator(
            group, devs, session=self,
            name=tag or f"{self.name}.comm", info=info,
            errhandler=self.errhandler)

    def finalize(self) -> None:
        """``MPI_Session_finalize``: communicators created from the
        session must already be freed (we free them, as ERRORS_RETURN
        quality-of-implementation); releases the instance refcount."""
        if self._finalized:
            return
        for c in self._comms:
            if not c._freed:
                c.free()
        self._comms.clear()
        self._finalized = True
        _instance_release()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.finalize()
        return False
