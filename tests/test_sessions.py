"""MPI-4 Session isolation (``ompi/instance/instance.c:361-720``):
per-session MCA var scope, CID space, coll selection, and failure
registry — two concurrent sessions must not bleed state into each other
or the world (the round-2 gap: session.py shared every global)."""
import numpy as np
import pytest

import ompi_tpu as MPI
from ompi_tpu.mca import var
from ompi_tpu.runtime import ft
from ompi_tpu.runtime.session import (Session, SessionCommunicator,
                                      instance_refcount)


def test_var_scope_isolation(world):
    """Concurrent sessions with different var overrides: each session's
    communicators see their own values; the global store never changes."""
    base = var.var_get("coll_xla_allreduce_algorithm", "auto")
    with Session() as s1, Session() as s2:
        s1.var_set("coll_xla_allreduce_algorithm", "hier")
        s2.var_set("coll_xla_allreduce_algorithm", "direct")
        assert s1.var_get("coll_xla_allreduce_algorithm") == "hier"
        assert s2.var_get("coll_xla_allreduce_algorithm") == "direct"
        # the global store is untouched
        assert var.var_get("coll_xla_allreduce_algorithm", "auto") == base

        c1 = s1.comm_create_from_group(s1.group_from_pset("mpi://WORLD"))
        c2 = s2.comm_create_from_group(s2.group_from_pset("mpi://WORLD"))
        x = np.ones((world.size, 8), np.float32)
        # both compute correctly through their own algorithm choice
        y1 = c1.allreduce(c1.put(x), MPI.SUM)
        y2 = c2.allreduce(c2.put(x), MPI.SUM)
        np.testing.assert_allclose(np.asarray(y1)[0], world.size)
        np.testing.assert_allclose(np.asarray(y2)[0], world.size)
        # each session's decision really read its own override
        m1 = c1.c_coll["allreduce"].device
        m2 = c2.c_coll["allreduce"].device
        with var.scope(s1.scope):
            assert m1._algorithm("allreduce", 32, True) == "hier"
        with var.scope(s2.scope):
            assert m2._algorithm("allreduce", 32, True) == "direct"


def test_session_var_set_does_not_leak_to_world(world):
    """A session override must not change what the world communicator's
    dispatch sees — even while the session is alive."""
    with Session() as s:
        s.var_set("coll_nbc_priority", -1)
        # the world still selects nbc for i-collectives
        assert var.var_get("coll_nbc_priority", 30) >= 0
        req = world.iallreduce(world.alloc((4,), np.float32, fill=1.0),
                               MPI.SUM)
        req.wait()


def test_cid_space_isolation(world):
    """Session communicators draw CIDs from the session's own space."""
    with Session() as s1, Session() as s2:
        c1a = s1.comm_create_from_group(s1.group_from_pset("mpi://WORLD"))
        c1b = s1.comm_create_from_group(s1.group_from_pset("mpi://SELF"))
        c2a = s2.comm_create_from_group(s2.group_from_pset("mpi://WORLD"))
        assert c1a.cid == 0 and c2a.cid == 0      # independent spaces
        assert c1b.cid > c1a.cid                  # monotone within one
        # children stay in the session's space and class
        subs = c1a.split([r % 2 for r in range(c1a.size)])
        assert isinstance(subs[0], SessionCommunicator)
        assert subs[0].cid > c1b.cid


def test_ft_registry_isolation(world):
    """A failure injected in one session poisons only that session."""
    with Session() as s1, Session() as s2:
        c1 = s1.comm_create_from_group(s1.group_from_pset("mpi://WORLD"))
        c2 = s2.comm_create_from_group(s2.group_from_pset("mpi://WORLD"))
        c1.set_errhandler(MPI.ERRORS_RETURN)
        s1.ft_registry.fail_rank(0, "injected in s1")
        with pytest.raises(MPI.MPIError):
            c1.allreduce(c1.alloc((2,), np.float32, fill=1.0), MPI.SUM)
        # session 2 and the world are unaffected
        y = c2.allreduce(c2.alloc((2,), np.float32, fill=1.0), MPI.SUM)
        np.testing.assert_allclose(np.asarray(y)[0], float(c2.size))
        assert not ft.is_failed(0)
        w = world.allreduce(world.alloc((2,), np.float32, fill=1.0),
                            MPI.SUM)
        np.testing.assert_allclose(np.asarray(w)[0], float(world.size))
        # ULFM recovery inside the session: shrink keeps the session's
        # registry and class
        shrunk = c1.shrink()
        assert isinstance(shrunk, SessionCommunicator)
        assert shrunk.size == c1.size - 1
        ys = shrunk.allreduce(shrunk.alloc((2,), np.float32, fill=1.0),
                              MPI.SUM)
        np.testing.assert_allclose(np.asarray(ys)[0], float(shrunk.size))


def test_session_agree_uses_session_registry(world):
    """coll/ftagree must consult the communicator's failure domain:
    a session-injected failure makes agree() raise in THAT session and
    nowhere else (ULFM contract + instance isolation)."""
    with Session() as s1, Session() as s2:
        c1 = s1.comm_create_from_group(s1.group_from_pset("mpi://WORLD"))
        c2 = s2.comm_create_from_group(s2.group_from_pset("mpi://WORLD"))
        s1.ft_registry.fail_rank(0, "injected in s1")
        with pytest.raises(MPI.MPIError) as ei:
            c1.agree([~0] * c1.size)
        assert hasattr(ei.value, "agreed_value")
        assert c2.agree([~0] * c2.size) == ~0      # s2 unaffected
        assert world.agree([~0] * world.size) == ~0


def test_session_scope_reaches_deferred_nbc_rounds(world):
    """A session's algorithm override must govern the nonblocking
    fused path even though its round executes later from the progress
    engine (the deferred-decision escape found in review)."""
    with Session() as s:
        s.var_set("coll_xla_allreduce_algorithm", "hier")
        c = s.comm_create_from_group(s.group_from_pset("mpi://WORLD"))
        x = c.alloc((1 << 15,), np.float32, fill=1.0)   # > fused_min
        req = c.iallreduce(x, MPI.SUM)
        req.wait()
        np.testing.assert_allclose(np.asarray(req.get())[0],
                                   float(c.size), rtol=1e-5)
        dev = c.c_coll["allreduce"].device
        assert any(k[0] == "allreduce" and "hier" in k
                   for k in dev._cache), list(dev._cache)


def test_session_bound_handle_uses_session_algorithm(world):
    """allreduce_bind on a SessionCommunicator warms with the
    session's algorithm choice, not the global one."""
    with Session() as s:
        s.var_set("coll_xla_allreduce_algorithm", "hier")
        c = s.comm_create_from_group(s.group_from_pset("mpi://WORLD"))
        x = c.alloc((16,), np.float32, fill=2.0)
        h = c.allreduce_bind(x, MPI.SUM)
        np.testing.assert_allclose(np.asarray(h(x))[0], 2.0 * c.size,
                                   rtol=1e-5)
        dev = c.c_coll["allreduce"].device
        assert any(k[0] == "allreduce" and "hier" in k
                   for k in dev._cache), list(dev._cache)


def test_instance_refcount(world):
    r0 = instance_refcount()
    s1 = Session()
    s2 = Session()
    assert instance_refcount() == r0 + 2
    s1.finalize()
    s1.finalize()                      # idempotent
    assert instance_refcount() == r0 + 1
    s2.finalize()
    assert instance_refcount() == r0


def test_finalized_session_rejects_use(world):
    s = Session()
    s.finalize()
    with pytest.raises(MPI.MPIError):
        s.group_from_pset("mpi://WORLD")
    with pytest.raises(MPI.MPIError):
        s.var_set("coll_nbc_priority", 10)


def test_session_finalize_frees_comms(world):
    """finalize quiesces ALL session communicators, including derived
    children (dup/split) — not just the directly-created ones."""
    s = Session()
    c = s.comm_create_from_group(s.group_from_pset("mpi://WORLD"))
    d = c.dup()
    subs = c.split([r % 2 for r in range(c.size)])
    s.finalize()
    assert c._freed and d._freed
    assert all(sc._freed for sc in subs if sc is not None)
    with pytest.raises(MPI.MPIError):
        c.barrier()
    with pytest.raises(MPI.MPIError):
        d.barrier()


def test_scope_epoch_keeps_world_memos_hot(world):
    """Interleaving session and world collectives must not invalidate
    the world's epoch-keyed decision memos (the hot-path property): the
    epoch token is scope-qualified, not globally bumped per scope
    entry/exit."""
    e0 = var.epoch()
    with Session() as s:
        c = s.comm_create_from_group(s.group_from_pset("mpi://WORLD"))
        x = np.ones((world.size, 4), np.float32)
        c.allreduce(c.put(x), MPI.SUM)
        world.allreduce(world.put(x), MPI.SUM)
        c.allreduce(c.put(x), MPI.SUM)
    assert var.epoch() == e0            # outside any scope: unchanged
    # inside a scope the token is scope-qualified, stable per scope
    with var.scope(s.scope):
        t1 = var.epoch()
        t2 = var.epoch()
    assert t1 == t2 and t1 != e0
