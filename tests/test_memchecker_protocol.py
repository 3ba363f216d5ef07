"""Round-3 hardening: the memchecker analogue (opal/mca/memchecker),
the pt2pt protocol switch (eager vs fabric-touching rendezvous,
pml_ob1_sendreq.h:389-460),
and thread stress of the matching engines (test/class/opal_fifo.c's
role)."""
import threading

import jax
import numpy as np
import pytest

import ompi_tpu as MPI
from ompi_tpu.mca import var
from ompi_tpu.utils import memchecker


@pytest.fixture
def memcheck():
    var.var_set("mpi_memchecker_enable", True)
    memchecker._reset_for_tests()
    yield
    var.var_set("mpi_memchecker_enable", False)
    memchecker._reset_for_tests()


def test_memchecker_detects_inflight_mutation(memcheck):
    buf = np.arange(8, dtype=np.float32)
    memchecker.inflight(buf, "pending op")
    buf[3] = 99.0                      # the race valgrind would flag
    with pytest.raises(memchecker.MemcheckError):
        memchecker.verify(buf)
    assert memchecker.violations() == 1


def test_memchecker_clean_buffer_passes(memcheck):
    buf = np.arange(8, dtype=np.float32)
    memchecker.inflight(buf)
    memchecker.verify(buf)             # untouched: fine
    memchecker.verify(buf)             # already released: no-op


def test_memchecker_undefined_read(memcheck):
    buf = np.zeros(4, np.float32)
    memchecker.undefined(buf, "posted receive")
    with pytest.raises(memchecker.MemcheckError):
        memchecker.check_readable(buf)
    memchecker.defined(buf)
    memchecker.check_readable(buf)     # defined again: fine


def test_memchecker_disabled_is_noop():
    memchecker._reset_for_tests()
    buf = np.zeros(4, np.float32)
    memchecker.inflight(buf)
    buf[0] = 1.0
    memchecker.verify(buf)             # disabled: silent


def test_memchecker_partitioned_send_discipline(memcheck, world):
    """MPI-4: partition i is library-owned from pready(i) to operation
    completion — writing it after pready is non-portable even though
    this engine copies eagerly; the memchecker flags it."""
    parts = [np.full(4, float(i)) for i in range(3)]
    req = world.psend_init(parts, dest=1, tag=5)
    rreq = world.precv_init(0, tag=5, partitions=3, dst=1)
    rreq.start()
    req.start()
    req.pready(0)
    parts[0][0] = 777.0                # violates the pready contract
    req.pready(1)
    with pytest.raises(memchecker.MemcheckError):
        req.pready(2)                  # completion verifies all parts


def test_protocol_switch_rendezvous_moves_bytes(world):
    """Device payloads above the eager limit are MOVED to the
    destination rank's device at send time (the fabric-touching
    rendezvous put); small payloads stay reference handoffs."""
    from ompi_tpu.runtime import spc
    var.var_set("pml_stacked_eager_limit", 1 << 10)
    try:
        big = jax.device_put(np.ones(4096, np.float32),
                             world.devices[0])      # 16 KB > 1 KB limit
        world.send(big, 0, 3, tag=11)
        data, _ = world.recv(0, tag=11, dst=3)
        assert list(data.devices()) == [world.devices[3]], \
            data.devices()
        np.testing.assert_allclose(np.asarray(data), 1.0)

        small = jax.device_put(np.ones(16, np.float32),
                               world.devices[0])
        world.send(small, 0, 3, tag=12)
        data2, _ = world.recv(0, tag=12, dst=3)
        assert list(data2.devices()) == [world.devices[0]]  # eager ref
    finally:
        var.var_set("pml_stacked_eager_limit", 1 << 16)


def test_perrank_engine_thread_stress():
    """The per-rank matching engine under concurrent senders/receivers
    (loopback router): no lost or duplicated messages, FIFO per tag
    stream (the reference stress-tests its lock-free queues the same
    way, test/class/opal_fifo.c)."""
    from ompi_tpu.core.group import Group
    from ompi_tpu.pml.perrank import PerRankEngine, Router

    kv = {}
    router = Router(0, 1, kv.__setitem__, kv.__getitem__)

    class _C:
        cid = "stress"
        size = 1

        def rank(self):
            return 0

        def world_rank_of(self, r):
            return 0
    eng = PerRankEngine(_C(), router)
    NT, NMSG = 4, 200
    errors = []

    def sender(t):
        for i in range(NMSG):
            eng.send(np.array([t, i]), 0, tag=t)

    def receiver(t):
        try:
            for i in range(NMSG):
                data, st = eng.recv(source=0, tag=t, timeout=60)
                assert data[0] == t and data[1] == i, (t, i, data)
        except Exception as e:          # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=f, args=(t,))
               for t in range(NT) for f in (sender, receiver)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
    router.close()
    assert not errors, errors[:3]


def test_stacked_engine_thread_stress(world):
    """The single-controller matching engine (native C++ core when
    available) under threads: per-thread tag streams stay FIFO and
    nothing is lost."""
    NT, NMSG = 4, 100
    errors = []

    def worker(t):
        try:
            for i in range(NMSG):
                world.send(np.array([t, i]), 0, 1, tag=100 + t)
            for i in range(NMSG):
                data, _ = world.recv(0, tag=100 + t, dst=1)
                assert data[1] == i, (t, i, data)
        except Exception as e:          # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(NT)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
    assert not errors, errors[:3]
