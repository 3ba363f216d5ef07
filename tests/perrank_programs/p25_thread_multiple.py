"""MPI_THREAD_MULTIPLE stress: several threads per rank send and
receive concurrently over BOTH transports (small frames on tcp, bulk
frames on the sm rings), exercising the bml sequencing holdback, the
sm producer locks, and the matching engine's thread safety. Every
message must arrive intact, per-(thread-tag) in order, with none lost.
"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
# pin the sm threshold (this program stresses the sm-ring producer
# locks; the init micro-probe would otherwise demote sm on hosts
# where the ring measures slower than sockets)
os.environ.setdefault("OMPI_TPU_MCA_btl_sm_min_bytes", str(32 << 10))
import jax
jax.config.update("jax_platforms", "cpu")
import threading                 # noqa: E402
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402

MPI.Init(MPI.THREAD_MULTIPLE)
world = MPI.get_comm_world()
r, n = world.rank(), world.size
assert n == 2
peer = 1 - r

NTHREADS = 4
NMSG = 25
BIG = (64 << 10) // 8            # 64 KB -> the sm bandwidth plane

errors = []


def sender(t):
    try:
        for i in range(NMSG):
            if i % 5 == 4:       # every 5th message is bulk (sm ring)
                world.send(np.full(BIG, t * 1000 + i, np.int64),
                           peer, tag=100 + t)
            else:
                world.send(np.array([t * 1000 + i], dtype=np.int64),
                           peer, tag=100 + t)
    except BaseException as e:   # noqa: BLE001
        errors.append(("send", t, e))


def receiver(t):
    try:
        for i in range(NMSG):
            data, st = world.recv(peer, tag=100 + t)
            assert st.tag == 100 + t
            # per-(src, tag) FIFO: message i of thread t's stream
            assert int(np.asarray(data).ravel()[0]) == t * 1000 + i, \
                (t, i, data)
            if i % 5 == 4:
                assert np.asarray(data).size == BIG
    except BaseException as e:   # noqa: BLE001
        errors.append(("recv", t, e))


threads = [threading.Thread(target=sender, args=(t,))
           for t in range(NTHREADS)]
threads += [threading.Thread(target=receiver, args=(t,))
            for t in range(NTHREADS)]
for th in threads:
    th.start()
for th in threads:
    th.join(timeout=120)
assert not any(th.is_alive() for th in threads), "stress threads hung"
assert not errors, errors

world.barrier()

# transports really mixed under concurrency
from ompi_tpu.runtime.init import _state  # noqa: E402
stats = _state["router"].endpoint.stats
assert stats["tcp"] > 0, stats
if _state["router"].endpoint.sm is not None:
    assert stats["sm"] > 0, stats

MPI.Finalize()
print(f"OK p25_thread_multiple rank={r}/{n} "
      f"sm={stats['sm']} tcp={stats['tcp']}", flush=True)
