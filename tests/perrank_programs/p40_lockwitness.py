"""Lock-order witness drill (docs/ANALYSIS.md): a 4-rank world with
pt2pt sends, persistent collectives, and ft heartbeats all running
concurrently under ``mpi_base_lockwitness``. Every lock the endpoint /
progress / detector bring-up creates is wrapped; the drill asserts the
acquisition-order graph this workload builds is ACYCLIC (no potential
deadlock anywhere on the exercised paths) and dumps the per-rank graph
for ``tools/tracedump summary`` to merge
(tests/test_analyze_multiproc.py).
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"  # ranks run on the host, never the chip
# arm the witness and the heartbeat detector BEFORE Init registers and
# reads the MCA vars (the env route mpirun users take)
os.environ["OMPI_TPU_MCA_mpi_base_lockwitness"] = "1"
os.environ.setdefault("OMPI_TPU_MCA_mpi_base_ft_hb_period", "0.05")

import jax

jax.config.update("jax_platforms", "cpu")

import threading                 # noqa: E402

import numpy as np               # noqa: E402

import ompi_tpu as MPI           # noqa: E402
from ompi_tpu.analyze import lockwitness  # noqa: E402
from ompi_tpu.mca import pvar    # noqa: E402

MPI.Init(MPI.THREAD_MULTIPLE)
assert lockwitness.installed, "witness must be armed by Init"
w = MPI.get_comm_world()
n, r = w.size, w.rank()
assert n == 4

NMSG = 20
errors = []

# an app-level ORDERED pair both threads nest consistently around
# their MPI calls: the framework's own hot paths follow the hand-off
# discipline (deliver/feed/set happen after lock release — the
# lock_blocking lint rule's domain), so app nesting is what puts real
# edges in the graph; taken in one global order it must stay acyclic
order_outer = threading.Lock()
order_inner = threading.Lock()


def pt2pt_ring():
    """Even ranks send-then-recv, odd recv-then-send — a full ring per
    iteration on the worker thread while collectives run on main."""
    try:
        right, left = (r + 1) % n, (r - 1) % n
        for i in range(NMSG):
            msg = np.full(256, r * 1000 + i, np.int64)
            with order_outer:
                with order_inner:
                    pass             # same order as the main thread
            if r % 2 == 0:
                w.send(msg, right, tag=40)
                data, _ = w.recv(left, tag=40)
            else:
                data, _ = w.recv(left, tag=40)
                w.send(msg, right, tag=40)
            assert int(np.asarray(data)[0]) == left * 1000 + i
    except BaseException as e:   # noqa: BLE001
        errors.append(e)


th = threading.Thread(target=pt2pt_ring)
th.start()

# persistent collective plan re-armed on the main thread, concurrent
# with the ring traffic and the detector's heartbeat ticks
data = np.full(512, float(r + 1), np.float32)
ref = np.asarray(w.allreduce(data, MPI.SUM))
req = w.allreduce_init(data, MPI.SUM)
for _ in range(10):
    req.start()
    req.wait()
assert np.asarray(req.get()).tobytes() == ref.tobytes()

th.join(timeout=120)
assert not th.is_alive(), "pt2pt thread hung"
assert not errors, errors

w.barrier()

rep = lockwitness.report()
assert rep["installed"]
# the workload must actually have exercised witnessed nesting …
assert rep["sites"], "no witnessed locks created"
assert rep["edges"], "no acquisition-order edges observed"
# … and the order graph must be ACYCLIC: no potential deadlock on any
# path this drill crossed (the ISSUE-10 acceptance assertion)
assert rep["cycles"] == [], rep["cycles"]
assert pvar.pvar_read("lockwitness_max_hold_us") > 0.0
assert pvar.pvar_read("lockwitness_edges") == len(rep["edges"])

dump_dir = os.environ.get("P40_DUMP_DIR", "/tmp")
lockwitness.dump(os.path.join(dump_dir, f"lw_r{r}.json"), rank=r)

MPI.Finalize()
print(f"OK p40_lockwitness rank={r}/{n} sites={len(rep['sites'])} "
      f"edges={len(rep['edges'])}", flush=True)
