"""Per-rank execution: textbook MPI programs under ``mpirun --per-rank``.

The round-2 wall: no textbook per-rank MPI program
could run — ``rank()`` returned 0 everywhere and nothing moved bytes
between processes. These tests launch the mpi4py-flavored smoke programs
in ``tests/perrank_programs/`` as REAL multi-process jobs: ``mpirun
--per-rank -n N`` forks N rank processes (the PRRTE fork/exec role,
``ompi/tools/mpirun/main.c:157-180``), each binds the JAX coordination
service (PMIx stand-in), pt2pt rides btl/tcp, collectives ride textbook
p2p algorithms or one compiled XLA program over the process mesh.
"""
import os
import subprocess
import time
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PROGS = os.path.join(_REPO, "tests", "perrank_programs")
_MPIRUN = os.path.join(_REPO, "ompi_tpu", "tools", "mpirun.py")

# (program, nprocs) — odd sizes exercise the non-power-of-2 paths of the
# binomial/dissemination algorithms.
CASES = [
    ("p01_hello.py", 2),
    ("p02_ring.py", 4),
    ("p03_halo.py", 3),
    ("p04_bcast.py", 3),
    ("p05_allreduce.py", 2),
    ("p06_gather_scatter.py", 3),
    ("p07_alltoall.py", 2),
    ("p08_barrier_probe.py", 3),
    ("p09_isend_irecv.py", 3),
    ("p10_split.py", 4),
    ("p11_scan_reduce.py", 3),
    ("p12_ssend_mprobe.py", 2),
    ("p13_rma.py", 3),
    ("p14_shmem.py", 3),
    ("p15_cart_halo.py", 4),
    ("p16_master_worker.py", 4),
    ("p20_shmem_ext.py", 3),
    ("p21_mpiio.py", 3),
    ("p22_part_sync.py", 3),
    ("p23_sessions.py", 3),
    ("p25_thread_multiple.py", 2),
    ("p26_churn.py", 3),
    ("p27_staged_coll.py", 3),
    ("p28_devxfer.py", 3),
    ("p29_stage_probe.py", 3),
    ("p30_bidir_bulk.py", 2),
]


def _run(prog: str, n: int):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_"))}
    cmd = [sys.executable, _MPIRUN, "--per-rank", "-n", str(n),
           "--timeout", "150", os.path.join(_PROGS, prog)]
    return subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=200, cwd=_REPO)


@pytest.mark.parametrize("prog,n", CASES,
                         ids=[c[0].removesuffix(".py") for c in CASES])
def test_perrank_program(prog, n):
    res = _run(prog, n)
    assert res.returncode == 0, \
        f"rc={res.returncode}\n--- out\n{res.stdout}\n--- err\n" \
        f"{res.stderr[-4000:]}"
    marker = f"OK {prog.removesuffix('.py')}"
    count = res.stdout.count(marker)
    assert count == n, f"expected {n} '{marker}' lines, got {count}:\n" \
                       f"{res.stdout}"


def test_cross_job_connect_accept(tmp_path):
    """TWO independently-launched mpirun jobs (two coordination
    services) rendezvous via Open_port/Comm_accept/Comm_connect and
    exchange pt2pt both directions including non-root ranks.

    Retried (3 attempts, with a drain pause): FOUR rank processes
    (each importing jax) plus two launchers share the 1-core CI host
    with whatever the suite ran just before, so the bounded
    rendezvous occasionally times out under load — a capacity
    artifact, not a product signal (the isolated run is
    deterministic, observed 20 s; two back-to-back attempts have
    been seen to collide with the same load spike)."""
    port_file = str(tmp_path / "port.txt")
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_"))}
    prog = os.path.join(_PROGS, "p18_connect.py")
    last = None
    for attempt in range(3):
        if attempt:
            time.sleep(20 * attempt)     # let the load spike drain
        if os.path.exists(port_file):
            os.unlink(port_file)
        jobs = []
        for role in ("accept", "connect"):
            # generous bounds: under full-suite load on the 1-core
            # host, four jax imports + the rendezvous can exceed 150 s
            cmd = [sys.executable, _MPIRUN, "--per-rank", "-n", "2",
                   "--timeout", "240", prog, role, port_file]
            jobs.append(subprocess.Popen(cmd, env=env,
                                         stdout=subprocess.PIPE,
                                         stderr=subprocess.PIPE,
                                         text=True, cwd=_REPO))
        outs = [j.communicate(timeout=300) for j in jobs]
        ok = all(j.returncode == 0 for j in jobs) and all(
            out.count(f"OK p18_connect {role}") == 2
            for (out, _), role in zip(outs, ("accept", "connect")))
        if ok:
            return
        last = [(role, j.returncode, out, err[-3000:])
                for (out, err), j, role in zip(outs, jobs,
                                               ("accept", "connect"))]
    raise AssertionError(
        f"cross-job rendezvous failed 3 times: {last}")


def test_perrank_ulfm_survives_real_death():
    """Rank n-1 os._exit()s mid-run; the survivors detect it through
    the connection monitor, their pending receives error, shrink()
    agrees on the survivor set, and the shrunk communicator computes.
    The job exits nonzero (the victim's code + jax's own shutdown
    barrier noise) — what matters is every survivor completing."""
    res = _run("p17_ulfm.py", 4)
    assert res.returncode != 0          # the victim really died
    count = res.stdout.count("OK p17_ulfm")
    assert count == 3, f"expected 3 survivor OKs, got {count}:\n" \
                       f"{res.stdout}\n--- err\n{res.stderr[-3000:]}"


def test_perrank_coll_interposition():
    """coll/sync + coll/monitoring interpose on per-rank communicators
    through the same MCA vars as the stacked world (outermost-call
    counting: internal composition never double-counts)."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_"))}
    cmd = [sys.executable, _MPIRUN, "--per-rank", "-n", "3",
           "--timeout", "150",
           "--mca", "coll_sync_barrier_before", "3",
           "--mca", "coll_monitoring_enable", "1",
           os.path.join(_PROGS, "p24_interpose.py")]
    res = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=200, cwd=_REPO)
    assert res.returncode == 0, \
        f"rc={res.returncode}\n{res.stdout}\n--- err\n{res.stderr[-4000:]}"
    assert res.stdout.count("OK p24_interpose") == 3, res.stdout
