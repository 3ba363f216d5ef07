"""Multi-controller integration: 2 real processes, one COMM_WORLD.

The round-1 gap: everything ran
single-controller and the jax.distributed wire-up was dead code. This
test launches TWO OS processes through ``tools/mpirun.py
--coordinator`` (the exec-shim launcher, spec
``ompi/tools/mpirun/main.c:157-180``), each binding 2 virtual CPU
devices; ``MPI.Init`` in each performs ``jax.distributed.initialize``
(the PMIx modex/fence stand-in, spec ``instance.c:547-569``) and builds
a 4-rank COMM_WORLD spanning the process boundary. The child asserts a
cross-process allreduce, the hier/DCN algorithm path under a genuine
``process_index > 0``, a cross-process barrier and a spanning
sub-communicator.
"""
import os
import socket
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CHILD = os.path.join(_REPO, "tests", "multiproc_child.py")
_MPIRUN = os.path.join(_REPO, "ompi_tpu", "tools", "mpirun.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_world():
    port = _free_port()
    # A clean environment: the children pick their own platform; the
    # parent test process's in-proc 8-device CPU world must not leak.
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_"))}
    procs = []
    for host_id in (0, 1):
        cmd = [sys.executable, _MPIRUN,
               "--coordinator", f"127.0.0.1:{port}",
               "--num-hosts", "2", "--host-id", str(host_id),
               "--mca", "coll_self_priority", "1",
               _CHILD]
        procs.append(subprocess.Popen(
            cmd, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, cwd=_REPO))
    outs = []
    for host_id, p in enumerate(procs):
        try:
            out, err = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((host_id, p.returncode, out, err))
    for host_id, rc, out, err in outs:
        assert rc == 0, f"host {host_id} rc={rc}\n--- out\n{out}\n--- err\n{err[-3000:]}"
        assert f"MULTIPROC-OK process={host_id}" in out, out
