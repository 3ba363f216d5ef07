"""``mpirun`` equivalent — a thin argv-translating launcher.

Behavioral spec: the reference's mpirun is an exec shim that finds
prterun, translates argv, and execs it (``ompi/tools/mpirun/main.c:32-48,
157-180``); the runtime (PRRTE) owns process placement.

TPU-native re-design: placement is device binding.
- Single-controller (default): ``mpirun -n N prog.py`` sets
  ``OMPI_TPU_MCA_mpi_base_num_ranks=N`` and execs ``python prog.py``
  once — the controller binds N mesh devices as ranks.
- Multi-host: ``--coordinator host:port --num-hosts H --host-id I``
  populate the jax.distributed coordination-service vars (the PMIx
  stand-in); one controller per host, each contributing its local
  devices.
- Per-rank: ``mpirun --per-rank -n N prog.py`` takes the PRRTE DVM role
  itself — fork/exec N rank processes on this host (each one MPI rank,
  ``rank() == jax.process_index()``), wire them to a local coordination
  service, wait for all, and propagate the first failure
  (``main.c:157-180``'s process-boundary role, without the external
  daemon).
``--mca k v`` translates to ``OMPI_TPU_MCA_<k>`` exactly like the
reference's ``--mca`` -> ``OMPI_MCA_*`` env translation.
"""
from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys


def build_env(args, base_env) -> dict:
    env = dict(base_env)
    # The launched program must find the library regardless of cwd (the
    # reference's mpirun prepends its own libdir the same way).
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    parts = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    if pkg_root not in parts:
        env["PYTHONPATH"] = os.pathsep.join([pkg_root] + parts)
    if args.n:
        env["OMPI_TPU_MCA_mpi_base_num_ranks"] = str(args.n)
    for k, v in args.mca or []:
        env[f"OMPI_TPU_MCA_{k}"] = v
    if args.coordinator:
        env["OMPI_TPU_MCA_mpi_base_distributed"] = "1"
        env["OMPI_TPU_MCA_mpi_base_coordinator"] = args.coordinator
        if args.num_hosts:
            env["OMPI_TPU_MCA_mpi_base_num_processes"] = str(args.num_hosts)
        if args.host_id is not None:
            env["OMPI_TPU_MCA_mpi_base_process_id"] = str(args.host_id)
    return env


def parse(argv):
    ap = argparse.ArgumentParser(prog="mpirun (ompi_tpu)")
    ap.add_argument("-n", "-np", type=int, default=0,
                    help="number of ranks (0 = all local devices)")
    ap.add_argument("--mca", nargs=2, action="append",
                    metavar=("VAR", "VALUE"),
                    help="set an MCA variable (e.g. coll_base_include xla)")
    ap.add_argument("--coordinator", default="",
                    help="host:port of the coordination service "
                         "(multi-host)")
    ap.add_argument("--num-hosts", type=int, default=0)
    ap.add_argument("--host-id", type=int, default=None)
    ap.add_argument("--per-rank", action="store_true",
                    help="one OS process per MPI rank "
                         "(rank() == process_index)")
    ap.add_argument("--timeout", type=float, default=0,
                    help="per-rank mode: kill the job after this many "
                         "seconds (0 = no limit)")
    ap.add_argument("program", nargs=argparse.REMAINDER,
                    help="program and its args")
    return ap.parse_args(argv)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_per_rank(args, prog) -> int:
    """Spawn N rank processes (the PRRTE daemon's fork/exec role) and
    reap them; first nonzero exit aborts the job, as mpirun does.

    Every rank runs on the host platform: a chip belongs to one process
    at a time, so N ranks reaching for it would fail or hang. Ranks get
    their own chip only once a launcher hands each one a device."""
    n = args.n or 2
    coord = args.coordinator or f"127.0.0.1:{_free_port()}"
    procs = []
    for r in range(n):
        env = dict(os.environ)
        pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        parts = [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                 if p]
        if pkg_root not in parts:
            env["PYTHONPATH"] = os.pathsep.join([pkg_root] + parts)
        env["JAX_PLATFORMS"] = "cpu"
        env["OMPI_TPU_MCA_mpi_base_distributed"] = "1"
        env["OMPI_TPU_MCA_mpi_base_per_rank"] = "1"
        env["OMPI_TPU_MCA_mpi_base_coordinator"] = coord
        env["OMPI_TPU_MCA_mpi_base_num_processes"] = str(n)
        env["OMPI_TPU_MCA_mpi_base_process_id"] = str(r)
        for k, v in args.mca or []:
            env[f"OMPI_TPU_MCA_{k}"] = v
        procs.append(subprocess.Popen(prog, env=env))
    rc = 0
    try:
        for p in procs:
            prc = p.wait(timeout=args.timeout or None)
            rc = rc or prc
    except subprocess.TimeoutExpired:
        rc = 124
    finally:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()
        _sweep_shm(coord)
    return rc


def _sweep_shm(coord: str) -> None:
    """Remove shared-memory files this job's ranks leaked (a killed
    rank never reaches its unlink) — the PRRTE session-cleanup role
    for the btl/sm ring files AND the btl/shmseg zero-copy segment
    pools. Tags, prefixes, and directory come from the btl modules
    themselves so the sweep can never diverge from the naming.

    Run as a script, mpirun's own process does NOT have the package
    on sys.path (script dir is tools/, and python never adds the cwd
    for scripts) — only the ranks get the PYTHONPATH injection. Put
    the package root on the path here, or the guarded import below
    silently no-ops the sweep and every crashed job leaks its files."""
    import glob
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if pkg_root not in sys.path:
        sys.path.insert(0, pkg_root)
    try:
        from ompi_tpu.btl.sm import _SHM_DIR, tag_for
    except Exception:                    # noqa: BLE001 — broken env:
        return                           # nothing we can safely sweep
    try:
        from ompi_tpu.btl.shmseg import SEG_PREFIX
    except Exception:                    # noqa: BLE001
        SEG_PREFIX = "otpuseg"
    try:
        from ompi_tpu.osc.shm import WIN_PREFIX
    except Exception:                    # noqa: BLE001
        WIN_PREFIX = "otpuwin"
    tag = tag_for(coord)
    for prefix in ("otpusm", SEG_PREFIX, WIN_PREFIX):
        for path in glob.glob(os.path.join(_SHM_DIR,
                                           f"{prefix}_{tag}_*")):
            try:
                os.unlink(path)
            except OSError:
                pass


def main(argv=None) -> None:
    args = parse(argv if argv is not None else sys.argv[1:])
    if not args.program:
        sys.stderr.write("mpirun: no program given\n")
        raise SystemExit(2)
    prog = args.program
    if prog[0].endswith(".py"):
        prog = [sys.executable] + prog
    if args.per_rank:
        raise SystemExit(run_per_rank(args, prog))
    env = build_env(args, os.environ)
    os.execvpe(prog[0], prog, env)      # exec shim, like mpirun->prterun


if __name__ == "__main__":
    main()
