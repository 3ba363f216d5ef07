"""The control and the planted faults of the collective mix
(``calls/coll_mix.py``), in the program's place under the harness's
window and check. Each has to come out ``correct: false``.

- ``control``: every collective through the library on its payload
  stored in bfloat16 between programs (cast down, the collective, cast
  back), one precision below the configuration's float32;
- ``alltoall_unchanged``: an alltoall that returns its input;
- ``bcast_root1``: a bcast from root 1 instead of 0;
- ``allgather_swapped``: an allgather with rows 0 and 1 swapped;
- ``rsb_next_block``: a reduce_scatter_block that gives each rank the
  next rank's block;
- ``altered``: one element of the bcast's result altered.

The other collectives of a fault run as the program runs them.

    python3 benchmark/tests/mix_controls.py --seeds 1 2 3 [--seconds 2]

runs each at the cell's own sizes (on the chip) and prints, per seed,
the numbers compared; ``--own-phases`` keeps only the phases a fault
breaks (every phase for the control). The CPU tests run the same at
small sizes.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL = "osu_coll_mix.4chip"
ALL = ("allgather", "alltoall", "bcast", "reduce_scatter_block")
# entry -> the phases whose results it breaks
BREAKS = {"control": ALL, "alltoall_unchanged": ("alltoall",),
          "bcast_root1": ("bcast",), "allgather_swapped": ("allgather",),
          "rsb_next_block": ("reduce_scatter_block",),
          "altered": ("bcast",)}


def _jit(comm, body):
    import jax
    return jax.jit(body, out_shardings=comm.sharding)


def entry(name: str, MPI, comm, program):
    """The window's function ``(x, op, coll)`` with entry ``name`` in
    the place of ``program``, the mix's own function."""
    import jax.numpy as jnp
    if name == "control":
        down = _jit(comm, lambda x: x.astype(jnp.bfloat16))
        up = _jit(comm, lambda y: y.astype(jnp.float32))
        return lambda x, op, coll: up(program(down(x), op, coll))
    if name == "alltoall_unchanged":
        wrong = {"alltoall": lambda x, op: x}
    elif name == "bcast_root1":
        wrong = {"bcast": lambda x, op: comm.bcast(x, 1)}
    elif name == "allgather_swapped":
        perm = jnp.array([1, 0] + list(range(2, comm.size)))
        swap = _jit(comm, lambda y: y[:, perm])
        wrong = {"allgather": lambda x, op: swap(comm.allgather(x))}
    elif name == "rsb_next_block":
        roll = _jit(comm, lambda y: jnp.roll(y, -1, axis=0))
        wrong = {"reduce_scatter_block":
                 lambda x, op: roll(comm.reduce_scatter_block(x, op))}
    else:
        bump = _jit(comm, lambda y: y.at[(0,) * y.ndim].add(1))
        wrong = {"bcast": lambda x, op: bump(program(x, op, "bcast"))}

    def fn(x, op, coll):
        if coll in wrong:
            return wrong[coll](x, op)
        return program(x, op, coll)
    return fn


def run(name: str, seed: int, seconds: float, MPI, shrink=None,
        own_phases: bool = False, log=lambda s: None) -> dict:
    """One run of the mix with entry ``name`` in the call's place."""
    from benchmark import harness, spec
    cell = spec.cell(CELL)
    if shrink:
        shrink(cell)
    if own_phases:
        cell.traffic["phases"] = [p for p in cell.traffic["phases"]
                                  if p["name"] in BREAKS[name]]
    call = cell.call
    program = call.function
    call.function = lambda MPI, comm: entry(name, MPI, comm,
                                            program(MPI, comm))
    return harness.run_cell(cell, MPI, seed, seconds, False,
                            time.perf_counter(), log)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--own-phases", action="store_true")
    args = ap.parse_args(argv)
    from benchmark import run as bench_run
    bench_run.configure_jax()
    import ompi_tpu as MPI
    bad = 0
    for name in args.only or list(BREAKS):
        for seed in args.seeds:
            t = time.perf_counter()
            r = run(name, seed, args.seconds, MPI,
                    own_phases=args.own_phases, log=bench_run.log)
            print(json.dumps({"entry": name, "seed": seed,
                              "correct": r["correct"],
                              "seconds": round(time.perf_counter() - t, 3),
                              "checks": r["checks"]}), flush=True)
            bad += r["correct"]
    return int(bad > 0)


if __name__ == "__main__":
    sys.exit(main())
