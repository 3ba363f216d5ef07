"""The control and the planted faults, in the program's place under the
harness's window and check.

The control is the reference computed one precision down (float32 ->
bfloat16, bfloat16 -> float8_e4m3fn, int32 -> int16) and put where the
window's call was; each fault breaks the call's output one way. Every
one has to come out ``correct: false``.

    python3 benchmark/tests/controls.py --workload <cell> --seeds 1 2 3

runs the control and every fault of the cell at the cell's own sizes
(on the chip) and prints, per seed, the numbers compared; the CPU
tests run the same at small sizes.
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

LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn",
         "int32": "int16"}

_NP_NAMES = {"sum": "add", "prod": "multiply", "max": "maximum",
             "min": "minimum", "band": "bitwise_and", "bor": "bitwise_or",
             "bxor": "bitwise_xor"}


def _jnp_op(name):
    import jax.numpy as jnp
    return getattr(jnp, _NP_NAMES[name])


def _allreduce(comm, body):
    """A jitted replacement for comm.allreduce(x, op): ``body(x, op)``
    on the stacked buffer, the result in the communicator's layout."""
    import jax
    cache = {}

    def fn(x, op):
        key = (x.shape, x.dtype, op.name)
        if key not in cache:
            cache[key] = jax.jit(lambda a: body(a, op.name),
                                 out_shardings=comm.sharding)
        return cache[key](x)
    return fn


def _reduce_local(body):
    import jax
    cache = {}

    def fn(a, b, op):
        key = (a.shape, a.dtype, op.name)
        if key not in cache:
            cache[key] = jax.jit(lambda x, y: body(x, y, op.name))
        return cache[key](a, b)
    return fn


def _reduce_local_low():
    """The control for reduce_local: cast down, combine and cast back
    as three programs, so that the operands and the result are stored
    in the lower type. In one program XLA may keep the excess
    precision of the cast (it did on the chip, PR 22), and the control
    would compute in the upper type after all."""
    import jax
    down = jax.jit(lambda x, t: x.astype(t), static_argnums=1)
    comb = {n: jax.jit(_jnp_op(n)) for n in _NP_NAMES}

    def fn(a, b, op):
        low = LOWER[a.dtype.name]
        return down(comb[op.name](down(a, low), down(b, low)), a.dtype.name)
    return fn


def allreduce_entries():
    """{name: body(x, op)} for the allreduce call."""
    import jax.numpy as jnp

    def reduce(x, op):
        return {"sum": jnp.sum, "max": jnp.max, "min": jnp.min,
                "prod": jnp.prod}[op](x, axis=0, keepdims=True)

    def control(x, op):
        low = x.astype(LOWER[x.dtype.name])
        r = reduce(low, op).astype(low.dtype)
        return jnp.broadcast_to(r, x.shape).astype(x.dtype)

    def unchanged(x, op):
        return x

    def half_batch(x, op):
        h = x.shape[0] // 2
        r = reduce(x[:max(h, 1)], op) * (2 if op == "sum" else 1)
        return jnp.broadcast_to(r, x.shape).astype(x.dtype)

    def no_exchange(x, op):
        return x * x.shape[0] if op == "sum" else x

    def altered(x, op):
        r = jnp.broadcast_to(reduce(x, op), x.shape)
        return r.at[0, 0].add(1).astype(x.dtype)
    return {"control": control, "unchanged": unchanged,
            "half_batch": half_batch, "no_exchange": no_exchange,
            "altered": altered}


def reduce_local_entries():
    """{name: body(a, b, op)} for the reduce_local call; the control
    is ``_reduce_local_low``."""
    def unchanged(a, b, op):
        return b

    def half_batch(a, b, op):
        h = a.shape[0] // 2
        return b.at[:h].set(_jnp_op(op)(a[:h], b[:h]))

    def altered(a, b, op):
        return _jnp_op(op)(a, b).at[0].add(1)
    return {"unchanged": unchanged, "half_batch": half_batch,
            "altered": altered}


def replace(cell, MPI, name: str) -> None:
    """Put entry ``name`` where the window's call was."""
    if cell.traffic["call"] == "allreduce":
        body = allreduce_entries()[name]
        cell.call.function = lambda MPI, comm: _allreduce(comm, body)
    elif name == "control":
        cell.call.function = lambda MPI, target: _reduce_local_low()
    else:
        body = reduce_local_entries()[name]
        cell.call.function = lambda MPI, target: _reduce_local(body)


def names(cell):
    if cell.traffic["call"] == "allreduce":
        return list(allreduce_entries())
    return ["control"] + list(reduce_local_entries())


def run(cell_name: str, name: str, seed: int, seconds: float, MPI,
        shrink=None, log=lambda s: None) -> dict:
    """One run of the cell with ``name`` in the call's place."""
    from benchmark import harness, spec
    cell = spec.cell(cell_name)
    if shrink:
        shrink(cell)
    replace(cell, MPI, name)
    return harness.run_cell(cell, MPI, seed, seconds, False,
                            time.perf_counter(), log)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--only", nargs="*", default=None)
    args = ap.parse_args(argv)
    from benchmark import run as bench_run, spec
    bench_run.configure_jax()
    import ompi_tpu as MPI
    bad = 0
    for name in args.only or names(spec.cell(args.workload)):
        for seed in args.seeds:
            r = run(args.workload, name, seed, args.seconds, MPI)
            print(json.dumps({"entry": name, "seed": seed,
                              "correct": r["correct"],
                              "checks": r["checks"]}), flush=True)
            bad += r["correct"]
    return int(bad > 0)


if __name__ == "__main__":
    sys.exit(main())
