"""Run one cell of ompi_tpu's chip benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The run loads the cell, makes its inputs on the device from the seed,
warms up every shape the cell uses (set-up, timed as ``setup_s``),
drives the MPI call for ``--seconds`` in a closed loop, compares a
sample of the outputs with a plain host reference, and prints one JSON
line last on stdout: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, ``breakdown`` (``--trace 1``) and ``checks``,
each number compared beside its limit. ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer ones, read from
a profiler trace of the window. Earlier lines on stderr name the path
that served each phase, the compiles inside the window and the set-up
split. A run that finds no TPU exits 1 and prints no result; so does a
run on fewer devices than the cell asks for.

Everything of a cell is data, found by name (``spec.py``):

- A cell: an entry of ``workloads`` in ``BENCHMARK.json`` naming a
  config, a traffic mix and its chips.
- A config: ``configs/<config>.json``, the deployment: ``ranks``, the
  (op, type) ``matrix`` it runs and ``max_message_bytes``.
- A traffic mix: ``traffic/<traffic>.json``, the MPI ``call`` and its
  ``phases``, taken in turn in blocks of ``block_seconds``. A phase has
  a ``role`` (``lat`` or ``bw``: the metrics that read it), the
  ``bytes_per_rank``, a ``pool`` of distinct inputs per case, the
  ``cases`` (op, dtype, ``amax`` of the integer data, null for random
  bits) that its calls cycle through, ``warmup_calls``, and the sample
  of outputs compared (``sample_per_block`` calls among the first
  ``sample_within`` of each block; of ``sample_blocks`` blocks only,
  where set).
- A call: ``calls/<call>.py``, with ``setup``, ``make_entries``,
  ``function`` (the entry the window drives), ``inputs``, ``output``,
  ``reference``, ``roofline_bytes`` and ``served``, and optionally
  ``complete(y)``, which waits for what ``function`` returned and
  returns the finished result that ``output`` reads: without it,
  ``y.block_until_ready()`` (a ``jax.Array``); for a request,
  ``Request.get``; for a numpy result, the result itself. A call that
  reuses its output buffer, as a persistent request its recvbuf,
  returns a copy or a fresh array, since a kept output must outlive
  the calls after it. Allgather, alltoall, bcast, or a host-buffer,
  nonblocking or persistent allreduce is a new call file.
- A metric: ``metrics/<metric>.py`` with ``read(ctx)``, returning the
  number or None where the run has nothing to read, and an entry in
  ``end_to_end`` or ``per_layer`` of ``BENCHMARK.json``.

A new cell is new files and a ``BENCHMARK.json`` entry; no file that is
there changes.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def configure_jax() -> str:
    """The persistent compile cache at a fixed path in the checkout, of
    the benchmark's own, keeping every program however small or fast to
    compile, with no size cap (a cap from the environment turns on
    eviction, whose access-time files failed to write on the chip's
    host and left every run compiling); libtpu writes no logs."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    cache = os.path.join(ROOT, ".bench_jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cache = configure_jax()
    import jax
    from benchmark import harness, spec
    cell = spec.cell(args.workload)
    t = time.perf_counter()
    devs = jax.devices()
    log(f"backend: {len(devs)} {devs[0].platform} devices, up in "
        f"{time.perf_counter() - t:.3f} s; compile cache {cache}")
    if len(devs) < cell.chips:
        log(f"{args.workload} needs {cell.chips} devices; JAX finds "
            f"{len(devs)} ({devs[0].platform}): no result")
        return 1
    import ompi_tpu as MPI
    result = harness.run_cell(cell, MPI, args.seed, args.seconds,
                              bool(args.trace), T_START, log)
    if result["device"]["platform"] != "tpu":
        harness.log_checks(result, log)
        log(f"platform {result['device']['platform']}, not tpu: no result")
        return 1
    print(json.dumps(result), flush=True)
    harness.log_checks(result, log)
    return 0


if __name__ == "__main__":
    sys.exit(main())
