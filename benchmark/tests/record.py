"""Record a traced window of a cell a few calls long, as the files in
``data/`` are: the cell's own set-up, warm-up and check, in blocks of
``--block-seconds``; the window's ``.xplane.pb`` is kept at ``--out``
and the traced run's metrics go to stdout.

    python3 benchmark/tests/record.py --workload osu_allreduce.4chip \\
        --seconds 0.04 --block-seconds 0.02 \\
        --out benchmark/tests/data/osu_allreduce.4chip.libspans.xplane.pb
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--block-seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    from benchmark import run
    run.configure_jax()
    from benchmark import harness, spec, tracereduce
    cell = spec.cell(args.workload)
    cell.traffic["block_seconds"] = args.block_seconds
    load = tracereduce.load

    def keep(path):
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        shutil.copyfile(path, args.out)
        return load(path)
    tracereduce.load = keep
    import ompi_tpu as MPI
    result = harness.run_cell(cell, MPI, args.seed, args.seconds, True,
                              T_START, run.log)
    print(json.dumps({k: result[k] for k in ("metrics", "device",
                                              "breakdown")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
