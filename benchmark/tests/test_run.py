"""Both cells end to end on the CPU at small sizes: the harness runs,
its check passes the program and fails the control and every planted
fault, and a run off the TPU prints no result."""
import json
import os
import subprocess
import sys
import time

import pytest

from benchmark import harness, spec
from benchmark.tests import controls
from benchmark.tests.conftest import shrink
from benchmark.tests.test_spec import BENCH, copy_benchmark

CELLS = ["osu_allreduce.4chip", "reduce_local.ddp_bucket.1chip"]


def run(mpi, name, seed=2 ** 31 + 12345, trace=False):
    cell = spec.cell(name)
    shrink(cell)
    return harness.run_cell(cell, mpi, seed, 0.6, trace,
                            time.perf_counter(), lambda s: None)


@pytest.mark.parametrize("name", CELLS)
def test_the_program_passes(mpi, name):
    r = run(mpi, name)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert r["checks"]["mismatched_elements"]["value"] == 0
    assert all(c["value"] >= 1 for k, c in r["checks"].items()
               if k.startswith("outputs_compared"))
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {m["name"] for m in
                                 spec.cell(name).end_to_end}
    assert r["device"]["platform"] == "cpu"


@pytest.mark.parametrize("name", CELLS)
def test_a_traced_run_reads_no_device_numbers_off_the_chip(mpi, name):
    r = run(mpi, name, trace=True)
    assert r["correct"]
    # the CPU has no TPU plane: no device metric, nothing made up
    assert r["metrics"] == {} and r["device"]["busy_s"] == 0.0


@pytest.mark.parametrize("name,entry", [
    (c, e) for c in CELLS
    for e in controls.names(spec.cell(c))])
def test_the_control_and_each_fault_fail(mpi, name, entry):
    r = controls.run(name, entry, 7, 0.6, mpi, shrink=shrink)
    assert not r["correct"]
    assert r["checks"]["mismatched_elements"]["value"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_a_run_off_the_tpu_prints_no_result(tmp_path, name):
    """The whole command on 4 virtual CPU devices, in a copy of the
    benchmark whose traffic is shrunk (data only): set-up, window and
    check run, then it exits 1 with nothing on stdout."""
    bdir = copy_benchmark(tmp_path)
    cell = spec.cell(name)
    shrink(cell)
    traffic = next(w["traffic"] for w in BENCH["workloads"]
                   if w["name"] == name)
    with open(os.path.join(bdir, "traffic", traffic + ".json"), "w") as f:
        json.dump(cell.traffic, f)
    env = dict(os.environ, PYTHONPATH=os.path.dirname(spec.HERE))
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload",
                        name, "--seed", "99", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 1 and p.stdout.strip() == "", p.stderr[-2000:]
    assert "check mismatched_elements: 0" in p.stderr
    assert p.stderr.strip().splitlines()[-1].endswith("no result")
