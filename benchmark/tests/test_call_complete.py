"""Calls whose result is not a ``jax.Array``: a host-buffer allreduce,
which returns a numpy array, and a nonblocking one, which returns a
request. Each is a cell added as new files only, in a copy of the
benchmark, and runs through the harness on the 4 virtual CPU devices."""
import json
import os
import time

from benchmark import harness, spec
from benchmark.tests.conftest import shrink
from benchmark.tests.test_spec import copy_benchmark

HOST_CALL = '''"""MPI_Allreduce on numpy host buffers, one row per rank."""
import numpy as np

from benchmark.calls import allreduce
from benchmark.calls.allreduce import (  # noqa: F401
    CheckError, reference, roofline_bytes, served, setup)


def make_entries(MPI, comm, phase, seed, salt):
    return [(np.asarray(x), op) for x, op in
            allreduce.make_entries(MPI, comm, phase, seed, salt)]


def function(MPI, comm):
    return comm.allreduce


def inputs(args):
    return args[0]


def complete(y):
    return y


def output(comm, y):
    if not isinstance(y, np.ndarray):
        raise CheckError(f"a host buffer's result is a {type(y)}")
    return y
'''

REQUEST_CALL = '''"""MPI_Iallreduce on device buffers, completed by MPI_Wait."""
from benchmark.calls.allreduce import (  # noqa: F401
    inputs, make_entries, output, reference, roofline_bytes, served,
    setup)


def function(MPI, comm):
    return comm.iallreduce


def complete(req):
    return req.get()
'''


def add_cell(tmp_path, name, call, source):
    """A copy of the benchmark with one more cell, made of a new call
    file, a new traffic file and a new BENCHMARK.json entry: the
    allreduce traffic, driving ``call``, reporting the allreduce cell's
    end-to-end metrics."""
    bdir = copy_benchmark(tmp_path)
    with open(os.path.join(bdir, "calls", call + ".py"), "w") as f:
        f.write(source)
    with open(os.path.join(bdir, "traffic", "osu_allreduce.json")) as f:
        traffic = json.load(f)
    traffic["call"] = call
    with open(os.path.join(bdir, "traffic", call + ".json"), "w") as f:
        json.dump(traffic, f)
    bench = json.load(open(tmp_path / "BENCHMARK.json"))
    bench["workloads"].append({
        "name": name, "config": "osu_coll.v5e-2x2", "traffic": call,
        "chips": 4, "why": "test"})
    for m in bench["end_to_end"]:
        if "osu_allreduce.4chip" in m.get("workloads", []):
            m["workloads"].append(name)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.cell(name, bench_dir=bdir)
    shrink(cell)
    return cell


def run(mpi, cell, seed=2 ** 31 + 3030):
    return harness.run_cell(cell, mpi, seed, 0.6, False,
                            time.perf_counter(), lambda s: None)


def assert_correct(r, cell):
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["checks"]["mismatched_elements"]["value"] == 0
    assert set(r["metrics"]) == {"setup_s", "lat_avg_us", "algbw_gbps"}
    assert {m["name"] for m in cell.end_to_end} == set(r["metrics"])


def test_a_host_buffer_call_runs_correct(tmp_path, mpi):
    cell = add_cell(tmp_path, "osu_allreduce_host.4chip",
                    "allreduce_host", HOST_CALL)
    assert_correct(run(mpi, cell), cell)


def test_a_host_buffer_call_returning_its_input_fails(tmp_path, mpi):
    cell = add_cell(tmp_path, "osu_allreduce_host.4chip",
                    "allreduce_host", HOST_CALL)
    cell.call.function = lambda MPI, comm: (lambda x, op: x)
    r = run(mpi, cell)
    assert not r["correct"]
    assert r["checks"]["mismatched_elements"]["value"] > 0


def test_a_request_returning_call_runs_correct(tmp_path, mpi):
    cell = add_cell(tmp_path, "osu_iallreduce.4chip", "iallreduce",
                    REQUEST_CALL)
    assert_correct(run(mpi, cell), cell)
