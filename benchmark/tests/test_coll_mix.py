"""The collective mix and the bf16 gradient-bucket cell on the CPU at
small sizes: both run end to end and pass the program; the mix's
control and planted faults (``mix_controls.py``) and the bf16 cell's
(``controls.py``) fail; the mix's per-phase readers against
hand-computed values."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmark import harness, phasespans, spec
from benchmark.tests import controls, mix_controls
from benchmark.tests.conftest import shrink
from benchmark.tests.test_libspans import host_trace
from benchmark.tests.test_measures import make_ctx, trace_of
from benchmark.tests.test_spec import BENCH, copy_benchmark

MIX, BF16 = "osu_coll_mix.4chip", "reduce_local.bf16_grad.1chip"
MiB = 1 << 20
COLLS = ("allgather", "alltoall", "bcast", "reduce_scatter_block")


def run(mpi, name, seed=2 ** 31 + 4242, trace=False):
    cell = spec.cell(name)
    shrink(cell)
    return harness.run_cell(cell, mpi, seed, 0.6, trace,
                            time.perf_counter(), lambda s: None)


@pytest.mark.parametrize("name", [MIX, BF16])
def test_the_program_passes(mpi, name):
    r = run(mpi, name)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert r["checks"]["mismatched_elements"]["value"] == 0
    compared = {k: c["value"] for k, c in r["checks"].items()
                if k.startswith("outputs_compared.")}
    phases = [p["name"] for p in spec.cell(name).traffic["phases"]]
    assert set(compared) == {f"outputs_compared.{p}" for p in phases}
    assert all(v >= 1 for v in compared.values())
    assert set(r["metrics"]) == {m["name"] for m in
                                 spec.cell(name).end_to_end}


@pytest.mark.parametrize("name", [MIX, BF16])
def test_a_run_off_the_tpu_prints_no_result(tmp_path, name):
    """The whole command on 4 virtual CPU devices, in a copy of the
    benchmark whose traffic is shrunk: it exits 1 with nothing on
    stdout after a clean check."""
    bdir = copy_benchmark(tmp_path)
    cell = spec.cell(name)
    shrink(cell)
    traffic = next(w["traffic"] for w in BENCH["workloads"]
                   if w["name"] == name)
    with open(os.path.join(bdir, "traffic", traffic + ".json"), "w") as f:
        json.dump(cell.traffic, f)
    env = dict(os.environ, PYTHONPATH=os.path.dirname(spec.HERE))
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload",
                        name, "--seed", "2147483999", "--seconds", "2",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 1 and p.stdout.strip() == "", p.stderr[-2000:]
    assert "check mismatched_elements: 0" in p.stderr
    assert p.stderr.strip().splitlines()[-1].endswith("no result")


def test_a_traced_mix_reads_no_device_numbers_off_the_chip(mpi):
    r = run(mpi, MIX, trace=True)
    assert r["correct"]
    assert r["metrics"] == {} and r["device"]["busy_s"] == 0.0


def test_the_mix_serves_each_collective(mpi):
    run(mpi, MIX)
    cell = spec.cell(MIX)
    line = cell.call.served(mpi, cell.call.setup(mpi, cell.config))
    for coll in COLLS:
        assert f"{coll} by " in line


@pytest.mark.parametrize("entry", list(mix_controls.BREAKS))
def test_the_mix_control_and_each_fault_fail(mpi, entry):
    r = mix_controls.run(entry, 7, 0.6, mpi, shrink=shrink)
    assert not r["correct"]
    assert r["checks"]["mismatched_elements"]["value"] > 0


def test_a_fault_of_its_own_phases_only(mpi):
    r = mix_controls.run("bcast_root1", 8, 0.6, mpi, shrink=shrink,
                         own_phases=True)
    assert not r["correct"]
    assert [k for k in r["checks"] if k.startswith("outputs_compared.")] \
        == ["outputs_compared.bcast"]


@pytest.mark.parametrize("entry", controls.names(spec.cell(BF16)))
def test_the_bf16_control_and_each_fault_fail(mpi, entry):
    r = controls.run(BF16, entry, 7, 0.6, mpi, shrink=shrink)
    assert not r["correct"]
    assert r["checks"]["mismatched_elements"]["value"] > 0


@pytest.mark.parametrize("coll,least", [
    ("allgather", 3 * 256 * MiB), ("alltoall", 192 * MiB),
    ("bcast", 256 * MiB), ("reduce_scatter_block", 192 * MiB)])
def test_roofline_bytes_at_four_ranks(coll, least):
    call = spec.cell(MIX).call
    phase = {"name": coll, "bytes_per_rank": 256 * MiB}
    assert call.roofline_bytes(phase, 4) == (least, "ici_bytes_per_s")


def test_reference_layouts():
    call = spec.cell(MIX).call
    x2 = np.arange(8.0).reshape(4, 2)
    x3 = np.arange(32.0).reshape(4, 4, 2)
    case = {"op": "sum"}
    ag = call.reference((x2, "allgather"), case)
    assert ag.shape == (4, 4, 2) and (ag[3] == x2).all()
    a2a = call.reference((x3, "alltoall"), case)
    assert (a2a[1, 2] == x3[2, 1]).all()
    bc = call.reference((x2, "bcast"), case)
    assert (bc == x2[0]).all()
    rs = call.reference((x3, "reduce_scatter_block"), case)
    assert rs.shape == (4, 2) and (rs[1] == x3[:, 1].sum(0)).all()


def test_per_phase_roofline_by_hand():
    # block 0 allgather (20 calls, 0.1 s busy), block 1 alltoall (50
    # calls, 0.05 s busy), both 0.5 s long
    ns = 1e9
    trace = trace_of([(0.1 * ns, 0.2 * ns), (0.6 * ns, 0.65 * ns)])
    ctx = make_ctx(MIX, [(0, 0.0, 0.5, 20), (1, 0.5, 1.0, 50)], trace,
                   [(0.0, 0.5 * ns), (0.5 * ns, 1.0 * ns)])
    ag = 20 * 3 * 256 * MiB / 200e9               # 0.0805306368 s
    a2a = 50 * 0.75 * 256 * MiB / 200e9           # 0.0503316480 s
    assert phasespans.roofline(ctx, "coll_mix", "allgather") \
        == pytest.approx(100 * ag / 0.1)
    assert phasespans.roofline(ctx, "coll_mix", "alltoall") \
        == pytest.approx(100 * a2a / 0.05)
    # no traced block of the phase, or another cell's call: nothing
    assert phasespans.roofline(ctx, "coll_mix", "bcast") is None
    assert phasespans.roofline(ctx, "allreduce", "allgather") is None
    ctx.trace.chips = []
    assert phasespans.roofline(ctx, "coll_mix", "allgather") is None


def test_per_phase_split_by_hand():
    # an allgather call and a bcast call, 1000 us each, each with its
    # own outer and launch spans; a stray launch span of another
    # collective in the bcast call is not the bcast's
    events = [("comm.allgather", 10, 610),
              ("coll.xla.launch:allgather/direct", 60, 560),
              ("comm.bcast", 2050, 2700),
              ("coll.xla.launch:bcast/scatter_allgather", 2100, 2400),
              ("coll.xla.launch:alltoall/direct", 2450, 2500)]
    t = host_trace(events, {"bench.call:allgather": ([0], [1000]),
                            "bench.call:bcast": ([2000], [3000])})
    ctx = make_ctx(MIX, [(0, 0.0, 1.0, 1), (2, 1.0, 2.0, 1)], t,
                   [(0, 1), (1, 2)])
    p = phasespans.split(ctx, "coll_mix")
    assert p["lib"].tolist() == [100e3, 350e3]
    assert p["launch"].tolist() == [500e3, 300e3]
    assert p["wait"].tolist() == [390e3, 300e3]
    total = sum(p[k] for k in ("before", "lib", "launch", "wait"))
    assert total.tolist() == p["call"].tolist()
    readers = spec.cell(MIX).readers([{"name": "lib_host_us.mix"},
                                      {"name": "launch_us.mix"}])
    assert readers["lib_host_us.mix"].read(ctx) == pytest.approx(225.0)
    assert readers["launch_us.mix"].read(ctx) == pytest.approx(400.0)
    # a program that writes no spans, as the parent's: nothing to read
    bare = host_trace([], {"bench.call:allgather": ([0], [1000])})
    ctx.trace = bare
    assert readers["lib_host_us.mix"].read(ctx) is None
    assert phasespans.split(ctx, "allreduce") is None
