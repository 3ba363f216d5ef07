"""BENCHMARK.json against the contract's shape, and discovery of
every piece of a cell by name, including a cell added as files only."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmark import spec

ROOT = os.path.dirname(spec.HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmark"]
    assert BENCH["command"][1].startswith("benchmark/")
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_units_and_keys():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for entry in BENCH["configs"] + BENCH["workloads"] + metrics:
        assert NAME.match(entry["name"]), entry["name"]
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = [w["name"] for w in BENCH["workloads"]]
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        # every cell that reports a per-layer metric reports what it moves
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m.get("workloads", moved)) <= set(moved), m["name"]
    for m in metrics:
        if m["unit"] == "%" and "roofline" in m["name"]:
            assert m["name"].endswith("_roofline")


def test_four_chip_cells_at_most_half():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 2)


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_resolves(w):
    cell = spec.cell(w["name"])
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and cell.per_layer
    readers = cell.readers(cell.end_to_end + cell.per_layer)
    assert all(callable(r.read) for r in readers.values())
    for fn in ("setup", "make_entries", "function", "inputs", "output",
               "reference", "roofline_bytes", "served"):
        assert callable(getattr(cell.call, fn))


def test_unknown_cell_is_refused():
    with pytest.raises(spec.SpecError):
        spec.cell("no_such_cell")


def test_traffic_outside_the_matrix_is_refused():
    cfg = {"matrix": [["sum", "float32"]], "max_message_bytes": 64}
    ph = {"name": "x", "bytes_per_rank": 8,
          "cases": [{"op": "max", "dtype": "float32"}]}
    with pytest.raises(spec.SpecError):
        spec.check_traffic(cfg, {"phases": [ph]})
    ph.update(cases=[{"op": "sum", "dtype": "float32"}], bytes_per_rank=65)
    with pytest.raises(spec.SpecError):
        spec.check_traffic(cfg, {"phases": [ph]})


def copy_benchmark(dst):
    """The committed benchmark alone: BENCHMARK.json and benchmark/."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(spec.HERE, os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return os.path.join(dst, "benchmark")


def test_a_cell_added_as_files_only(tmp_path):
    """A new traffic mix and a new metric, each a new file, and a new
    entry in BENCHMARK.json: the runner finds them by name."""
    bdir = copy_benchmark(tmp_path)
    with open(os.path.join(bdir, "traffic", "osu_allreduce.json")) as f:
        traffic = json.load(f)
    traffic["phases"] = traffic["phases"][:1]
    with open(os.path.join(bdir, "traffic", "osu_latency_only.json"),
              "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(bdir, "metrics", "calls_per_s.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx.win.calls\n")
    bench = json.load(open(tmp_path / "BENCHMARK.json"))
    bench["workloads"].append({
        "name": "osu_latency.4chip", "config": "osu_coll.v5e-2x2",
        "traffic": "osu_latency_only", "chips": 4, "why": "test"})
    bench["per_layer"].append({
        "name": "calls_per_s", "unit": "1/s", "better": "higher",
        "source": "host_clock", "layer": "device", "moves": "lat_avg_us",
        "workloads": ["osu_latency.4chip"]})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("lat_avg_us", "idle_share.lat"):
            m["workloads"].append("osu_latency.4chip")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.cell("osu_latency.4chip", bench_dir=bdir)
    assert [p["name"] for p in cell.traffic["phases"]] == ["lat"]
    assert {m["name"] for m in cell.per_layer} == {"idle_share.lat",
                                                   "calls_per_s"}
    assert {m["name"] for m in cell.end_to_end} == {"setup_s",
                                                    "lat_avg_us"}
    assert cell.readers(cell.per_layer, bdir)["calls_per_s"].read(
        type("C", (), {"win": type("W", (), {"calls": 7})})) == 7


def test_the_benchmark_alone_gives_no_result(tmp_path):
    """A directory with only BENCHMARK.json and benchmark/ (no
    program) exits non-zero and prints no result."""
    copy_benchmark(tmp_path)
    env = dict(os.environ, PYTHONPATH="")
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload",
                        "reduce_local.ddp_bucket.1chip", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
