"""Find a cell's pieces by name.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``. Everything
else is a file found from a name in that entry or in the metric lists:

    configs/<config>.json    the deployment (ranks, types, ops, sizes)
    traffic/<traffic>.json   the MPI call, its phases, sizes and cases
    calls/<call>.py          how to drive that call and its reference
                             (the functions ``run.py`` lists; an optional
                             ``complete(y)`` finishes a call's result)
    metrics/<metric>.py      ``read(ctx)`` -> a number, or None
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from types import ModuleType
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))


class SpecError(ValueError):
    """A cell, file or entry that the benchmark cannot use."""


def _json(path: str):
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def load_module(path: str, name: str) -> ModuleType:
    """Import a file of the benchmark by its path (metric names hold
    dots, so they are not importable by module name)."""
    if not os.path.isfile(path):
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    call: ModuleType
    end_to_end: List[dict]     # the BENCHMARK.json entries it reports
    per_layer: List[dict]

    def readers(self, entries: List[dict], bench_dir: str = HERE
                ) -> Dict[str, ModuleType]:
        return {m["name"]: load_module(
            os.path.join(bench_dir, "metrics", m["name"] + ".py"),
            "benchmark_metric_" + m["name"].replace(".", "_"))
            for m in entries}


def _reports(entry: dict, cell: str, e2e_names) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    return e2e_names is None or entry["moves"] in e2e_names


def cell(name: str, bench_dir: str = HERE) -> Cell:
    """The cell ``name`` of the BENCHMARK.json beside ``bench_dir``."""
    bench = _json(os.path.join(os.path.dirname(bench_dir),
                               "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")
    config = _json(os.path.join(bench_dir, "configs",
                                entry["config"] + ".json"))
    traffic = _json(os.path.join(bench_dir, "traffic",
                                 entry["traffic"] + ".json"))
    call = load_module(os.path.join(bench_dir, "calls",
                                    traffic["call"] + ".py"),
                       "benchmark_call_" + traffic["call"])
    check_traffic(config, traffic)
    e2e = [m for m in bench["end_to_end"] if _reports(m, name, None)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(name, int(entry["chips"]), config, traffic, call, e2e,
                layer)


def check_traffic(config: dict, traffic: dict) -> None:
    """Each case of the traffic is a pair the deployment runs, at a
    size it allows."""
    pairs = {tuple(p) for p in config["matrix"]}
    for ph in traffic["phases"]:
        if ph["bytes_per_rank"] > config["max_message_bytes"]:
            raise SpecError(f"phase {ph['name']}: {ph['bytes_per_rank']} "
                            "B per rank is over the config's maximum")
        for case in ph["cases"]:
            if (case["op"], case["dtype"]) not in pairs:
                raise SpecError(f"phase {ph['name']}: ({case['op']}, "
                                f"{case['dtype']}) is not in the matrix")
