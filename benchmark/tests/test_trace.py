"""The trace reducer on traces recorded on the chip (``data/``): a
traced window of each cell, a few calls long, written by the window's
own annotations (blocks: 19 calls of 8 B then 2 of 256 MiB on four
chips; 5 calls of reduce_local on 25 MiB operands on one chip)."""
import os

import numpy as np
import pytest

from benchmark import measures, tracereduce
from benchmark.tests.test_measures import make_ctx

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MiB = 1 << 20


@pytest.fixture(scope="module")
def four():
    return tracereduce.load(os.path.join(DATA,
                                         "osu_allreduce.4chip.xplane.pb"))


def test_planes_spans_and_kinds(four):
    assert len(four.chips) == 4
    assert {k: len(v[0]) for k, v in four.spans.items()} == {
        "bench.block:lat": 1, "bench.call:lat": 19,
        "bench.block:bw": 1, "bench.call:bw": 2}
    kinds = {tracereduce.op_kind(n) for n, _ in four.ops}
    assert kinds == {"all-reduce", "all-gather", "reduce", "reshape",
                     "copy", "fusion", "broadcast"}
    top = tracereduce.device_ops(four)
    assert top[0][0].endswith(" all-reduce") and len(top) <= 10


def test_busy_union_inside_the_blocks(four):
    s, e = four.span("bench.block:bw")
    per_chip = [c.within(s, e)[0] for c in four.chips]
    # rabenseifner at 256 MiB keeps every chip busy ~90 % of the block
    assert all(0.85 * (e - s)[0] < b < (e - s)[0] for b in per_chip)
    assert four.busy(s, e)[0] == pytest.approx(np.mean(per_chip))
    s, e = four.span("bench.block:lat")
    assert four.busy(s, e)[0] < 0.01 * (e - s)[0]


def test_per_layer_metrics_from_the_trace(four):
    (lat_s, lat_e), (bw_s, bw_e) = [
        (float(s[0]), float(e[0])) for s, e in
        (four.span(f"bench.block:{n}") for n in ("lat", "bw"))]
    ctx = make_ctx("osu_allreduce.4chip",
                   [(0, 0.0, 0.02, 19), (1, 0.02, 0.04, 2)], four,
                   [(lat_s, lat_e), (bw_s, bw_e)])
    busy = four.busy([bw_s], [bw_e])[0]
    least = 2 * 1.5 * 256 * MiB / 200e9 * 1e9
    roof = measures.roofline(ctx, "allreduce", "bw")
    assert roof == pytest.approx(100 * least / busy) and 0 < roof < 100
    idle = measures.idle_share(ctx, "bw")
    assert idle == pytest.approx(100 * (1 - busy / (bw_e - bw_s)))
    assert measures.idle_share(ctx, "lat") > 99
    s, e = four.span("bench.call:lat")
    assert measures.host_residue_us(ctx, "lat") == pytest.approx(
        np.median((e - s) - four.busy(s, e)) / 1e3)
    gaps = tracereduce.idle_gaps(four, lat_s, bw_e)
    assert gaps and all(sec > 0 for _, sec in gaps)


def test_one_chip_reduce_local():
    one = tracereduce.load(os.path.join(
        DATA, "reduce_local.ddp_bucket.1chip.xplane.pb"))
    assert len(one.chips) == 1 and len(one.span("bench.call:bw")[0]) == 5
    assert {tracereduce.op_kind(n) for n, _ in one.ops} == {
        "add", "maximum", "minimum", "multiply"}
    s, e = (float(x[0]) for x in one.span("bench.block:bw"))
    busy = one.busy([s], [e])[0]
    # five elementwise ops of ~117 us each, nothing else on the device;
    # the device clock runs a little ahead of the host's, so a few us of
    # the first op fall before the block's annotation
    total = sum(ns for _, ns in one.ops)
    assert 0.98 * total < busy <= total
    ctx = make_ctx("reduce_local.ddp_bucket.1chip", [(0, 0.0, 0.004, 5)],
                   one, [(s, e)], size=1)
    least = 5 * 3 * 25 * MiB / 819e9 * 1e9
    assert measures.roofline(ctx, "reduce_local", "bw") == pytest.approx(
        100 * least / busy)
    assert measures.idle_share(ctx, "bw") == pytest.approx(
        100 * (1 - busy / (e - s)))
    assert measures.host_residue_us(ctx, "lat") is None
