"""The metric arithmetic against hand-computed values, on a made-up
window and trace."""
import numpy as np
import pytest

from benchmark import harness, measures, spec, tracereduce, window

MiB = 1 << 20


def make_ctx(cell_name, blocks, trace=None, trace_blocks=None, size=4):
    cell = spec.cell(cell_name)
    win = window.Window([window.Block(*b) for b in blocks], [], None)
    return harness.Context(cell, win, 12.5, size, "TPU v5 lite", trace,
                           trace_blocks)


def trace_of(busy, spans=None):
    s, e = zip(*busy)
    spans = {k: (np.array(v[0], float), np.array(v[1], float))
             for k, v in (spans or {}).items()}
    z = np.zeros(0)
    return tracereduce.Trace([tracereduce.Busy(s, e)], [], spans,
                             (z, z, []))


def test_latency_and_bandwidth_on_the_host_clock():
    # phase 0 = lat (8 B), phase 1 = bw (256 MiB)
    ctx = make_ctx("osu_allreduce.4chip",
                   [(0, 0.0, 1.0, 10000), (1, 1.0, 2.0, 100),
                    (0, 2.0, 3.0, 5000)])
    assert measures.avg_latency_us(ctx, "lat") == pytest.approx(
        2.0 / 15000 * 1e6)
    assert measures.algbw_gbps(ctx, "bw") == pytest.approx(
        256 * MiB * 100 / 1.0 / 1e9)         # 26.8435456 GB/s


def test_allreduce_roofline():
    # 100 calls of 256 MiB on 4 ranks, 4 ms of device time each
    ns = 1e9
    trace = trace_of([(0.1 * ns, 0.5 * ns)])
    ctx = make_ctx("osu_allreduce.4chip", [(1, 0.0, 1.0, 100)], trace,
                   [(0.0, 1.0 * ns)])
    least = 100 * 1.5 * 256 * MiB / 200e9           # 0.201326592 s
    assert measures.roofline(ctx, "allreduce", "bw") == pytest.approx(
        100 * least / 0.4)                           # 50.331648 %
    assert measures.roofline(ctx, "reduce_local", "bw") is None
    assert measures.idle_share(ctx, "bw") == pytest.approx(60.0)
    assert measures.idle_share(ctx, "lat") is None


def test_reduce_local_roofline():
    # 1000 calls of 25 MiB operands, 150 us of device time each
    trace = trace_of([(0.0, 0.15e9)])
    ctx = make_ctx("reduce_local.ddp_bucket.1chip", [(0, 0.0, 1.0, 1000)],
                   trace, [(0.0, 1e9)], size=1)
    least = 3 * 25 * MiB / 819e9                     # 96.02 us
    assert measures.roofline(ctx, "reduce_local", "bw") == pytest.approx(
        100 * least / 150e-6)


def test_host_residue():
    # three 100 us calls, each with 20 us of device work inside
    us = 1e3
    starts = np.array([0, 200, 400]) * us
    busy = [(s + 50 * us, s + 70 * us) for s in starts]
    trace = trace_of(busy, {"bench.call:lat": (starts, starts + 100 * us)})
    ctx = make_ctx("osu_allreduce.4chip", [(0, 0.0, 1.0, 3)], trace,
                   [(0.0, 1e6)])
    assert measures.host_residue_us(ctx, "lat") == pytest.approx(80.0)


def test_an_unknown_device_is_an_error():
    trace = trace_of([(0.0, 1.0)])
    ctx = make_ctx("osu_allreduce.4chip", [(1, 0.0, 1.0, 1)], trace,
                   [(0.0, 1e9)])
    ctx.device_kind = "TPU v99"
    with pytest.raises(spec.SpecError):
        measures.roofline(ctx, "allreduce", "bw")


def test_busy_union_and_spans():
    b = tracereduce.Busy([0, 5, 2, 20], [3, 8, 4, 30])
    assert list(b.s) == [0, 5, 20] and list(b.e) == [4, 8, 30]
    assert b.within(np.array([0, 2, 9]), np.array([10, 6, 25])).tolist() \
        == [7, 3, 5]
    assert tracereduce.Busy([], []).within([0], [1]).tolist() == [0]


@pytest.mark.parametrize("name,kind", [
    ("all-reduce.3", "all-reduce"), ("all-gather-start.1", "all-gather"),
    ("reduce-scatter-done", "reduce-scatter"), ("fusion.14", "fusion"),
    ("collective-permute-start.2.1", "collective-permute"),
    ("copy", "copy")])
def test_op_kind(name, kind):
    assert tracereduce.op_kind(name) == kind
