"""The library-span split (``libspans.py``) and its six readers: on a
made-up trace against hand-computed values, on traced windows recorded
on the chip with the spans (``data/*.libspans.xplane.pb``, written by
``record.py``), and on the earlier recordings, which have none."""
import os

import numpy as np
import pytest

from benchmark import libspans, spec, tracereduce
from benchmark.tests.test_measures import make_ctx

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
US = 1e3
READERS = {"osu_allreduce.4chip": ("lib_host_us.lat", "launch_us.lat",
                                   "ready_wait_us.lat"),
           "reduce_local.ddp_bucket.1chip": ("lib_host_us.op",
                                             "launch_us.op",
                                             "ready_wait_us.op")}
CALL_ROLE = {"osu_allreduce.4chip": ("allreduce", "lat"),
             "reduce_local.ddp_bucket.1chip": ("reduce_local", "bw")}


def read(name, ctx):
    cell = spec.cell(name)
    return {m: cell.readers([{"name": m}])[m].read(ctx)
            for m in READERS[name]}


def host_trace(events, spans):
    """A one-chip trace whose host events are ``events`` (name, start
    us, end us) and whose ``bench.*`` spans are ``spans``."""
    names = [n for n, _, _ in events]
    s = np.array([a for _, a, _ in events]) * US
    e = np.array([b for _, _, b in events]) * US
    spans = {k: (np.array(a, float) * US, np.array(b, float) * US)
             for k, (a, b) in spans.items()}
    return tracereduce.Trace([tracereduce.Busy([0.0], [1.0])], [], spans,
                             (s, e, names))


def test_split_by_hand():
    # three 1000 us calls; the middle one holds no comm.allreduce (a
    # call the program did not annotate) and is left out
    events = [("comm.allreduce", 10, 610), ("py_xla_execute", 100, 200),
              ("coll.xla.launch:allreduce/direct", 60, 560),
              ("comm.allreduce", 2050, 2700),
              ("coll.xla.launch:allreduce/direct", 2100, 2600)]
    calls = ([0, 1000, 2000], [1000, 2000, 3000])
    t = host_trace(events, {"bench.call:lat": calls})
    ctx = make_ctx("osu_allreduce.4chip", [(0, 0.0, 1.0, 3)], t, [(0, 1)])
    p = libspans.split(ctx, "allreduce", "lat")
    assert p["before"].tolist() == [10 * US, 50 * US]
    assert p["lib"].tolist() == [100 * US, 150 * US]
    assert p["launch"].tolist() == [500 * US, 500 * US]
    assert p["wait"].tolist() == [390 * US, 300 * US]
    assert read("osu_allreduce.4chip", ctx) == pytest.approx(
        {"lib_host_us.lat": 125.0, "launch_us.lat": 500.0,
         "ready_wait_us.lat": 345.0})
    # the other cell's call is not this one's; off the chip, nothing
    assert libspans.split(ctx, "reduce_local", "lat") is None
    t.chips = []
    assert libspans.split(ctx, "allreduce", "lat") is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_recorded_calls_add_up(name):
    """Each traced call of the recording: before + lib + launch + wait
    is its ``bench.call`` span, and every part is positive."""
    trace = tracereduce.load(os.path.join(DATA,
                                          f"{name}.libspans.xplane.pb"))
    call, role = CALL_ROLE[name]
    ctx = make_ctx(name, [(0, 0.0, 1.0, 1)], trace, [(0, 1)])
    p = libspans.split(ctx, call, role)
    n = len(ctx.traced_calls(role)[0])
    assert n >= 3 and len(p["call"]) == n
    total = sum(p[k] for k in libspans.PARTS)
    np.testing.assert_allclose(total, p["call"], rtol=0, atol=1.0)
    assert all((p[k] > 0).all() for k in libspans.PARTS)
    got = read(name, ctx)
    assert all(v > 0 for v in got.values())
    # the three medians cover the call, but for the window's own loop
    med_call = float(np.median(p["call"])) / US
    assert 0.85 * med_call < sum(got.values()) < 1.15 * med_call


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_trace_without_library_spans_reads_nothing(name):
    trace = tracereduce.load(os.path.join(DATA, f"{name}.xplane.pb"))
    ctx = make_ctx(name, [(0, 0.0, 1.0, 1)], trace, [(0, 1)])
    assert read(name, ctx) == {m: None for m in READERS[name]}
