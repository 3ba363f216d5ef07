"""The tracer's profiler sink: the library's layer spans land in a
``jax.profiler`` trace (on the profiler's clock, beside the device ops)
while a session records, and in the span ring when tracing is on —
without a cid, so they stay out of the collective attribution."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.core import op as op_mod
from ompi_tpu.trace import attribution
from ompi_tpu.trace import core as trace_core
from ompi_tpu.trace.ring import Span

LAYER_SPANS = {"comm.allreduce", "coll.xla.launch:allreduce/direct",
               "op.reduce_local", "op.launch:sum"}


@pytest.fixture(autouse=True)
def _clean_tracer():
    trace_core.disable()
    trace_core.reset()
    yield
    trace_core.disable()
    trace_core.reset()


def _calls(world, n=3):
    """``n`` rounds of an 8 B allreduce and a reduce_local on device
    operands, each inside a marker annotation of the calling thread."""
    x = world.alloc((2,), np.float32, fill=1.0)
    a, b = jnp.arange(8.0), jnp.ones(8)
    world.allreduce(x).block_until_ready()          # compiled, memo filled
    op_mod.reduce_local(a, b, op_mod.SUM).block_until_ready()
    for _ in range(n):
        with jax.profiler.TraceAnnotation("test.call:allreduce"):
            world.allreduce(x).block_until_ready()
        with jax.profiler.TraceAnnotation("test.call:reduce_local"):
            op_mod.reduce_local(a, b, op_mod.SUM).block_until_ready()


def _host_events(path):
    """{line index: [(name, start ns, end ns)]} of the dotted spans."""
    from jax.profiler import ProfileData
    out = {}
    for p, plane in enumerate(ProfileData.from_file(path).planes):
        for i, line in enumerate(plane.lines):
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events
                   if e.name.split(".")[0] in ("test", "comm", "coll", "op")]
            if evs:
                out[p, i] = evs
    return out


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_layer_spans_land_in_the_profiler_trace(world, tmp_path):
    assert not trace_core.active and not trace_core.recording()
    with jax.profiler.trace(str(tmp_path)):
        assert trace_core.recording()
        _calls(world)
    assert not trace_core.recording()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    lines = _host_events(path)
    # every span on one line: the calling (main) thread's, with the
    # markers it wrote itself
    assert len(lines) == 1
    (evs,) = lines.values()
    names = {n for n, _, _ in evs}
    assert LAYER_SPANS <= names
    assert {n for n in names if not n.startswith("test.")} == LAYER_SPANS

    def of(name):
        return [e for e in evs if e[0] == name]
    for marker, outer, launch in (
            ("test.call:allreduce", "comm.allreduce",
             "coll.xla.launch:allreduce/direct"),
            ("test.call:reduce_local", "op.reduce_local", "op.launch:sum")):
        calls = of(marker)
        assert len(calls) == 3
        for c in calls:
            (o,) = [e for e in of(outer) if _inside(e, c)]
            (_,) = [e for e in of(launch) if _inside(e, o)]
    # the ring stayed off: a profiler session alone arms no ring
    assert trace_core.stats()["spans"] == 0


def _coll_call(comm, coll, elems):
    """A call of ``coll`` on ``elems`` f32 per rank, in its stacked
    layout."""
    n = comm.size
    if coll in ("allgather", "bcast"):
        x = comm.alloc((elems,), np.float32, fill=1.0)
    else:
        x = comm.put(np.ones((n, n, elems // n), np.float32))
    return {"allgather": lambda: comm.allgather(x),
            "alltoall": lambda: comm.alltoall(x),
            "bcast": lambda: comm.bcast(x, 0),
            "reduce_scatter_block":
                lambda: comm.reduce_scatter_block(x, op_mod.SUM)}[coll]


@pytest.fixture(scope="module")
def quad(world):
    """A communicator over four of the CPU devices."""
    return world.split([0 if r < 4 else 1 for r in range(world.size)])[0]


@pytest.mark.parametrize("coll", ["allgather", "alltoall", "bcast",
                                  "reduce_scatter_block"])
def test_each_collective_writes_its_layer_spans(quad, tmp_path, coll):
    """Each call writes one ``comm.<coll>`` span with exactly one
    ``coll.xla.launch:<coll>/<alg>`` inside it: a call that fills
    coll/xla's memo (its compile inside) and three that hit it."""
    hit = _coll_call(quad, coll, 8)
    hit().block_until_ready()
    miss = _coll_call(quad, coll, 12)
    with jax.profiler.trace(str(tmp_path)):
        for run in (miss, hit, hit, hit):
            with jax.profiler.TraceAnnotation(f"test.call:{coll}"):
                run().block_until_ready()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    (evs,) = _host_events(path).values()
    launch = f"coll.xla.launch:{coll}/"
    assert {n for n, _, _ in evs if not n.startswith(launch)} == {
        f"test.call:{coll}", f"comm.{coll}"}
    calls = [e for e in evs if e[0] == f"test.call:{coll}"]
    assert len(calls) == 4
    for c in calls:
        (o,) = [e for e in evs if e[0] == f"comm.{coll}" and _inside(e, c)]
        (_,) = [e for e in evs if e[0].startswith(launch) and _inside(e, o)]
    # the launch span names the algorithm that served, on both paths
    assert len({n for n, _, _ in evs if n.startswith(launch)}) == 1


@pytest.mark.parametrize("block,alg", [((256,), "alltoall_sum"),
                                       ((2, 128), "direct")],
                         ids=["1d", "2d"])
def test_reduce_scatter_block_launch_span_names_its_form(quad, tmp_path,
                                                         block, alg):
    """A SUM over 1-D f32 blocks of whole 128-lane rows is served by
    ``alltoall_sum``; a 2-D block keeps ``direct``."""
    n = quad.size
    x = quad.put(np.ones((n, n) + block, np.float32))
    quad.reduce_scatter_block(x, op_mod.SUM).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        quad.reduce_scatter_block(x, op_mod.SUM).block_until_ready()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    (evs,) = _host_events(path).values()
    assert [n for n, _, _ in evs if n.startswith("coll.")] == [
        f"coll.xla.launch:reduce_scatter_block/{alg}"]


def test_layer_spans_in_the_ring_carry_no_cid(world):
    trace_core.enable(capacity=256)
    _calls(world, n=2)
    trace_core.disable()
    layer = [s for s in trace_core.spans() if s.name in LAYER_SPANS]
    # a warm-up round and two more, each with its launch span (the memo
    # miss path has one too)
    assert {s.name for s in layer} == LAYER_SPANS
    assert all(s.cid is None and s.seq is None for s in layer)
    assert len([s for s in layer if s.name == "comm.allreduce"]) == 3
    # a rank-skewed barrier beside them: the attribution of the
    # collective is the same with and without the layer spans
    skewed = [Span("coll_barrier", 1.0 + (0.05 if r == 2 else 0.0), 0.02,
                   tid=100 + r, rank=r, cid="w", seq=0) for r in range(4)]
    alone = attribution.late_arrival(skewed)
    assert alone and alone[0]["critical_rank"] == 2
    assert attribution.late_arrival(skewed + layer) == alone
