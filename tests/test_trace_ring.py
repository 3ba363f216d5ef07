"""The trace subsystem's storage and gating contracts: fixed-capacity
drop-and-count ring, pvar surfacing, rank-symmetric sequencing, and —
the acceptance-critical one — zero span allocation / zero extra
locking when tracing is off (the default)."""
import threading

import numpy as np
import pytest

from ompi_tpu.mca import pvar
from ompi_tpu.trace import core as trace_core
from ompi_tpu.trace.ring import Span, SpanRing


@pytest.fixture(autouse=True)
def _clean_tracer():
    trace_core.disable()
    trace_core.reset()
    yield
    trace_core.disable()
    trace_core.reset()


def _span(name="coll_allreduce", ts=0.0, dur=1e-6, rank=0):
    return Span(name, ts, dur, tid=1, rank=rank)


def test_ring_never_grows_past_capacity_and_counts_drops():
    ring = SpanRing(4)
    accepted = [ring.push(_span(ts=i)) for i in range(7)]
    assert accepted == [True] * 4 + [False] * 3
    assert len(ring) == 4
    assert ring.pushed == 4
    assert ring.dropped == 3
    # the stored spans are the FIRST four (drop-newest: a runaway trace
    # truncates, it never evicts the window being debugged)
    assert [s.ts for s in ring.snapshot()] == [0.0, 1.0, 2.0, 3.0]


def test_overflow_surfaces_through_trace_dropped_pvar():
    trace_core.enable(capacity=2)
    for i in range(5):
        tok = trace_core.begin("coll_barrier", cid="w")
        trace_core.end(tok)
    assert pvar.pvar_read("trace_spans") == 2
    assert pvar.pvar_read("trace_dropped") == 3
    assert len(trace_core.spans()) == 2


def test_begin_end_records_duration_tid_and_args():
    trace_core.enable(capacity=16)
    tok = trace_core.begin("pml_send", dest=3, tag=7)
    trace_core.end(tok, nbytes=8)
    (s,) = trace_core.spans()
    assert s.name == "pml_send"
    assert s.kind == "span"
    assert s.dur >= 0.0
    assert s.tid == threading.get_ident()
    assert s.args == {"dest": 3, "tag": 7, "nbytes": 8}


def test_sequence_counters_are_per_comm_per_event():
    """The attribution layer matches the Nth collective on a comm
    across ranks — sequencing must advance per (cid, name), not
    globally."""
    trace_core.enable(capacity=16)
    toks = [trace_core.begin("coll_allreduce", cid="w"),
            trace_core.begin("coll_allreduce", cid="w"),
            trace_core.begin("coll_barrier", cid="w"),
            trace_core.begin("coll_allreduce", cid="other")]
    for t in toks:
        trace_core.end(t)
    seqs = {(s.name, s.cid, s.seq) for s in trace_core.spans()}
    assert ("coll_allreduce", "w", 0) in seqs
    assert ("coll_allreduce", "w", 1) in seqs
    assert ("coll_barrier", "w", 0) in seqs
    assert ("coll_allreduce", "other", 0) in seqs


def test_instants_record_zero_duration():
    trace_core.enable(capacity=16)
    trace_core.instant("pml_wakeup_flush", wakeups=3)
    (s,) = trace_core.spans()
    assert s.kind == "instant" and s.dur == 0.0


def test_disabled_hot_path_allocates_no_spans(monkeypatch, world):
    """Tracing off (the default) and no profiler session: the
    collective/pt2pt gate is ONE attribute read, the layer spans' one
    check more — begin/end/instant must never run."""
    def boom(*a, **kw):
        raise AssertionError("tracer touched while disabled")
    monkeypatch.setattr(trace_core, "begin", boom)
    monkeypatch.setattr(trace_core, "instant", boom)
    assert trace_core.active is False
    assert not trace_core.recording()

    # stacked collective entry (the composer never wrapped the vtable):
    # the first call fills coll/xla's memo, the second hits it
    x = world.alloc((2,), np.float32, fill=1.0)
    world.allreduce(x)
    world.allreduce(x).block_until_ready()
    # the other collectives' layer spans, on the memo's miss and hit
    blocks = world.put(np.ones((world.size, world.size, 2), np.float32))
    for call in (lambda: world.bcast(x, 0), lambda: world.allgather(x),
                 lambda: world.alltoall(blocks),
                 lambda: world.reduce_scatter_block(blocks)):
        call()
        call().block_until_ready()

    # MPI_Reduce_local on device operands (the op device combiner)
    import jax.numpy as jnp
    import ompi_tpu
    a = jnp.arange(4, dtype=jnp.float32)
    ompi_tpu.reduce_local(a, a, ompi_tpu.SUM).block_until_ready()

    # per-rank pml entry (loopback engine)
    from ompi_tpu.pml.perrank import PerRankEngine, Router
    kv = {}
    router = Router(0, 1, kv.__setitem__, kv.__getitem__)

    class _C:
        cid = "trace-off"
        size = 2

        def rank(self):
            return 0

        def world_rank_of(self, r):
            return 0
    eng = PerRankEngine(_C(), router)
    try:
        eng.send(np.float32(1.0), dest=1, tag=5)
        eng.recv(source=0, tag=5, timeout=10)
        eng.send_small(np.float32(2.0), [1], tag=6)
        eng.recv(source=0, tag=6, timeout=10)
    finally:
        router.close()
    assert trace_core.stats()["spans"] == 0


def test_stacked_vtable_unwrapped_when_disabled(world):
    from ompi_tpu.trace.core import _TracedSlot
    for func, mod in world.c_coll.items():
        assert not isinstance(mod, _TracedSlot), func


def test_enable_is_idempotent_and_disable_keeps_ring_readable():
    trace_core.enable(capacity=8)
    tok = trace_core.begin("coll_bcast", cid="w")
    trace_core.end(tok)
    trace_core.enable()                  # no-op: ring survives
    assert len(trace_core.spans()) == 1
    trace_core.disable()
    assert trace_core.active is False
    assert len(trace_core.spans()) == 1  # readable post-mortem
