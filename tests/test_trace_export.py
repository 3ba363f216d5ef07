"""Perfetto export schema, mpisync timebase alignment, late-arrival
attribution on a synthetic skewed barrier, live tracing through the
coll composer / per-rank interposer, the tracedump CLI, and the
summary round trip."""
import json

import numpy as np
import pytest

from ompi_tpu.mca import pvar
from ompi_tpu.trace import attribution, perfetto
from ompi_tpu.trace import core as trace_core
from ompi_tpu.trace.ring import Span


@pytest.fixture(autouse=True)
def _clean():
    trace_core.disable()
    trace_core.reset()
    attribution.reset_watermarks()
    yield
    trace_core.disable()
    trace_core.reset()
    attribution.reset_watermarks()


BASE = 1000.0                            # an arbitrary perf_counter era
# rank -> clock offset against rank 0 (what mpisync.measure_offset
# reports: remote_now - local_now); rank timestamps below are recorded
# on each rank's OWN clock, so they carry its offset
OFFSETS = {0: 0.0, 1: 0.25, 2: -0.125, 3: 0.5}
# true arrival skew injected at rank 2 (the late rank)
LATE_RANK, LATE_BY = 2, 0.050


def _skewed_barrier_spans(seq=0):
    """coll_barrier on comm 'w': every rank arrives at BASE (+50 ms for
    the late rank) in TRUE time; each records on its own skewed clock;
    all leave together 10 ms after the last arrival."""
    spans = []
    for rank, off in OFFSETS.items():
        t_arr = BASE + (LATE_BY if rank == LATE_RANK else 0.0)
        t_end = BASE + LATE_BY + 0.010
        spans.append(Span("coll_barrier", t_arr + off,
                          t_end - t_arr, tid=100 + rank, rank=rank,
                          cid="w", seq=seq))
    return spans


def test_perfetto_export_schema_and_monotonic_ts():
    spans = _skewed_barrier_spans()
    spans.append(Span("pml_wakeup_flush", BASE + 0.02, 0.0, tid=101,
                      rank=1, kind="instant"))
    obj = perfetto.export(spans, rank_offsets=OFFSETS)
    text = json.dumps(obj)               # Perfetto-loadable: valid JSON
    parsed = json.loads(text)
    evs = parsed["traceEvents"]
    assert parsed["displayTimeUnit"] == "ms"
    for ev in evs:
        assert {"name", "ph", "pid", "tid", "ts"} <= set(ev)
    # one pid per rank, named
    names = {ev["pid"]: ev["args"]["name"] for ev in evs
             if ev["ph"] == "M" and ev["name"] == "process_name"}
    assert names == {r: f"rank {r}" for r in OFFSETS}
    # spans are complete events with dur; instants are thread-scoped
    assert all("dur" in ev for ev in evs if ev["ph"] == "X")
    assert any(ev["ph"] == "i" and ev["s"] == "t" for ev in evs)
    # timeline events are globally ts-sorted (so per-pid too)
    tl = [ev["ts"] for ev in evs if ev["ph"] != "M"]
    assert tl == sorted(tl)


def test_offset_alignment_puts_ranks_on_one_timebase():
    """Raw timestamps disagree by the clock offsets; after alignment
    the only remaining spread is the injected 50 ms arrival skew."""
    spans = _skewed_barrier_spans()
    evs = [e for e in perfetto.to_events(spans, rank_offsets=OFFSETS)
           if e["ph"] == "X"]
    arrivals = {e["pid"]: e["ts"] for e in evs}
    base_us = BASE * 1e6
    for rank, ts in arrivals.items():
        expect = base_us + (LATE_BY * 1e6 if rank == LATE_RANK else 0)
        assert ts == pytest.approx(expect, abs=1.0), rank
    # unaligned, rank 3's +0.5 s clock error would dwarf the skew
    raw = {e["pid"]: e["ts"] for e in perfetto.to_events(spans)
           if e["ph"] == "X"}
    assert raw[3] - raw[0] > 0.4e6


def test_late_arrival_attribution_names_the_late_rank():
    reports = attribution.late_arrival(_skewed_barrier_spans(),
                                       rank_offsets=OFFSETS)
    assert len(reports) == 1
    r = reports[0]
    assert r["name"] == "coll_barrier" and r["cid"] == "w"
    assert r["critical_rank"] == LATE_RANK
    assert r["skew_s"] == pytest.approx(LATE_BY, rel=1e-6)
    by_rank = {row["rank"]: row for row in r["ranks"]}
    # on-time ranks were blocked for the full skew, then in-op 10 ms
    assert by_rank[0]["blocked_s"] == pytest.approx(LATE_BY, rel=1e-6)
    assert by_rank[0]["in_op_s"] == pytest.approx(0.010, rel=1e-4)
    # the late rank blocked nobody-but-itself: zero wait, full op
    assert by_rank[LATE_RANK]["blocked_s"] == pytest.approx(0.0, abs=1e-9)
    # skew watermark surfaced per comm and in the aggregate pvar
    assert pvar.pvar_read("trace_skew_watermarks")["w"] == \
        pytest.approx(LATE_BY, rel=1e-6)
    assert pvar.pvar_read("trace_skew_cw") == pytest.approx(
        LATE_BY, rel=1e-6)


def test_attribution_ignores_pt2pt_and_instants():
    spans = _skewed_barrier_spans()
    # same (cid-less) seq space must not fabricate occurrences
    spans.append(Span("pml_send", BASE, 1e-6, tid=1, rank=0))
    spans.append(Span("pml_send", BASE + 1, 1e-6, tid=1, rank=1))
    spans.append(Span("pml_wakeup_flush", BASE, 0.0, tid=1, rank=0,
                      kind="instant"))
    reports = attribution.late_arrival(spans, rank_offsets=OFFSETS)
    assert [r["name"] for r in reports] == ["coll_barrier"]


def test_live_stacked_collectives_are_traced(mpi, world):
    """End to end through the real composer: tracing enabled before
    communicator construction wraps the selected vtable; a collective
    then yields a span under the hooks event name, and the export is
    Perfetto-loadable."""
    trace_core.enable(capacity=1024)
    comm = None
    try:
        comm = world.dup()               # selection re-runs: wrapped
        x = comm.alloc((2,), np.float32, fill=1.0)
        comm.allreduce(x)
        comm.allreduce(x)
        comm.barrier()
        spans = trace_core.spans()
        names = [s.name for s in spans]
        assert names.count("coll_allreduce") == 2
        assert "coll_barrier" in names
        ars = [s for s in spans if s.name == "coll_allreduce"]
        assert [s.seq for s in ars] == [0, 1]    # rank-symmetric seq
        assert all(s.cid == str(comm.cid) for s in ars)
        assert all(s.dur > 0 for s in ars)
        json.dumps(perfetto.export(spans))       # loadable
    finally:
        if comm is not None:
            comm.free()


def test_live_perrank_interpose_traces_collectives():
    """The per-rank tier: interpose() rebinds collectives with the
    span shim when tracing is on at construction."""
    from ompi_tpu.core.group import Group
    from ompi_tpu.core.rankcomm import RankCommunicator
    from ompi_tpu.pml.perrank import Router
    trace_core.enable(capacity=256)
    kv = {}
    router = Router(0, 1, kv.__setitem__, kv.__getitem__)
    try:
        comm = RankCommunicator(Group([0]), 0, router, cid="tr-live")
        assert "trace" in comm._coll_interposers
        out = comm.allreduce(np.float64(2.0))
        assert float(out) == 2.0
        names = [s.name for s in trace_core.spans()]
        assert "coll_allreduce" in names
        # composition stays single-span: the outermost frame only
        assert names.count("coll_allreduce") == 1
    finally:
        router.close()


def test_tracedump_cli_merges_dumps(tmp_path):
    from ompi_tpu.tools import tracedump
    files = []
    for rank, off in OFFSETS.items():
        mine = [s.to_dict() for s in _skewed_barrier_spans()
                if s.rank == rank]
        p = tmp_path / f"trace_r{rank}.json"
        p.write_text(json.dumps(
            {"rank": rank, "offset_s": off, "spans": mine}))
        files.append(str(p))

    out = tmp_path / "perfetto.json"
    assert tracedump.main(files + ["-o", str(out)]) == 0
    evs = json.loads(out.read_text())["traceEvents"]
    assert {e["pid"] for e in evs} == set(OFFSETS)

    rep = tmp_path / "report.json"
    assert tracedump.main(files + ["--format", "report",
                                   "-o", str(rep)]) == 0
    report = json.loads(rep.read_text())
    assert report["late_arrival"][0]["critical_rank"] == LATE_RANK


def test_trace_dump_and_load_roundtrip(tmp_path):
    trace_core.enable(capacity=8)
    tok = trace_core.begin("coll_allreduce", cid="w")
    trace_core.end(tok)
    path = trace_core.dump(str(tmp_path / "d.json"), offset_s=0.125)
    d = trace_core.load_dump(path)
    assert d["offset_s"] == 0.125
    assert d["spans"][0]["name"] == "coll_allreduce"
    assert d["stats"]["spans"] == 1


def test_bench_trace_summary_roundtrips_json():
    """``attribution.summarize`` is machine-readable: its json round
    trip is bit-identical."""
    trace_core.enable(capacity=32)
    for s in _skewed_barrier_spans():
        s.ts -= OFFSETS[s.rank]          # one process, one timebase:
        trace_core._ring.push(s)         # a live ring is pre-aligned
    summary = attribution.summarize(trace_core.spans(),
                                    trace_core.stats())
    assert json.loads(json.dumps(summary)) == summary
    assert summary["spans"] == 4
    assert summary["by_name"]["coll_barrier"]["count"] == 4
    assert summary["late_arrival_top"][0]["critical_rank"] == LATE_RANK
