"""pml/perrank — the per-rank (multi-controller) matching engine.

Behavioral spec: ob1's receive-side matching
(``ompi/mca/pml/ob1/pml_ob1_recvfrag.c:296-330``): arriving fragments are
matched against posted receives (source/tag with wildcards); unmatched
fragments queue in arrival order; ordering is FIFO per (source, comm) —
MPI's non-overtaking rule. Unlike the single-controller stacked engine,
this one serves exactly ONE rank per process, frames arrive from btl/tcp
reader threads, and a blocking receive genuinely blocks — the matching
send is produced by another OS process, so recv-before-send is the
natural order (the reference's semantics the stacked engine cannot
express).

Synchronous send (MPI_Ssend): the sender attaches an ack id; the
receiver's match emits a control frame back; the sender's request
completes on the ack — the rendezvous-ACK handshake of
``pml_ob1_sendreq.h:389-460`` reduced to its observable semantics.

Frame routing: one process-wide :class:`Router` owns the TcpEndpoint and
demultiplexes frames by communicator CID; frames for a CID whose engine
is not yet constructed (a peer raced ahead through comm creation) wait in
a pending queue — the reference's "non-matching fragments held until the
communicator exists" behavior (comm_cid.c activation).
"""
from __future__ import annotations

import itertools
import threading
import time as _time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Tuple

import numpy as np

from ompi_tpu import telemetry as _tele
from ompi_tpu.btl.tcp import PeerDownError, decode_payload, encode_payload
from ompi_tpu.core.errhandler import ERR_PENDING, ERR_RANK, ERR_TAG, MPIError
from ompi_tpu.core.request import Request, Status
from ompi_tpu.runtime import progress as _progress
from ompi_tpu.trace import core as _trace

ANY_SOURCE = -1
ANY_TAG = -1
PROC_NULL = -2


def _ft_send(endpoint, wdest: int, header: dict, raw: bytes) -> None:
    """Send with the ULFM error mapping: a connection that dies UNDER a
    send (after the btl exhausted its reconnect retry) reports the rank
    failed and surfaces ``MPI_ERR_PROC_FAILED`` — never a raw socket
    error (satellite (a) of docs/RESILIENCE.md)."""
    try:
        endpoint.send_frame(wdest, header, raw)
    except PeerDownError as e:
        from ompi_tpu.core.errhandler import ERR_PROC_FAILED
        from ompi_tpu.runtime import ft
        ft.fail_rank(e.world_rank, "connection down during send")
        raise MPIError(
            ERR_PROC_FAILED,
            f"peer world rank {e.world_rank} failed during send") from e


class Router:
    """Process-wide frame router: CID -> engine, plus the ack table."""

    def __init__(self, rank: int, nprocs: int, kv_set, kv_get):
        self.rank = rank
        self.nprocs = nprocs
        self.kv_set = kv_set             # the modex plane (devxfer
        self.kv_get = kv_get             # publishes its address here)
        self._engines: Dict[Any, "PerRankEngine"] = {}
        self._pending: Dict[Any, List[Tuple[dict, bytes]]] = {}
        # ack id -> [Event, reply payload] (replies carry RMA get/fetch
        # results back to the origin)
        self._acks: Dict[int, list] = {}
        self._ack_ids = itertools.count(1)
        self._lock = threading.Lock()
        # wid -> handler(header, raw) for one-sided targets (the osc
        # active-message plane; handlers run on reader threads and must
        # not block)
        self._rma: Dict[Any, Any] = {}
        self._closing = False
        self._departed: set = set()      # peers that said goodbye
        # -- resilience plane (docs/RESILIENCE.md) ---------------------
        # revoked communicator CIDs + per-cid callbacks (the reliable
        # revoke broadcast's local state, coll_base_revoke_local.c) and
        # the optional heartbeat detector (ft/detector, attached by
        # runtime/init after wire_up)
        self._revoked: set = set()
        self._revoke_cbs: Dict[Any, list] = {}
        self.detector = None
        # whatever ingress learns of a death (EOF monitor, heartbeat
        # declaration, remote obituary) funnels through the registry;
        # the listener does the local cleanup AND re-broadcasts — the
        # registry's first-report dedup terminates the flood
        from ompi_tpu.runtime import ft
        ft.add_listener(self._on_rank_failed)
        # segment-train reassembly for the pipelined rendezvous
        # (pml/pipeline): keyed (source world rank, pipe id), fed by
        # rail reader threads BELOW the matching layer — created before
        # the endpoint so no reader thread can race it
        from ompi_tpu.pml.pipeline import PipeStore
        self.pipes = PipeStore()
        # the bml/r2 multiplexer: sm rings for same-host eager frames,
        # tcp for the rest (and as the failure detector's wire)
        from ompi_tpu.btl.bml import BmlEndpoint
        self.endpoint = BmlEndpoint(rank, nprocs, kv_set, kv_get,
                                    self._deliver,
                                    on_peer_lost=self._peer_lost)
        # the ctl flush-window counters ride the MPI_T pvar plumbing
        # next to the wakeup-coalescing pvars (docs/SMALLMSG.md)
        from ompi_tpu.mca import pvar
        pvar.pvar_register_dict(
            "btl_ctl", self.endpoint.tcp.ctl_stats,
            help_prefix="ctl flush window: ")

    def wire_up(self) -> None:
        """Eagerly connect to every peer (the reference's add_procs
        endpoint setup). Besides first-send latency, this is what makes
        the failure detector COMPLETE: each pair then has identified
        connections in both directions, so a process death is observed
        by every survivor — not just the peers the victim happened to
        message. (At real scale this would be lazy wire-up plus an
        obituary gossip; eager is right for the worlds one host runs.)"""
        for peer in range(self.nprocs):
            if peer != self.rank:
                try:
                    self.endpoint._connect(peer)
                except Exception:        # noqa: BLE001 — peer may be
                    pass                 # dead already; detector covers

    # -- failure detection (ULFM over real process death) --------------
    def begin_shutdown(self) -> None:
        """Called at finalize: announce graceful departure to every
        connected peer (a 'bye' obituary-suppressor — without it, a
        fast survivor's close after a failure would look like a second
        death to slower survivors), then stop treating EOFs as
        failures locally."""
        for peer in list(self.endpoint._peers):
            try:
                self.endpoint.send_frame(peer, {"ctl": "bye",
                                                "peer": self.rank})
            except Exception:            # noqa: BLE001
                pass
        self._closing = True

    def _peer_lost(self, world_rank: int) -> None:
        """An identified peer connection died: the ULFM event. Report
        it into the process default registry; the registry listener
        (:meth:`_on_rank_failed`) does the local cleanup and the
        obituary broadcast — same path whatever the ingress."""
        if self._closing or world_rank in self._departed:
            return                       # graceful exit, not death
        from ompi_tpu.runtime import ft
        ft.fail_rank(world_rank, "peer connection lost")

    def _on_rank_failed(self, world_rank: int, reason: str) -> None:
        """Registry listener (fires exactly once per failed rank):
        complete every pending operation that could have matched the
        dead rank in error (ompi/request/req_ft.c over a REAL dead
        process) and fan the obituary out as a reliable ``ftdead``
        broadcast — the PMIx event-propagation role. Receivers dedup
        through their own registries, so the flood terminates."""
        if self._closing:
            return
        # unfinished segment trains from the dead sender can never
        # complete — fail their waiters now (pml/pipeline)
        self.pipes.fail_peer(world_rank)
        # slots parked for (or attached from) the dead rank can never
        # be returned by it — reclaim/unmap them now (btl/shmseg)
        plane = getattr(getattr(self, "endpoint", None), "shm_seg",
                        None)
        if plane is not None:
            try:
                plane.peer_failed(world_rank)
            except Exception:            # noqa: BLE001
                pass
        with self._lock:
            engines = list(self._engines.values())
        for eng in engines:
            try:
                eng._peer_failed(world_rank)
            except Exception:            # noqa: BLE001
                pass
        self._broadcast_ctl({"ctl": "ftdead", "rank": world_rank,
                             "peer": self.rank})

    def _broadcast_ctl(self, header: dict) -> None:
        """Best-effort fan-out of a ctl frame to every live peer over
        the UNSEQUENCED tcp path (these frames carry no ``_sq``, so a
        lost one leaves no reorder-buffer hole; reliability comes from
        every learner re-forwarding on first receipt)."""
        from ompi_tpu.runtime import ft
        failed = ft.failed_ranks()
        for peer in range(self.nprocs):
            if peer == self.rank or peer in failed:
                continue
            try:
                self.endpoint.tcp.send_frame(peer, dict(header))
            except Exception:            # noqa: BLE001 — a dying
                pass                     # learner is its own obituary

    # -- revoke plane (MPIX_Comm_revoke over the ctl wire) -------------
    def revoke(self, rcid) -> None:
        """Locally revoke ``rcid`` and start the reliable broadcast
        (coll_base_revoke_local.c's role: first receipt re-forwards,
        the revoked-set membership test terminates the flood)."""
        self._on_revoke(rcid)

    def is_revoked(self, rcid) -> bool:
        return rcid in self._revoked

    def register_revoke_cb(self, rcid, cb) -> None:
        with self._lock:
            self._revoke_cbs.setdefault(rcid, []).append(cb)

    def unregister_revoke_cb(self, rcid) -> None:
        with self._lock:
            self._revoke_cbs.pop(rcid, None)

    def _on_revoke(self, rcid) -> None:
        with self._lock:
            if rcid in self._revoked:
                return                   # flood termination
            self._revoked.add(rcid)
            cbs = list(self._revoke_cbs.get(rcid, []))
        if _tele.active:
            # flight-recorder trigger: first receipt of a revocation is
            # incident evidence worth freezing (rate-limited inside)
            from ompi_tpu.telemetry import flightrec as _flightrec
            _flightrec.record("revoke", {"rcid": str(rcid),
                                         "rank": self.rank})
        self._broadcast_ctl({"ctl": "revoke", "rcid": rcid,
                             "peer": self.rank})
        for cb in cbs:
            try:
                cb()
            except Exception:            # noqa: BLE001
                pass

    def register(self, cid, engine: "PerRankEngine") -> None:
        with self._lock:
            self._engines[cid] = engine
            backlog = self._pending.pop(cid, [])
        for header, raw in backlog:
            engine._incoming(header, raw)

    def unregister(self, cid) -> None:
        with self._lock:
            self._engines.pop(cid, None)

    def new_ack(self) -> Tuple[int, list]:
        """Returns (ack id, entry). The entry is ``[Event, reply]``;
        _deliver pops the table slot and mutates THIS list, so waiters
        read the reply from their own reference and nothing leaks —
        one entry per ack regardless of who forgets to collect it."""
        aid = next(self._ack_ids)
        ent = [threading.Event(), None]
        with self._lock:
            self._acks[aid] = ent
        return aid, ent

    def cancel_ack(self, aid: int) -> None:
        """Drop a pending ack slot (timeout path)."""
        with self._lock:
            self._acks.pop(aid, None)

    def register_rma(self, wid, handler) -> None:
        with self._lock:
            self._rma[wid] = handler

    def unregister_rma(self, wid) -> None:
        with self._lock:
            self._rma.pop(wid, None)

    def _deliver(self, header: dict, raw: bytes) -> None:
        """Called from btl reader threads (and loopback sends)."""
        ctl = header.get("ctl")
        if ctl == "hb":
            d = self.detector
            if d is not None:
                d.on_heartbeat(header["peer"])
            # telemetry RTT echo: the sender stamped "ht" only while
            # its telemetry was on; reply in kind only while OURS is on
            # too — with the plane off neither side's frames change
            if _tele.active and "ht" in header:
                try:
                    self.endpoint.tcp.send_frame(
                        header["peer"],
                        {"ctl": "hbr", "peer": self.rank,
                         "ht": header["ht"]})
                except Exception:        # noqa: BLE001 — best-effort
                    pass
            return
        if ctl == "hbr":
            if _tele.active:
                hist = _tele.HB_RTT
                if hist is not None:
                    rtt = _time.perf_counter() - float(header["ht"])
                    hist.record(max(rtt, 0.0) * 1e6)
            return
        if ctl == "ftdead":
            # remote obituary: feed the registry (dedups); our own
            # listener re-forwards on first receipt. An obituary about
            # OURSELVES is a false accusation — the accusers will
            # exclude us either way; don't poison our own registry.
            r = header["rank"]
            if not (self._closing or r == self.rank
                    or r in self._departed):
                from ompi_tpu.runtime import ft
                ft.fail_rank(r, "obituary from rank %s"
                             % header.get("peer"))
            return
        if ctl == "revoke":
            self._on_revoke(header["rcid"])
            return
        if ctl == "segfree":
            # receiver finished with a shared slot we own (btl/shmseg
            # zero-copy plane): return it to the per-peer free pool
            plane = getattr(getattr(self, "endpoint", None),
                            "shm_seg", None)
            if plane is not None:
                plane.release(header["peer"], header["i"])
            return
        if ctl == "bye":
            with self._lock:
                self._departed.add(header["peer"])
            return
        if header.get("ctl") == "ack":
            with self._lock:
                ent = self._acks.pop(header["ack_id"], None)
            if ent is not None:
                if "desc" in header:
                    ent[1] = decode_payload(header["desc"], raw)
                _progress.wake(ent[0])   # coalesces under a drain batch
            return
        if "rma" in header:
            with self._lock:
                h = self._rma.get(header["wid"])
            if h is not None:
                h(header, raw)
            return
        if "pipeseg" in header:
            # a rail-striped segment of a pipelined rendezvous train:
            # reassembled by index below the matching layer — only the
            # train's ordered init frame participates in matching
            self.pipes.deliver(header, raw)
            return
        cid = header["cid"]
        with self._lock:
            eng = self._engines.get(cid)
            if eng is None:
                self._pending.setdefault(cid, []).append((header, raw))
                return
        eng._incoming(header, raw)

    def send_ack(self, world_rank: int, ack_id: int,
                 reply: Any = None) -> None:
        header = {"ctl": "ack", "ack_id": ack_id}
        raw = b""
        if reply is not None:
            header["desc"], raw = encode_payload(reply)
        self.endpoint.send_frame(world_rank, header, raw)

    def close(self) -> None:
        self._closing = True
        from ompi_tpu.runtime import ft
        ft.remove_listener(self._on_rank_failed)
        d, self.detector = self.detector, None
        if d is not None:
            try:
                d.stop()
            except Exception:            # noqa: BLE001
                pass
        self.endpoint.close()


class _Msg:
    __slots__ = ("src", "tag", "data", "ack")

    def __init__(self, src: int, tag: int, data: Any,
                 ack: Optional[Tuple[int, int]] = None):
        self.src = src                  # comm-local source rank
        self.tag = tag
        self.data = data
        self.ack = ack                  # (sender world rank, ack id)


class RankRequest(Request):
    """A receive (or synchronous-send) request completed by the engine
    from a btl reader thread; wait blocks on a real Event."""

    cancelled = False                    # MPI_Cancel outcome

    def __init__(self, src: int, tag: int):
        super().__init__(arrays=[])
        self._complete = False
        self._event = threading.Event()
        self._error: Optional[BaseException] = None
        self.status = Status(source=src, tag=tag)

    def cancel(self) -> None:
        """MPI_Cancel: succeeds only while the receive is still
        posted (unmatched); a matched/completed request is past the
        cancellation point and the call is a no-op (cancel.c.in
        semantics)."""
        fn = getattr(self, "_cancel_fn", None)
        if fn is not None:
            fn()

    def _deliver(self, msg: _Msg) -> None:
        self._result = msg.data
        self.status.source = msg.src
        self.status.tag = msg.tag
        self.status.count = int(getattr(msg.data, "size", 1) or 1)
        self.status.nbytes = int(getattr(msg.data, "nbytes", -1))
        self._complete = True
        # completion is a cancellation point (cancel() becomes a no-op)
        # — drop the closure NOW: it captures this request, and the
        # request → closure → cell → request cycle pins the payload
        # (up to a whole segment train) until a full gen-2 gc pass
        self._cancel_fn = None
        _progress.wake(self._event)      # coalesced under drain batches

    def _fail(self, err: BaseException) -> None:
        """ULFM (req_ft.c): complete the pending request in error —
        the matching send can never arrive from a dead peer."""
        self._error = err
        self._complete = True
        self._cancel_fn = None           # break the cancel-closure cycle
        _progress.wake(self._event)

    def test(self):
        if self._complete and self._error is not None:
            raise self._error
        return (True, self.status) if self._complete else (False, None)

    def wait(self, timeout: Optional[float] = None):
        if not self._event.wait(timeout if timeout is not None else 600):
            raise MPIError(ERR_PENDING,
                           "recv timed out waiting for a matching send")
        if self._error is not None:
            raise self._error
        from ompi_tpu.pml.pipeline import PipePayload
        if isinstance(self._result, PipePayload):
            # MPI completion means the data is PLACED: assemble the
            # segment train now so the store's multi-MB buffer is
            # released even if the caller never calls get()
            from ompi_tpu.pml.pipeline import maybe_resolve as _pr
            self._result = _pr(self._result)
        return self.status

    def get(self):
        """Wait (raising any stored ULFM error — the base contract)
        and resolve a device-rendezvous payload on THIS (consumer)
        thread — the pull must never run on a btl reader thread."""
        self.wait()
        from ompi_tpu.btl.devxfer import maybe_resolve
        from ompi_tpu.pml.pipeline import maybe_resolve as _pipe_resolve
        self._result = _pipe_resolve(maybe_resolve(self._result))
        return self._result


def thread_request(job) -> RankRequest:
    """Run ``job`` on a daemon worker thread; the returned request
    completes with the job's result, or in error through the same
    ``_fail`` path ULFM uses. The generic request-based-operation
    primitive (request-based RMA rput/rget, ``osc.h:269-279``)."""
    req = RankRequest(ANY_SOURCE, ANY_TAG)

    def run():
        try:
            req._deliver(_Msg(ANY_SOURCE, 0, job()))
        except BaseException as e:      # noqa: BLE001 — surfaced at wait
            req._fail(e)
    threading.Thread(target=run, daemon=True).start()
    return req


class CombineSlot:
    """An inline-combining receive slot (the ``btl_sendi`` role,
    ``opal/mca/btl/btl.h`` inline-send, applied to the receive side):
    btl reader threads park small collective contributions directly
    into the slot; the LAST arrival folds them in deterministic rank
    order and wakes the consumer exactly once. Collapses the per-round
    wakeup tax that made an 8 B per-rank allreduce cost ~18 pingpongs
    on a 1-core host."""

    __slots__ = ("_vals", "_need", "_fold", "_event", "_lock",
                 "_error", "result")

    def __init__(self, nranks: int, need: int, fold):
        self._vals: List[Any] = [None] * nranks   # by source rank
        self._need = need
        self._fold = fold                 # fold(ordered_values) -> result
        self._event = threading.Event()
        self._lock = threading.Lock()
        self._error: Optional[BaseException] = None
        self.result: Any = None

    def feed(self, src: int, value: Any) -> None:
        with self._lock:
            if self._vals[src] is not None or self._need <= 0:
                return                    # duplicate / already failed
            self._vals[src] = value
            self._need -= 1
            done = self._need == 0
        if done:
            # deterministic rank-ordered fold (MPI promises allreduce
            # returns the SAME value everywhere; arrival-order folding
            # of floats would not) — n tiny folds on this reader
            # thread beat one more cross-thread wakeup
            try:
                self.result = self._fold(self._vals)
            except BaseException as e:    # noqa: BLE001
                self._error = e
            _progress.wake(self._event)  # one coalesced consumer wake

    def put_own(self, rank: int, value: Any) -> None:
        """The caller's own contribution (never counted in _need)."""
        self._vals[rank] = value

    def fail(self, err: BaseException) -> None:
        with self._lock:
            self._need = -1
        self._error = err
        _progress.wake(self._event)

    def wait(self, timeout: float = 600):
        if not self._event.wait(timeout):
            raise MPIError(ERR_PENDING,
                           "combining collective timed out")
        if self._error is not None:
            raise self._error
        return self.result


class PerRankEngine:
    """Matching state for ONE rank of one communicator.

    ``comm`` provides ``cid``, ``size``, ``rank()``, and
    ``world_rank_of(local_rank)`` for endpoint addressing.
    """

    def __init__(self, comm, router: Router):
        self.comm = comm
        self.router = router
        self._lock = threading.Lock()
        self.unexpected: Dict[int, Deque[_Msg]] = {}   # src -> FIFO
        self._arrival: Deque[int] = deque()            # src arrival order
        self.posted: List[Tuple[int, int, RankRequest]] = []
        self._combine: Dict[int, CombineSlot] = {}     # tag -> slot
        # sub-eager dispatch cache: per-(dtype, shape) marshalled
        # descriptor templates for the small-message multicast path —
        # the control plane stops re-boxing the same 8 B shape on
        # every collective call (see send_small)
        self._small_desc: Dict[Tuple[str, tuple], dict] = {}
        # per-peer traffic accounting (the pml/monitoring role): THIS
        # rank's sends/receives by comm-local peer, consumed by
        # tools/profile's matrix (each rank holds its own rows in a
        # per-rank world; aggregate with comm.allgather)
        self.traffic: Dict[Tuple[int, int], List[int]] = {}
        router.register(comm.cid, self)

    # -- wire side -----------------------------------------------------
    def _incoming(self, header: dict, raw: bytes) -> None:
        d = header["desc"]
        if d.get("kind") == "devrndv":
            # descriptor-only frame: the device payload is pulled
            # lazily on the consumer thread (btl/devxfer)
            from ompi_tpu.btl.devxfer import DevPayload
            payload = DevPayload(self.router, d)
        elif d.get("kind") == "pipe":
            # pipelined-rendezvous init frame (pml/pipeline): matches
            # NOW with the right counts; the segment train assembles
            # on the consumer thread at resolve time
            from ompi_tpu.pml.pipeline import PipePayload
            payload = PipePayload(self.router, d)
        elif d.get("kind") == "shmseg":
            # zero-copy descriptor frame (btl/shmseg): adopt the
            # payload in place over the sender's shared slot; the
            # array's finalizer returns the slot when the receiver
            # drops its last reference
            from ompi_tpu.btl import shmseg as _shmseg
            payload = _shmseg.adopt(self.router.endpoint, d)
        else:
            payload = decode_payload(d, raw)
            # inline-combining fast path: a posted CombineSlot for this
            # tag absorbs the contribution right here on the reader
            # thread — no matching, no request, no per-message wakeup
            with self._lock:
                slot = self._combine.get(header["tag"])
            if slot is not None:
                slot.feed(header["src"], payload)
                return
        msg = _Msg(header["src"], header["tag"], payload,
                   ack=(header["wsrc"], header["ack_id"])
                   if header.get("ack_id") else None)
        with self._lock:
            for i, (src, tag, req) in enumerate(self.posted):
                if ((src == ANY_SOURCE or src == msg.src)
                        and (tag == ANY_TAG or tag == msg.tag)):
                    self.posted.pop(i)
                    matched = req
                    break
            else:
                self.unexpected.setdefault(msg.src, deque()).append(msg)
                self._arrival.append(msg.src)
                matched = None
        if matched is not None:
            self._ack(msg)
            matched._deliver(msg)

    def _ack(self, msg: _Msg) -> None:
        if msg.ack is not None:
            wsrc, aid = msg.ack
            self.router.send_ack(wsrc, aid)

    # -- inline-combining slots (small-message collective fast path) ---
    def post_combine(self, tag: int, nranks: int, need: int,
                     fold, own: Optional[Tuple[int, Any]] = None
                     ) -> CombineSlot:
        """Post a combining slot for one collective round. Must be
        posted before (or while) contributions arrive; ones that raced
        ahead sit in the unexpected queue and are drained here. The
        caller's own contribution goes in via ``own`` BEFORE the slot
        becomes visible — a fast peer may complete the fold before the
        caller runs another line."""
        slot = CombineSlot(nranks, need, fold)
        if own is not None:
            slot.put_own(*own)
        drained: List[_Msg] = []
        with self._lock:
            self._combine[tag] = slot
            for s, q in list(self.unexpected.items()):
                i = 0
                while i < len(q):
                    if q[i].tag == tag:
                        drained.append(q[i])
                        del q[i]
                        try:
                            self._arrival.remove(s)
                        except ValueError:
                            pass
                    else:
                        i += 1
        for m in drained:
            slot.feed(m.src, m.data)
        return slot

    def end_combine(self, tag: int) -> None:
        with self._lock:
            self._combine.pop(tag, None)

    def _take_unexpected(self, source: int, tag: int,
                         remove: bool = True) -> Optional[_Msg]:
        """Caller holds self._lock. Wildcard source scans in arrival
        order (the unexpected queue's FIFO across sources)."""
        srcs = (list(dict.fromkeys(self._arrival))
                if source == ANY_SOURCE else [source])
        for s in srcs:
            q = self.unexpected.get(s)
            if not q:
                continue
            for i, msg in enumerate(q):
                if tag == ANY_TAG or tag == msg.tag:
                    if remove:
                        del q[i]
                        try:
                            self._arrival.remove(s)
                        except ValueError:
                            pass
                    return msg
        return None

    # -- send side -----------------------------------------------------
    def send(self, data: Any, dest: int, tag: int = 0,
             synchronous: bool = False) -> Request:
        # telemetry gate: one attribute read when off; the histogram
        # times the full post-to-wire-handoff service (the degraded
        # self-health signal reads its p99)
        if _tele.active:
            hist = _tele.PML_SEND
            tok = hist.start()
            try:
                return self._send_traced(data, dest, tag, synchronous)
            finally:
                hist.observe(tok)
        return self._send_traced(data, dest, tag, synchronous)

    def _send_traced(self, data: Any, dest: int, tag: int = 0,
                     synchronous: bool = False) -> Request:
        # tracing gate: one attribute read when off (hooks event name
        # "pml_send" — the PERUSE/MPI_T stream and the trace agree);
        # cid rides in args so pt2pt spans stay out of the collective
        # sequence space the attribution layer groups on
        if _trace.active:
            tok = _trace.begin("pml_send", cid=None,
                               cc=str(self.comm.cid), dest=dest, tag=tag)
            try:
                return self._send_impl(data, dest, tag, synchronous)
            finally:
                _trace.end(tok)
        return self._send_impl(data, dest, tag, synchronous)

    def _send_impl(self, data: Any, dest: int, tag: int = 0,
                   synchronous: bool = False) -> Request:
        if dest == PROC_NULL:
            return Request.completed()
        if not (0 <= dest < self.comm.size):
            raise MPIError(ERR_RANK, f"bad destination rank {dest}")
        if not isinstance(tag, int) or tag < 0:
            raise MPIError(ERR_TAG, f"send tag must be an int >= 0, "
                                    f"got {tag!r}")
        from ompi_tpu.runtime import ft
        if ft.is_failed(self.comm.world_rank_of(dest)):
            # symmetric with the recv fail-fast: no silent buffering
            # into a dead socket, no raw OSError later
            from ompi_tpu.core.errhandler import ERR_PROC_FAILED
            raise MPIError(ERR_PROC_FAILED,
                           f"send peer rank {dest} has failed")
        # protocol switch (pml_ob1_sendreq.h:389-460): large device
        # arrays ride the PJRT transfer plane (register + descriptor-
        # only header, receiver pulls D2D); everything else goes
        # eager copy over the host byte path
        from ompi_tpu.btl import devxfer
        dev_desc = devxfer.try_register(self.router, data)
        if dev_desc is not None:
            desc, raw = dev_desc, b""
            wire_bytes = int(data.nbytes)   # moved out-of-band (D2D)
        else:
            # host byte path, fastest plane first: same-host bulk
            # payloads pack ONCE into a shared segment slot and ship a
            # descriptor (btl/shmseg) — shm beats compression for
            # pt2pt because there are no wire bytes to save. None
            # means the plane declined (off, cross-host, pool dry) and
            # nothing touched the wire.
            from ompi_tpu.btl import shmseg as _shmseg
            zreq = _shmseg.maybe_send_zerocopy(self, data, dest, tag,
                                               synchronous)
            if zreq is not None:
                return zreq
            # then the segment-pipelined rendezvous (pml/pipeline,
            # docs/LARGEMSG.md); again None means nothing touched the
            # wire — fall through to the unchanged eager path
            from ompi_tpu.pml import pipeline as _pipeline
            preq = _pipeline.maybe_send_pipelined(self, data, dest,
                                                  tag, synchronous)
            if preq is not None:
                return preq
            desc, raw = encode_payload(data)
            wire_bytes = len(raw)
        me = self.comm.rank()
        t = self.traffic.setdefault((me, dest), [0, 0])
        t[0] += 1
        t[1] += wire_bytes
        header = {"cid": self.comm.cid, "src": me,
                  "tag": tag, "desc": desc}
        ent = aid = None
        if synchronous:
            aid, ent = self.router.new_ack()
            header["ack_id"] = aid
            header["wsrc"] = self.comm.world_rank_of(self.comm.rank())
        _ft_send(self.router.endpoint, self.comm.world_rank_of(dest),
                 header, raw)
        if ent is not None and not ent[0].wait(600):
            self.router.cancel_ack(aid)
            raise MPIError(ERR_PENDING,
                           "ssend timed out waiting for the receive")
        return Request.completed()

    def send_small(self, data: Any, dests, tag: int) -> None:
        """Sub-eager multicast fast path (the combined small-message
        collectives): marshal the payload ONCE, reuse a cached
        per-(dtype, shape) descriptor, and push one frame per
        destination with none of the per-call protocol work the
        general ``send`` must do (devxfer registration, sync-ack
        plumbing, per-dest re-encoding). ``dests`` are comm-local
        ranks, validated by the collective's own construction; the
        caller's rank must not appear in ``dests`` (self-contributions
        go through ``CombineSlot.put_own``)."""
        if _tele.active:
            hist = _tele.PML_SEND
            tok = hist.start()
            try:
                return self._send_small_traced(data, dests, tag)
            finally:
                hist.observe(tok)
        return self._send_small_traced(data, dests, tag)

    def _send_small_traced(self, data: Any, dests, tag: int) -> None:
        if _trace.active:
            tok = _trace.begin("pml_send", cid=None,
                               cc=str(self.comm.cid), tag=tag,
                               ndest=(len(dests)
                                      if hasattr(dests, "__len__")
                                      else -1), small=True)
            try:
                return self._send_small_impl(data, dests, tag)
            finally:
                _trace.end(tok)
        return self._send_small_impl(data, dests, tag)

    def _send_small_impl(self, data: Any, dests, tag: int) -> None:
        if isinstance(data, np.generic):
            # numpy scalars ride the raw nd encoding as 0-d arrays —
            # a pickle round trip costs 4x the marshal of the whole
            # frame (the residual in the round-6 scalar 8 B row); the
            # collective's epilogue restores the scalar type
            data = np.asarray(data)
        if isinstance(data, np.ndarray):
            arr = data if data.flags.c_contiguous \
                else np.ascontiguousarray(data)
            key = (arr.dtype.str, arr.shape)
            desc = self._small_desc.get(key)
            if desc is None:
                desc = self._small_desc[key] = {
                    "kind": "nd", "dtype": arr.dtype.str,
                    "shape": arr.shape}
            raw = arr.tobytes()
        else:
            desc, raw = encode_payload(data)
        me = self.comm.rank()
        header = {"cid": self.comm.cid, "src": me, "tag": tag,
                  "desc": desc}
        nraw = len(raw)
        endpoint = self.router.endpoint
        world_of = self.comm.world_rank_of
        from ompi_tpu.runtime import ft
        from ompi_tpu.core.errhandler import ERR_PROC_FAILED
        for dest in dests:
            if ft.is_failed(world_of(dest)):
                raise MPIError(ERR_PROC_FAILED,
                               f"send peer rank {dest} has failed")
            t = self.traffic.setdefault((me, dest), [0, 0])
            t[0] += 1
            t[1] += nraw
            # the bml copies the header before stamping its sequence
            # number, so one template serves every destination
            _ft_send(endpoint, world_of(dest), header, raw)

    def bind_small_multicast(self, example: Any, dests) -> Any:
        """Pre-bound sub-eager multicast (the persistent-collective
        staging prebind, coll/persistent): the descriptor template,
        world-rank map and per-peer traffic rows resolve ONCE here;
        each send is the contiguous byte copy, the per-peer liveness
        check (which must stay per-call — peers die between rounds),
        and the frame pushes. The registered buffer's (dtype, shape)
        is the persistent contract; a refill that changes either
        falls back to a freshly-built descriptor."""
        arr = np.asarray(example)
        key = (arr.dtype.str, arr.shape)
        desc = self._small_desc.get(key)
        if desc is None:
            desc = self._small_desc[key] = {
                "kind": "nd", "dtype": arr.dtype.str,
                "shape": arr.shape}
        me = self.comm.rank()
        peers = [(d, self.comm.world_rank_of(d),
                  self.traffic.setdefault((me, d), [0, 0]))
                 for d in dests]
        endpoint = self.router.endpoint
        cid = self.comm.cid
        from ompi_tpu.core.errhandler import ERR_PROC_FAILED
        from ompi_tpu.runtime import ft

        def send(data: Any, tag: int) -> None:
            a = np.asarray(data)
            if not a.flags.c_contiguous:
                a = np.ascontiguousarray(a)
            d0 = desc
            if (a.dtype.str, a.shape) != key:   # contract violated:
                d0 = {"kind": "nd", "dtype": a.dtype.str,   # stay
                      "shape": a.shape}                     # correct
            raw = a.tobytes()
            header = {"cid": cid, "src": me, "tag": tag, "desc": d0}
            nraw = len(raw)
            for dest, wdest, t in peers:
                if ft.is_failed(wdest):
                    raise MPIError(ERR_PROC_FAILED,
                                   f"send peer rank {dest} has failed")
                t[0] += 1
                t[1] += nraw
                _ft_send(endpoint, wdest, header, raw)
        return send

    # -- receive side --------------------------------------------------
    def _cancel_posted(self, req: RankRequest) -> None:
        with self._lock:
            present = any(e[2] is req for e in self.posted)
            self.posted = [e for e in self.posted if e[2] is not req]
        if present:
            req.cancelled = True
            req._deliver(_Msg(ANY_SOURCE, ANY_TAG, None))

    def irecv(self, source: int = ANY_SOURCE,
              tag: int = ANY_TAG) -> RankRequest:
        req = RankRequest(source, tag)
        req._cancel_fn = lambda: self._cancel_posted(req)
        if source == PROC_NULL:
            req._deliver(_Msg(PROC_NULL, tag, None))
            return req
        with self._lock:
            msg = self._take_unexpected(source, tag)
            if msg is None:
                self.posted.append((source, tag, req))
        if msg is not None:
            self._ack(msg)
            req._deliver(msg)
            return req
        # a receive posted AFTER the peer's death can never match
        # (req_ft.c: fail fast instead of hanging); in-flight failures
        # are flushed by _peer_failed
        if source != ANY_SOURCE and 0 <= source < self.comm.size:
            from ompi_tpu.runtime import ft
            if ft.is_failed(self.comm.world_rank_of(source)):
                self._drop_posted(req)
                from ompi_tpu.core.errhandler import ERR_PROC_FAILED
                req._fail(MPIError(ERR_PROC_FAILED,
                                   f"receive peer rank {source} has "
                                   f"failed"))
        return req

    def _drop_posted(self, req: RankRequest) -> None:
        with self._lock:
            self.posted = [e for e in self.posted if e[2] is not req]

    def _peer_failed(self, world_rank: int) -> None:
        """Complete pending NAMED receives on the dead peer in error.
        Wildcard (ANY_SOURCE) receives stay posted and matchable — a
        live sender may still satisfy them (the reference's
        PROC_FAILED_PENDING keeps the request completable,
        req_ft.c; failing them outright would strand an in-flight
        message from a healthy peer). A wildcard that only the dead
        peer could have matched eventually times out."""
        if getattr(self.comm, "no_peer_map", False):
            return                   # intercomm engine: local deaths
        local = next((i for i in range(self.comm.size)
                      if self.comm.world_rank_of(i) == world_rank), None)
        if local is None:
            return
        from ompi_tpu.core.errhandler import ERR_PROC_FAILED
        with self._lock:
            hit = [e for e in self.posted if e[0] == local]
            self.posted = [e for e in self.posted if e not in hit]
            # combining slots still waiting on the dead peer's
            # contribution can never complete
            slots = [s for s in self._combine.values()
                     if 0 <= local < len(s._vals)
                     and s._vals[local] is None]
        for (_, _, req) in hit:
            req._fail(MPIError(
                ERR_PROC_FAILED,
                f"peer rank {local} died while this receive was "
                f"pending (shrink or restrict to live peers to "
                f"continue)"))
        for s in slots:
            s.fail(MPIError(
                ERR_PROC_FAILED,
                f"peer rank {local} died during a combining "
                f"collective"))

    def _flush_all(self, make_err) -> None:
        """Revocation flush (MPIX_Comm_revoke): complete EVERY pending
        operation on this engine in error — wildcards included. Unlike
        a single peer death, a revoked communicator can never match
        anything again (req_ft.c's revocation branch), so nothing may
        stay posted."""
        with self._lock:
            hit, self.posted = self.posted, []
            slots = [s for s in self._combine.values()
                     if any(v is None for v in s._vals)]
        for (_, _, req) in hit:
            req._fail(make_err())
        for s in slots:
            try:
                s.fail(make_err())
            except Exception:            # noqa: BLE001
                pass

    def recv(self, source: int = ANY_SOURCE, tag: int = ANY_TAG,
             timeout: Optional[float] = None) -> Tuple[Any, Status]:
        # telemetry: the recv histogram's duration IS blocked-waiting;
        # it doubles as the health monitor's per-peer wait ingress (the
        # matched source is only known at completion, so attribution
        # happens after the observe)
        if _tele.active:
            hist = _tele.PML_RECV
            tok = hist.start()
            try:
                data, st = self._recv_traced(source, tag, timeout)
            finally:
                hist.observe(tok)
            from ompi_tpu.telemetry import health as _health
            _health.note_wait(self.comm.world_rank_of(st.source),
                              _time.perf_counter() - tok)
            return data, st
        return self._recv_traced(source, tag, timeout)

    def _recv_traced(self, source: int = ANY_SOURCE, tag: int = ANY_TAG,
                     timeout: Optional[float] = None
                     ) -> Tuple[Any, Status]:
        # the span covers post-to-completion: its duration IS the
        # blocked-waiting time a late sender costs this rank
        if _trace.active:
            tok = _trace.begin("pml_recv", cid=None,
                               cc=str(self.comm.cid), src=source,
                               tag=tag)
            try:
                req = self.irecv(source, tag)
                st = req.wait(timeout)
                return req.get(), st
            finally:
                _trace.end(tok)
        req = self.irecv(source, tag)
        st = req.wait(timeout)
        return req.get(), st

    # -- probe ---------------------------------------------------------
    def iprobe(self, source: int = ANY_SOURCE, tag: int = ANY_TAG
               ) -> Tuple[bool, Optional[Status]]:
        with self._lock:
            msg = self._take_unexpected(source, tag, remove=False)
        if msg is None:
            return False, None
        return True, Status(source=msg.src, tag=msg.tag,
                            count=int(getattr(msg.data, "size", 1) or 1),
                            nbytes=int(getattr(msg.data, "nbytes", -1)))

    def probe(self, source: int = ANY_SOURCE, tag: int = ANY_TAG,
              timeout: float = 600, poll: float = 0.0005) -> Status:
        """Blocking probe: spin-wait (with backoff) until a matching
        message is pending — the opal_progress poll loop."""
        import time
        deadline = time.monotonic() + timeout
        while True:
            ok, st = self.iprobe(source, tag)
            if ok:
                return st
            if time.monotonic() > deadline:
                raise MPIError(ERR_PENDING, "probe timed out")
            time.sleep(poll)
            poll = min(poll * 2, 0.01)

    def mprobe(self, source: int = ANY_SOURCE, tag: int = ANY_TAG,
               timeout: float = 600) -> _Msg:
        import time
        deadline = time.monotonic() + timeout
        while True:
            with self._lock:
                msg = self._take_unexpected(source, tag)
            if msg is not None:
                self._ack(msg)
                return msg
            if time.monotonic() > deadline:
                raise MPIError(ERR_PENDING, "mprobe timed out")
            time.sleep(0.0005)

    @staticmethod
    def mrecv(msg: _Msg) -> Tuple[Any, Status]:
        from ompi_tpu.btl.devxfer import maybe_resolve
        from ompi_tpu.pml.pipeline import maybe_resolve as _pipe_resolve
        data = _pipe_resolve(maybe_resolve(msg.data))
        return data, Status(source=msg.src, tag=msg.tag,
                            count=int(getattr(data, "size", 1) or 1),
                            nbytes=int(getattr(data, "nbytes", -1)))

    def close(self) -> None:
        self.router.unregister(self.comm.cid)
