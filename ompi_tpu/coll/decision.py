"""coll decision tables — fixed per-collective algorithm selection.

Re-design of coll/tuned's decision functions
(``coll_tuned_decision_fixed.c:40-45``; the allreduce rule block at
``:55-120`` is 1,644 lines of comm-size x message-size switch points
"averaged across contributors' clusters"). On TPU the honest default is
different: XLA's ``direct`` lowering already emits an ICI-optimal
schedule, so the fixed table only diverges from ``direct`` where an
explicit schedule is semantically or structurally better (multi-host
tiers, root-targeted traffic). The *structure* —
ordered (min_comm_size, min_message_bytes) -> algorithm rules, first
match from the most specific — mirrors the reference so that operators
can retune via the dynamic-rules JSON exactly as tuned's dynamic file
does (``coll_tuned_component.c:187-191``).

Rule shape: ``{func: [[min_comm_size, min_bytes, algorithm], ...]}`` —
rules are scanned in order, the *last* rule whose thresholds are both
satisfied wins (so files list rules from general to specific, the way
the reference's nested size switches read).

Provenance (what each row rests on):

- **measured on the chip**: the TPU allreduce row, ``direct`` at every
  size. On a v5e 2x2 host (four chips over ICI) XLA lowers a
  reduce-scatter to a full all-reduce plus a slice, so the two-phase
  Rabenseifner schedule paid for ``direct``'s one all-reduce and then
  for an all-gather and an HBM round trip besides; forced A/B pairs at
  64, 128 and 256 MiB per rank, f32 SUM, all went to ``direct``, by
  1.6x to 2.0x in time per call.
- **measured on the chip**: the bcast row, ``direct`` (the root-masked
  psum, one all-reduce) at every size. A scatter+allgather bcast's
  psum_scatter lowers on v5e 2x2 to the same full all-reduce, and its
  all-gather and loop came on top; forced A/B pairs at 64, 128 and
  256 MiB per rank, f32 from root 0, all went to ``direct``, 2.14 /
  3.73 / 6.54 ms against 4.57 / 8.77 / 17.18 ms per call. The ppermute
  trees lost too at 256 MiB: binomial 14.9 ms, pipeline 26.8 ms.
- **conjecture**: the root-targeted TPU rows (reduce/gather/scatter
  above 64 KiB) encode wire-byte arithmetic, not multi-chip
  measurements. They are the retuning surface for real hardware via the
  dynamic-rules JSON, exactly tuned's workflow.
- **unsupported**: the CPU symmetric fallback for reduce/gather/scatter
  cites a host-mesh A/B whose record no longer exists; it stands until
  a measurement replaces it.
- the multihost ``hier`` rows are structural (two-tier traffic shape),
  exercised for correctness across a real process boundary
  (tests/multiproc_child.py) but not latency-measured.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

# Fixed decision tables. Every entry must name an algorithm the xla
# component implements for that collective (see the coll_xla_<func>_
# algorithm enumerators in coll/xla.py).
FIXED_RULES: Dict[str, List[Sequence]] = {
    # One fused collective (XLA's own schedule) at every size: on ICI
    # it beats the explicit redscat+allgather shape even at 256 MiB
    # per rank (measured, see the module docstring).
    "allreduce": [[0, 0, "direct"]],
    # The root-masked psum at every size: on ICI it beats
    # scatter+allgather even at 256 MiB per rank (measured, see the
    # module docstring).
    "bcast": [[0, 0, "direct"]],
    "allgather": [[0, 0, "direct"]],
    "alltoall": [[0, 0, "direct"]],
    "reduce_scatter_block": [[0, 0, "direct"]],
    "barrier": [[0, 0, "direct"]],
    # Root-targeted collectives (round 2): below the threshold one
    # fused symmetric op wins on latency; above it the root-directed
    # schedule wins on wire bytes (reduce: 1/2, gather: 1/n, scatter:
    # 1/n of the symmetric alias). The crossovers are conjecture (see
    # the module docstring), retunable via the dynamic-rules file.
    "reduce": [
        [0, 0, "alias"],
        [0, 64 << 10, "rabenseifner_root"],
    ],
    "gather": [
        [0, 0, "allgather"],
        [0, 64 << 10, "binomial"],
    ],
    "scatter": [
        [0, 0, "direct"],
        [0, 64 << 10, "binomial"],
    ],
}

# Algorithms that reorder floating-point combines relative to rank
# order; selection must fall back to 'direct' for non-commutative ops
# (the reference documents the same constraint per algorithm,
# coll_base_allreduce.c:291-294). reduce/in_order_binary is
# deliberately ABSENT: it is the one tree whose combine order equals
# rank order — the non-commutative-correct choice
# (coll_base_functions.h:276).
REORDERING = frozenset({"hier", "rabenseifner_root"})


def _match(rules: List[Sequence], comm_size: int, nbytes: int) -> str:
    alg = "direct"
    for rule in rules:
        try:
            if comm_size >= rule[0] and nbytes >= rule[1]:
                alg = str(rule[2])
        except (IndexError, TypeError):
            continue                  # malformed user rule: skip it
    return alg


_SYMMETRIC_FALLBACK = {"reduce": "alias", "gather": "allgather",
                       "scatter": "direct"}


def effective_rules(func: str, multihost: bool = False,
                    dynamic: Dict[str, Dict] | None = None,
                    platform: str = "") -> List[Sequence]:
    """The rule list :func:`decide` actually scans for ``func`` after
    every override source (dynamic file, multihost structure, measured
    platform branches) — the single source both ``decide`` and the
    introspection table read, so the two can't drift."""
    rules = None
    if dynamic:
        rules = dynamic.get(func, {}).get("algorithm_rules")
    if rules:
        return rules
    if multihost and func in ("allreduce", "bcast", "allgather",
                              "reduce_scatter_block", "barrier"):
        # Multi-host: the two-tier composition keeps bulk traffic on
        # ICI and only chunk-sized exchanges on DCN (coll/han's role).
        # The xla module demotes to 'direct' where hier doesn't apply
        # (ragged groups, non-sum reduce_scatter).
        return [[0, 0, "hier"]]
    if func in _SYMMETRIC_FALLBACK:
        if multihost:
            # Cross-process ppermute chains serialize on the DCN tier;
            # the fused symmetric ops let XLA schedule the slow tier.
            return [[0, 0, _SYMMETRIC_FALLBACK[func]]]
        if platform == "cpu":
            # On the shared-memory host backend "wire bytes saved"
            # cost nothing; a host-mesh A/B once had the log-round
            # root-targeted schedules losing to one fused op at every
            # size (its record is gone). The root-targeted defaults
            # below are for ICI, where the traffic asymmetry is real.
            return [[0, 0, _SYMMETRIC_FALLBACK[func]]]
    rules = FIXED_RULES.get(func)
    if not rules:
        return [[0, 0, "direct"]]
    return rules


def decide(func: str, comm_size: int, nbytes: int, multihost: bool,
           dynamic: Dict[str, Dict] | None = None,
           platform: str = "") -> str:
    """Pick an algorithm for ``func`` on a ``comm_size``-rank comm moving
    ``nbytes`` per rank. ``dynamic`` is the tuned dynamic-rules dict; a
    ``{func: {"algorithm_rules": [...]}}`` entry overrides the fixed
    table wholesale (the reference's dynamic file has the same
    override-don't-merge semantics)."""
    return _match(effective_rules(func, multihost, dynamic, platform),
                  comm_size, nbytes)


# -- compression gating (ompi_tpu/compress; EQuARX-style) -------------------
# Only these collectives have a compressed schedule, and only these
# dtypes quantize meaningfully (integer payloads would need a lossless
# codec; f16 is already half-width).
COMPRESSIBLE = frozenset({"allreduce", "allgather",
                          "reduce_scatter_block"})
COMPRESS_DTYPES = frozenset({"float32", "float64", "bfloat16"})


def compress_eligible(func: str, nbytes: int, dtype_name: str,
                      op=None) -> bool:
    """True when the (func, per-rank payload, dtype, op) tuple takes
    the compressed path: the MCA var is on, the payload is a large
    eligible float, and the reduction (if any) is a sum — MPI
    reduction-op semantics for every other op fall back to the
    uncompressed path (dequantized partial maxima, products etc. would
    silently change the documented error model)."""
    from ompi_tpu import compress
    if not compress.enabled():
        return False
    if func not in COMPRESSIBLE:
        return False
    if str(dtype_name) not in COMPRESS_DTYPES:
        return False
    if nbytes < compress.min_bytes():
        return False
    if op is not None and func != "allgather" \
            and getattr(op, "xla_prim", None) != "sum":
        return False
    return True


def compression_rules() -> Dict[str, List[Sequence]]:
    """Effective compression rows (after MCA overrides), in the same
    [min_comm_size, min_bytes, algorithm] shape as the fixed tables;
    empty when ``mpi_base_compress`` is off."""
    from ompi_tpu import compress
    if not compress.enabled():
        return {}
    alg = f"compressed:{compress.codec_name()}"
    return {func: [[0, compress.min_bytes(), alg]]
            for func in sorted(COMPRESSIBLE)}


# -- large-message pipeline gating (ompi_tpu/pml/pipeline) ------------------
# Host-tier collectives with a segment-pipelined schedule
# (core/rankcomm): the ring allreduce and chain bcast whose chunk hops
# ride the pml's pipelined rendezvous (docs/LARGEMSG.md).
PIPELINED: Dict[str, str] = {"allreduce": "pipelined_ring",
                             "bcast": "pipelined_chain"}


def pipeline_rules() -> Dict[str, List[Sequence]]:
    """Effective segment-pipeline rows in the fixed-table shape; empty
    when ``mpi_base_pipeline_enable`` is off (off = byte-identical
    serial dispatch). Two ranks minimum: a 1-rank 'ring' is a copy."""
    from ompi_tpu.pml import pipeline as _pl
    if not _pl.enabled():
        return {}
    mb = _pl.min_bytes()
    return {func: [[2, mb, alg]]
            for func, alg in sorted(PIPELINED.items())}


def pipeline_plan(nbytes: int, rails: int = 1,
                  rail_gbps: "float | None" = None) -> Dict[str, int]:
    """Segment size and rail count for one ``nbytes`` pipelined
    transfer: segments sized to carry ~2 ms of wire time at the probed
    per-rail bandwidth (``btl/bml._probe_stream``'s tcp estimate,
    recorded once in ``probe_basis['rail_gbps']`` and reused here
    instead of re-probing), clamped to [256 KiB, 8 MiB] — grown toward
    ``pipeline_depth`` segments per train (up to the ceiling), and
    never fewer than ~4. The segment-count floor exists because the
    window must fill before any overlap exists; the growth rule
    because each segment costs a fixed slice of host CPU (header,
    syscall, rail-thread wake), and past a full window extra segments
    only add that overhead — measured on the paced tier, 4x8 MiB
    beats 8x4 MiB by ~15% end to end."""
    seg = 1 << 20
    if rail_gbps:
        seg = int(float(rail_gbps) * 1e9 * 0.002)
    seg = max(256 << 10, min(8 << 20, seg))
    from ompi_tpu.pml import pipeline as _pl
    seg = max(seg, min(8 << 20, int(nbytes) // max(1, _pl.depth())))
    seg = min(seg, max(64 << 10, int(nbytes) // 4))
    return {"segment_bytes": int(seg), "rails": max(1, int(rails))}


# -- zero-copy shared-segment fold gating (ompi_tpu/btl/shmseg) -------------
# Node-local collectives with an in-segment schedule (core/rankcomm):
# partner shards are folded directly in shared memory — reduce-scatter
# over segment slices, then in-place allgather (docs/LARGEMSG.md).
SHM_FOLDS: Dict[str, str] = {"allreduce": "shm_fold"}


def shm_rules() -> Dict[str, List[Sequence]]:
    """Effective in-segment fold rows in the fixed-table shape; empty
    when ``mpi_base_shm_zerocopy`` is off (off = byte-identical ring
    dispatch). Two ranks minimum: a 1-rank fold is a copy."""
    from ompi_tpu.btl import shmseg as _shm
    if not _shm.enabled():
        return {}
    mb = _shm.min_bytes()
    return {func: [[2, mb, alg]]
            for func, alg in sorted(SHM_FOLDS.items())}


# -- persistent/bucket gating (ompi_tpu/coll/persistent) --------------------
def persistent_rules() -> Dict[str, List[Sequence]]:
    """The pre-bound persistent-plan rows (MPI-4 ``*_init`` family),
    keyed ``<func>_init``: one row per collective whose init builds a
    launch-only plan — algorithm decided, executable compiled, staging
    bound at init (docs/PERSISTENT.md). Unconditional capability, so
    the rows are always present."""
    from ompi_tpu.coll import persistent as _p
    return {f"{func}_init": [[0, 0, "persistent_prebound"]]
            for func in _p.PERSISTENT_FUNCS}


def bucket_rules() -> Dict[str, List[Sequence]]:
    """Effective bucket-fusion rows in the fixed-table shape; empty
    when ``mpi_base_bucket`` is off (off = byte-identical unfused
    dispatch). The threshold is a CEILING — payloads above
    ``mpi_base_bucket_bytes`` never bucket — encoded in the algorithm
    label since the rule shape only carries floors."""
    from ompi_tpu.coll import persistent as _p
    if not _p.bucket_enabled():
        return {}
    b = _p.bucket_bytes()
    return {func: [[0, 0, f"bucket_fuse:<={b}B"]]
            for func in sorted(_p.FUSED_FUNCS)}


def decision_table(comm_size: int = 0, multihost: bool = False,
                   dynamic: Dict[str, Dict] | None = None,
                   platform: str = "") -> Dict[str, List[Sequence]]:
    """The *effective* selection table, after every override source:
    the per-func MCA algorithm pins (``coll_xla_<func>_algorithm``),
    the dynamic-rules file, the multihost/platform branches, and the
    compression rows (present only when ``mpi_base_compress`` is on).
    This is the introspection surface ``api/tool.decision_table``
    exposes — asking which algorithm a (func, size, nbytes) tuple picks
    no longer requires calling the collective."""
    from ompi_tpu.mca import var as _var
    table: Dict[str, List[Sequence]] = {}
    funcs = sorted(set(FIXED_RULES) | {"scan"})
    for func in funcs:
        pinned = _var.var_get(f"coll_xla_{func}_algorithm", "auto")
        if pinned not in (None, "auto"):
            table[func] = [[0, 0, str(pinned)]]
        else:
            table[func] = [list(r) for r in effective_rules(
                func, multihost, dynamic, platform)]
    for func, rows in compression_rules().items():
        table[func] = table[func] + [list(r) for r in rows]
    for func, rows in bucket_rules().items():
        table[func] = table[func] + [list(r) for r in rows]
    for func, rows in pipeline_rules().items():
        table[func] = table[func] + [list(r) for r in rows]
    for func, rows in shm_rules().items():
        table[func] = table[func] + [list(r) for r in rows]
    for func, rows in persistent_rules().items():
        table[func] = [list(r) for r in rows]
    return table
