"""ompi_tpu.trace — per-rank collective/pt2pt tracing.

Counters (SPC, pvars, monitoring tables) answer "how many / how much";
this subsystem answers "when / who was late": a fixed-capacity span
ring fed by begin/end instrumentation at the collective entry points
(coll composer + per-rank interposition), pt2pt (pml/perrank), the btl
ctl flush paths (tcp/sm) and progress wakeups — aligned across
controllers by mpisync offsets, exported as Perfetto JSON, and
attributed per collective (arrival skew, critical rank, blocked vs
in-op time). See docs/OBSERVABILITY.md.

Hot-path contract: everything is gated on ``core.active`` (one module
attribute read when off — no span allocation, no locking beyond the
existing SPC path); the library's layer spans also reach a recording
``jax.profiler`` session, gated on ``active or recording()``.
"""
from ompi_tpu.trace import attribution, perfetto          # noqa: F401
from ompi_tpu.trace.core import (                          # noqa: F401
    begin, disable, dump, enable, end, instant, load_dump,
    maybe_enable_from_var, process_rank, recording, reset, set_process_rank,
    span, span_dicts, spans, stats, tracing_enabled, wrap_coll_vtable,
)
from ompi_tpu.trace.ring import Span, SpanRing            # noqa: F401


def is_active() -> bool:
    """Live gate (hot paths read ``trace.core.active`` directly)."""
    from ompi_tpu.trace import core
    return core.active
