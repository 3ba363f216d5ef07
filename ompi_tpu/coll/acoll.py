"""coll/acoll — architecture-aware collective tuning hints.

Behavioral spec: ``ompi/mca/coll/acoll`` — AMD "zen-aware" intra-node
collectives whose value is entirely in ENCODING THE CHIP TOPOLOGY
(CCX/CCD cache domains, NUMA fabric) into algorithm and segmentation
choices (``docs/tuning-apps/collectives/acoll.rst``).

TPU-native re-design: the architecture that matters here is the TPU
generation's interconnect shape — v2/v3/v4/v5p are 2-D/3-D tori with
wraparound links, v5e is a 2-D mesh, v6e widens the links — which
changes the right segment size for pipelined schedules and the right
ladder arity for n-level hierarchical composition (coll/xhc). This
component detects the generation from the PJRT ``device_kind`` string
and installs generation defaults for ``coll_xla_segsize`` and the xhc
ladder arity, at DEFAULT precedence only: any user/env/file setting
wins, exactly how the reference's per-arch tables defer to explicit
tuning.

Provenance (the decision-table discipline): every hint below is
CONJECTURE from interconnect arithmetic (link count x per-link
bandwidth => segment size that fills the pipe at ~1 ms granularity),
not multi-chip measurement — one visible chip cannot A/B an ICI mesh.
They are starting points for the dynamic-rules retuning workflow, and
``ompi_info``'s var dump shows whether a hint or a user value is live.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

from ompi_tpu.coll.framework import coll_framework
from ompi_tpu.mca import var
from ompi_tpu.mca.base import Component

# generation -> (segsize bytes, ladder arity). Keys are matched as
# substrings of the PJRT device_kind (e.g. "TPU v5 lite", "TPU v4").
# arity None = leave coll_xhc_levels alone (xhc's locality-derived
# ladder stays in charge).
GENERATION_HINTS: Dict[str, Tuple[int, Optional[int]]] = {
    # 3-D torus, 6 links/chip: deeper pipelines pay off -> larger segs
    "v4": (4 << 20, 4),
    "v5p": (4 << 20, 4),
    # 2-D mesh (no wraparound), 4 links/chip: shorter pipes
    "v5 lite": (1 << 20, 2),
    "v5e": (1 << 20, 2),
    # wider links: fewer, larger segments
    "v6": (8 << 20, 4),
    # host backend (the CI mesh): MEASURED, not conjecture — the
    # round-4 32 MB sweep on the 8-rank CPU mesh put ring_segmented at
    # 4 MB segments ahead of both 1 MB segments and the plain ring
    # (one-off sweep also covered 256 KB/16 MB, both worse). No ladder
    # hint — xhc keeps its locality fallback.
    "cpu": (4 << 20, None),
}


def detect_generation(device_kind: str) -> Optional[str]:
    dk = device_kind.lower()
    for key in sorted(GENERATION_HINTS, key=len, reverse=True):
        if key in dk:
            return key
    return None


class AcollComponent(Component):
    """Hints provider, not a module provider: comm_query never wins —
    the component's entire effect is the generation defaults it
    installs at register time (deferring to any explicit setting)."""

    name = "acoll"

    _hints_done = False

    def register_params(self) -> None:
        var.var_register("coll", "acoll", "enable", vtype="bool",
                         default=True,
                         help="Install TPU-generation-aware default "
                              "tuning (segsize, ladder arity) detected "
                              "from the PJRT device kind; explicit "
                              "user/env/file settings always win")
        var.var_register("coll", "acoll", "detected", vtype="str",
                         default="",
                         help="The generation key the detector matched "
                              "(introspection; empty = no match)")

    def _ensure_hints(self) -> None:
        """Lazy (first selection): every other component's vars are
        registered by then, so DEFAULT-precedence detection is
        well-defined."""
        if AcollComponent._hints_done:
            return
        AcollComponent._hints_done = True
        if not var.var_get("coll_acoll_enable", True):
            return
        try:
            import jax
            kind = getattr(jax.devices()[0], "device_kind", "") or \
                jax.devices()[0].platform
        except Exception:               # noqa: BLE001
            return
        gen = detect_generation(str(kind))
        if gen is None:
            return
        segsize, arity = GENERATION_HINTS[gen]
        var.var_set("coll_acoll_detected", gen)
        # DEFAULT-precedence install: applied only while each var still
        # sits at its registration default from every other source
        if var.var_source("coll_xla_segsize") == var.SOURCE_DEFAULT:
            var.var_set("coll_xla_segsize", segsize,
                        source=var.SOURCE_DEFAULT)
        # the ladder-arity half: xhc falls back to locality when its
        # levels var is empty; the generation hint supplies a uniform
        # arity ladder instead (still overridable by any explicit
        # coll_xhc_levels setting)
        if (arity is not None
                and var.var_source("coll_xhc_levels")
                == var.SOURCE_DEFAULT):
            var.var_set("coll_xhc_levels", str(arity),
                        source=var.SOURCE_DEFAULT)

    def comm_query(self, comm):
        self._ensure_hints()
        return None                     # hints only; never a module


coll_framework.register(AcollComponent())
