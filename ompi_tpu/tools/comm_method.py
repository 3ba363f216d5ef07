"""hook/comm_method — print the per-communicator selection table.

Behavioral spec: the reference's ``ompi/mca/hook/comm_method`` (1,237
LoC) prints, at init/finalize, a rank x rank matrix of which transport
(pml/btl) serves each peer pair plus which coll components were
selected, so operators can confirm the fast path is actually in use.

TPU-native re-design: there is one data plane (XLA over ICI), so the
peer-pair matrix degenerates into the communicator -> mesh binding; the
interesting selection surface is the per-function coll vtable (which
component won each collective) and the device tier each rank's shard
lives on. ``table(comm)`` returns that; the CLI prints it. Enable the
init-time print the way the reference does, via the MCA var
``hook_comm_method_display`` (reference: ``hook_comm_method_verbose``).
"""
from __future__ import annotations

from typing import Dict

from ompi_tpu.mca import var

var.var_register(
    "hook", "comm_method", "display", vtype="bool", default=False,
    help="Print the communicator selection table (coll component per "
         "function + mesh binding) when a communicator is set up")


def table(comm) -> Dict:
    """The selection table for ``comm``: per-collective winning
    component, plus the mesh/transport summary."""
    per_func = getattr(comm, "_coll_winners", None)
    priorities = getattr(comm, "_coll_priorities", None)
    if per_func is None or priorities is None:
        if getattr(comm, "devices", None):
            # Not selected yet (or a bare mock): run the shared helper.
            from ompi_tpu.coll.framework import select_winners
            winners, selected = select_winners(comm)
            per_func = {f: comp.name
                        for f, (comp, _m) in winners.items()}
            priorities = [(comp.name, prio)
                          for prio, comp, _m in selected]
        else:
            # per-rank communicator: collectives are the built-in
            # textbook/XLA algorithms, not framework-selected modules
            per_func = {"*": "rankcomm-builtin"}
            priorities = []
    devices = list(getattr(comm, "devices", []) or [])
    procs = sorted({getattr(d, "process_index", 0) for d in devices})
    out = {
        "comm": getattr(comm, "name", None) or f"cid={comm.cid}",
        "size": comm.size,
        "platform": devices[0].platform if devices else "none",
        "devices": [str(getattr(d, "id", i))
                    for i, d in enumerate(devices)],
        "hosts": len(procs),
        "data_plane": ("xla/ici" if devices and
                       devices[0].platform != "cpu" else "xla/host"),
        "coll": dict(per_func),
        "priorities": list(priorities),
    }
    # per-rank worlds: the bml's per-transport frame counts — which
    # btl actually carried this rank's pt2pt traffic (the transport
    # matrix the reference's comm_method hook prints)
    router = getattr(comm, "router", None)
    ep = getattr(router, "endpoint", None)
    if ep is not None and hasattr(ep, "stats"):
        out["pt2pt_transports"] = dict(ep.stats)
        out["btl_sm"] = getattr(ep, "sm", None) is not None
        # the MEASURED basis for the bulk-routing decision (the init
        # micro-probe): operators see why sm carries bulk — or why it
        # was demoted — instead of trusting a hard-coded default
        basis = getattr(ep, "probe_basis", None)
        if basis:
            out["btl_probe"] = dict(basis)
    # the staged device tier's measured switch point (same discipline:
    # the decision shows its data)
    from ompi_tpu.coll.tuned import probed_stage_basis
    sb = probed_stage_basis()
    if sb.get("ran"):
        out["stage_probe"] = sb
    return out


def format_table(comm) -> str:
    t = table(comm)
    lines = [
        f"comm {t['comm']}: {t['size']} rank(s) on {t['platform']} "
        f"({t['hosts']} host(s)), data plane {t['data_plane']}",
        f"  devices: {', '.join(t['devices'])}",
        f"  component priorities: "
        f"{', '.join(f'{n}={p}' for n, p in t['priorities'])}",
        "  coll selection:",
    ]
    for func, comp in sorted(t["coll"].items()):
        lines.append(f"    {func:>22}: {comp}")
    return "\n".join(lines)


def maybe_display(comm) -> None:
    """Called from communicator setup when the display var is on (the
    reference hooks mpi_init/finalize the same way)."""
    if var.var_get("hook_comm_method_display", False):
        print(format_table(comm))


def main() -> None:
    import ompi_tpu as MPI
    if not MPI.Initialized():
        MPI.Init()
    print(format_table(MPI.get_comm_world()))


if __name__ == "__main__":
    main()
