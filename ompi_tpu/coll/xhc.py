"""coll/xhc — n-level hierarchical intra-node collectives.

Behavioral spec: ``ompi/mca/coll/xhc`` — builds an n-level hierarchy
from hwloc locality (NUMA / socket / cache levels, ``xhc/README.md``)
and runs each collective level-by-level over shared memory: members
combine into their level leader, leaders repeat one level up, and the
result fans back down.

TPU-native re-design: "shared memory" is the controller's device-resident
stacked array — combining into a leader is a row reduction, fanning down
is a row broadcast; each level's step is one small XLA program. Levels
come from device locality (process index, then slice/NUMA index when
exposed) or from the MCA var ``coll_xhc_levels`` ("2,2" = pairs, then
pairs-of-leaders), the flat-mesh stand-in for the cache/NUMA ladder.
Unlike han (which composes *components* over sub-communicators), xhc
owns the whole ladder — the same division of labor as the reference.
"""
from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ompi_tpu.core import op as op_mod
from ompi_tpu.mca import var
from ompi_tpu.mca.base import Component
from ompi_tpu.coll.framework import coll_framework


def build_levels(n: int, sizes: List[int]) -> List[List[List[int]]]:
    """Partition ranks into an n-level ladder. ``sizes[l]`` is the group
    size at level l (innermost first). Returns per level the list of
    groups (each a list of member ranks); level l's members are level
    l-1's leaders. A final top level groups all remaining leaders."""
    levels: List[List[List[int]]] = []
    members = list(range(n))
    for s in sizes:
        if s <= 1 or len(members) <= 1:
            break
        groups = [members[i:i + s] for i in range(0, len(members), s)]
        levels.append(groups)
        members = [g[0] for g in groups]
    if len(members) > 1:
        levels.append([members])
    return levels


def locality_sizes(devices) -> Optional[List[int]]:
    """Infer ladder sizes from device locality: ranks per process
    (innermost), then everything. None if the ladder is trivial."""
    procs = {}
    for d in devices:
        procs.setdefault(int(getattr(d, "process_index", 0) or 0), 0)
        procs[int(getattr(d, "process_index", 0) or 0)] += 1
    if len(procs) <= 1:
        return None
    per = max(procs.values())
    return [per] if per > 1 else None


class XhcModule:
    def __init__(self, comm, sizes: List[int]):
        self.comm = comm
        self.levels = build_levels(comm.size, sizes)

    # -- the ladder passes --------------------------------------------
    def _reduce_up(self, xg, op: op_mod.Op):
        """Combine members into leaders, level by level; returns the
        array with every level's leader row holding its subtree
        reduction (top leader = rank levels[-1][0][0] holds the total)."""
        for groups in self.levels:
            for g in groups:
                if len(g) == 1:
                    continue
                rows = jnp.asarray(np.asarray(g))
                red = op.reduce_tree(jnp.take(xg, rows, axis=0), axis=0)
                xg = xg.at[g[0]].set(red)
        return xg

    def _fan_down(self, xg, src_row: int):
        """Broadcast ``src_row``'s value down the ladder."""
        val = xg[src_row]
        return jnp.broadcast_to(val[None], xg.shape)

    def allreduce(self, x, op: op_mod.Op = op_mod.SUM):
        xg = jnp.asarray(x)
        up = self._reduce_up(xg, op)
        top = self.levels[-1][0][0] if self.levels else 0
        out = self._fan_down(up, top)
        return jax.device_put(out, self.comm.sharding)

    def reduce(self, x, op: op_mod.Op = op_mod.SUM, root: int = 0):
        xg = jnp.asarray(x)
        up = self._reduce_up(xg, op)
        top = self.levels[-1][0][0] if self.levels else 0
        out = jnp.zeros_like(xg).at[root].set(up[top])
        return jax.device_put(out, self.comm.sharding)

    def bcast(self, x, root: int = 0):
        xg = jnp.asarray(x)
        out = self._fan_down(xg, root)
        return jax.device_put(out, self.comm.sharding)

    def barrier(self) -> None:
        token = jnp.ones((self.comm.size, 1), jnp.float32)
        jax.block_until_ready(self.allreduce(token, op_mod.SUM))


class XhcComponent(Component):
    name = "xhc"

    def register_params(self) -> None:
        var.var_register("coll", "xhc", "priority", vtype="int", default=25,
                         help="Selection priority of the n-level "
                              "hierarchical component")
        var.var_register("coll", "xhc", "levels", vtype="str", default="",
                         help="Comma list of group sizes per level, "
                              "innermost first (empty = device locality)")

    def comm_query(self, comm):
        from ompi_tpu.coll import han as _han
        if _han._in_construction() or getattr(comm, "_han_inner", False):
            return None
        prio = var.var_get("coll_xhc_priority", 25)
        if prio < 0:
            return None
        spec = (var.var_get("coll_xhc_levels", "") or "").strip()
        basis = "var"
        if spec:
            try:
                sizes = [int(s) for s in spec.split(",") if s.strip()]
            except ValueError:
                return None
        else:
            sizes = locality_sizes(comm.devices)
            if sizes is None:
                # the hwloc-depth walk: OS
                # topology levels, else a labeled synthetic
                # factorization so the ladder still has depth on flat
                # virtual meshes
                from ompi_tpu.utils.locality import ladder_sizes
                sizes, basis = ladder_sizes(comm.size, comm.devices)
                if sizes is None:
                    return None
            else:
                basis = "device-locality"
        if comm.size <= 1 or not sizes:
            return None
        mod = XhcModule(comm, sizes)
        mod.level_basis = basis          # provenance for comm_method
        return (prio, mod)


coll_framework.register(XhcComponent())
