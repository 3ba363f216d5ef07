"""Device-native neighbor collectives — ppermute waves along topology
edges.

Behavioral spec: the neighborhood collectives of the base registry
(``ompi/mca/coll/base/coll_base_functions.h:185-320``) over the topo
framework (``ompi/mca/topo/``): each rank exchanges buffers with its
cart/graph neighbors; cart shifts are the halo-exchange workhorse.

TPU-native re-design (round 3 — the round-2 versions were host NumPy
round-trips): a neighbor exchange IS a set of
``ppermute`` patterns. Every (source → dest) topology edge is assigned
to a *wave* by greedy edge coloring (each wave touches every rank at
most once as source and once as dest — König: ≤ max-degree waves on the
bipartite edge graph); each wave is ONE ``jax.lax.ppermute`` over the
communicator's mesh axis, i.e. one XLA collective-permute riding ICI
neighbor links. A cart dimension's ± shifts color into single waves, so
a 2-D halo exchange compiles to 4 collective-permutes — exactly the
hand-written pattern. Chunk selection (alltoall's per-edge chunks) and
result assembly are local ``take_along_axis`` ops on the sharded rank
axis; nothing touches the host.
"""
from __future__ import annotations

from collections import defaultdict, deque
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

AXIS = "mpi_r"


class NeighborPlan:
    """Edge-colored exchange schedule for one (comm, topo)."""

    def __init__(self, comm):
        topo = comm.topo
        n = comm.size
        in_nb = topo.neighbors
        out_nb = getattr(topo, "out_neighbors", topo.neighbors)
        self.n = n
        self.in_lists = [list(in_nb(r)) for r in range(n)]
        self.out_lists = [list(out_nb(r)) for r in range(n)]
        self.max_in = max((len(l) for l in self.in_lists), default=0)
        self.max_out = max((len(l) for l in self.out_lists), default=0)
        # valid in-slot index lists (host API compresses invalid slots)
        self.valid_slots = [
            [i for i, s in enumerate(l) if 0 <= s < n]
            for l in self.in_lists]
        self.slot_valid = np.zeros((n, max(self.max_in, 1)), bool)
        for r, l in enumerate(self.in_lists):
            for i, s in enumerate(l):
                self.slot_valid[r, i] = 0 <= s < n

        # FIFO multiplicity pairing of (src,dst) out-slots with in-slots
        # (duplicate edges from periodic dims of size <= 2 / multigraphs)
        out_q: Dict[Tuple[int, int], deque] = defaultdict(deque)
        for s in range(n):
            for j, d in enumerate(self.out_lists[s]):
                if 0 <= d < n:
                    out_q[(s, d)].append(j)
        # edge = (src, dst, out_slot or None, in_slot)
        edges: List[Tuple[int, int, Optional[int], int]] = []
        for d in range(n):
            for i, s in enumerate(self.in_lists[d]):
                if not (0 <= s < n):
                    continue
                q = out_q.get((s, d))
                j = q.popleft() if q else None
                edges.append((s, d, j, i))

        # Greedy edge coloring: a wave may use each rank once as source
        # and once as destination (ppermute constraint + one chunk per
        # source per wave). König: a bipartite multigraph needs at most
        # max-degree colors, so W stays small (cart: 2 per dimension).
        waves: List[dict] = []
        # assembly maps: out[r, i] = wave_out[r, wmap[r, i]]
        self.wmap = np.zeros((n, max(self.max_in, 1)), np.int32)
        self.has_chunk = np.zeros((n, max(self.max_in, 1)), bool)
        for (s, d, j, i) in edges:
            for wi, w in enumerate(waves):
                if s not in w["srcs"] and d not in w["dsts"]:
                    break
            else:
                wi = len(waves)
                w = {"perm": [], "jsel": np.zeros(n, np.int32),
                     "srcs": set(), "dsts": set()}
                waves.append(w)
            w["perm"].append((s, d))
            w["jsel"][s] = j if j is not None else 0
            w["srcs"].add(s)
            w["dsts"].add(d)
            self.wmap[d, i] = wi
            self.has_chunk[d, i] = j is not None
        self.waves = waves
        self.n_waves = len(waves)
        self.edges = edges              # (src, dst, out_slot, in_slot)


def _plan(comm) -> NeighborPlan:
    cache = getattr(comm, "_nbr_plan", None)
    if cache is None or cache[0] is not comm.topo:
        cache = (comm.topo, NeighborPlan(comm))
        comm._nbr_plan = cache
    return cache[1]


def _fns(comm) -> Dict:
    """Compiled-exchange cache, owned by the PLAN so a topo reassignment
    invalidates both together (a stale jitted fn would exchange along
    the old topology's edges)."""
    plan = _plan(comm)
    fns = getattr(plan, "_fns", None)
    if fns is None:
        fns = plan._fns = {}
    return fns


def _wave_permute(comm, arr, perm):
    """One wave: a single XLA collective-permute over the mesh axis."""
    import jax
    from jax.sharding import PartitionSpec as P
    return jax.shard_map(
        lambda a: jax.lax.ppermute(a, AXIS, perm=perm),
        mesh=comm.mesh, in_specs=P(AXIS), out_specs=P(AXIS))(arr)


def device_neighbor_allgather(comm, x) -> List[Any]:
    """x: stacked (N, *s) device buffer; returns per-rank device arrays
    (deg_r, *s) — each rank's neighbors' buffers in neighbor order."""
    import jax
    import jax.numpy as jnp
    plan = _plan(comm)
    key = ("ag", x.shape, str(x.dtype))
    fn = _fns(comm).get(key)
    if fn is None:
        perms = [tuple(w["perm"]) for w in plan.waves]
        wmap = jnp.asarray(plan.wmap)
        mask = jnp.asarray(plan.slot_valid)

        def build(buf):
            if not perms:
                return jnp.zeros((plan.n, 1) + buf.shape[1:], buf.dtype)
            outs = [_wave_permute(comm, buf, p) for p in perms]
            stacked = jnp.stack(outs, axis=1)        # (N, W, *s)
            idx = wmap.reshape(wmap.shape + (1,) * (buf.ndim - 1))
            res = jnp.take_along_axis(stacked, idx, axis=1)
            m = mask.reshape(mask.shape + (1,) * (buf.ndim - 1))
            return jnp.where(m, res, 0)              # (N, maxD, *s)
        fn = _fns(comm)[key] = jax.jit(build)
    res = fn(x)
    return [res[r, plan.valid_slots[r]] if plan.valid_slots[r]
            else jnp.empty((0,) + x.shape[1:], x.dtype)
            for r in range(plan.n)]


def device_neighbor_alltoall(comm, x) -> List[Any]:
    """x: stacked (N, max_out_deg, *s); rank r's j-th chunk goes to its
    j-th out-neighbor; returns per-rank (deg_in_r, *s) device arrays."""
    import jax
    import jax.numpy as jnp
    plan = _plan(comm)
    key = ("a2a", x.shape, str(x.dtype))
    fn = _fns(comm).get(key)
    if fn is None:
        perms = [tuple(w["perm"]) for w in plan.waves]
        jsels = [jnp.asarray(w["jsel"]) for w in plan.waves]
        wmap = jnp.asarray(plan.wmap)
        mask = jnp.asarray(plan.slot_valid & plan.has_chunk)

        def build(buf):                              # (N, D_out, *s)
            payload = buf.shape[2:]
            if not perms:
                return jnp.zeros((plan.n, 1) + payload, buf.dtype)
            outs = []
            for p, jsel in zip(perms, jsels):
                idx = jsel.reshape((plan.n, 1) + (1,) * len(payload))
                chunk = jnp.take_along_axis(buf, idx, axis=1)[:, 0]
                outs.append(_wave_permute(comm, chunk, p))
            stacked = jnp.stack(outs, axis=1)        # (N, W, *s)
            idx = wmap.reshape(wmap.shape + (1,) * len(payload))
            res = jnp.take_along_axis(stacked, idx, axis=1)
            m = mask.reshape(mask.shape + (1,) * len(payload))
            return jnp.where(m, res, 0)              # (N, maxD_in, *s)
        fn = _fns(comm)[key] = jax.jit(build)
    res = fn(x)
    return [res[r, plan.valid_slots[r]] if plan.valid_slots[r]
            else jnp.empty((0,) + x.shape[2:], x.dtype)
            for r in range(plan.n)]
