"""Prove that ompi_tpu's main path runs on the TPU.

One process drives the library through the entry points a user calls —
``Init``, the communicator's collectives, persistent requests, host
buffers, a training step, the Pallas flash kernel — and checks every
result against numpy. It never starts a child that touches JAX: the
chip belongs to one process.

    python chip_smoke.py             # one chip (the default)
    python chip_smoke.py --chips 4   # the cross-chip path, four chips

The phases run in order; the first failure exits non-zero and no result
is printed. Each phase prints its wall time and its compile counts on an
earlier line (set-up facts, not metrics). The last line of stdout is the
verdict, ``{"ok": true, "device": {...}}``.

Sizes are the ones users run (BASELINE.json): 8 B and 256 MB f32 per
rank for the collectives, 64 MB for each pinned algorithm. All data
holds small integers, so every sum is exact in any order and every check
is bitwise.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import sys
import time
import traceback

import numpy as np

MB = 1 << 20
F32 = 4

# every coll/xla allreduce lowering a single-host world runs ('hier'
# needs a multi-host mesh and demotes to 'direct' without one)
XLA_ALLREDUCE_ALGORITHMS = ("direct",)
# the root-targeted schedules the TPU decision table picks above 64 KiB
ROOT_ALGORITHMS = (("reduce", "rabenseifner_root"), ("gather", "binomial"),
                   ("scatter", "binomial"))


class SmokeError(AssertionError):
    """A phase's result disagreed with its reference."""


class Checks:
    """Counts a phase's comparisons: a phase that compared nothing did
    not run, and the runner fails it."""

    def __init__(self):
        self.n = 0

    def true(self, cond, what: str) -> None:
        self.n += 1
        if not cond:
            raise SmokeError(what)

    def equal(self, got, want, what: str) -> None:
        got = np.asarray(got)
        self.true(got.shape == np.shape(want)
                  and np.array_equal(got, want), f"{what}: mismatch")

    def close(self, got, want, what: str, rtol: float,
              atol: float) -> None:
        got, want = np.asarray(got), np.asarray(want)
        self.true(got.shape == want.shape and bool(np.all(np.isfinite(got)))
                  and np.allclose(got, want, rtol=rtol, atol=atol),
                  f"{what}: max |diff| "
                  f"{float(np.max(np.abs(got - want), initial=0.0))}")


class CompileStats:
    """JAX's own compile events: backend compiles (persistent-cache
    hits included) with their seconds, and persistent-cache hits."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.compiles = 0
        self.seconds = 0.0
        self.hits = 0
        event = dispatch.BACKEND_COMPILE_EVENT

        def on_duration(name, secs, **_kw):
            if name == event:
                self.compiles += 1
                self.seconds += secs

        def on_event(name, **_kw):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple:
        return self.compiles, self.seconds, self.hits


# ---------------------------------------------------------------------
# data and references
# ---------------------------------------------------------------------
_PERIOD = 13


def _base(salt: int) -> np.ndarray:
    return ((np.arange(_PERIOD) * 7 + salt) % _PERIOD - 6).astype(np.float32)


def pattern(comm, local_shape, salt: int = 0):
    """A stacked (size, *local_shape) f32 buffer of small integers,
    made on the devices in the communicator's sharding."""
    import jax
    import jax.numpy as jnp
    shape = (comm.size,) + tuple(local_shape)
    total = int(np.prod(shape))

    def make():
        i = jax.lax.iota(jnp.int32, total)
        return ((i * 7 + salt) % _PERIOD - 6).astype(jnp.float32) \
            .reshape(shape)
    return jax.jit(make, out_shardings=comm.sharding)()


def pattern_np(size: int, local_shape, salt: int = 0) -> np.ndarray:
    """The host copy of :func:`pattern`."""
    shape = (size,) + tuple(local_shape)
    return np.resize(_base(salt), int(np.prod(shape))).reshape(shape)


def rows(y):
    """(rank, host row) for each rank of a stacked device result,
    fetched one shard at a time after the computation finished."""
    y.block_until_ready()
    for s in y.addressable_shards:
        yield s.index[0].start or 0, np.asarray(s.data)[0]


@contextlib.contextmanager
def mca(**values):
    """Override MCA variables for the dynamic extent (a var scope: the
    process-wide store is untouched afterwards)."""
    from ompi_tpu.mca import var
    scope = var.VarScope()
    for k, v in values.items():
        scope.set(k, v)
    with var.scope(scope):
        yield


def xla_module(comm):
    """The coll/xla module serving ``comm``'s device buffers."""
    from ompi_tpu.coll.tuned import TunedCollModule
    from ompi_tpu.coll.xla import XlaCollModule
    mod = comm.c_coll["allreduce"]
    if isinstance(mod, TunedCollModule):
        mod = mod.device
    if not isinstance(mod, XlaCollModule):
        raise SmokeError(f"allreduce is served by {type(mod).__name__}, "
                         "not coll/xla")
    return mod


def xla_comm(world):
    """A duplicate of ``world`` selected with coll/self out of the way,
    so that a 1-rank world runs its device buffers through coll/xla."""
    with mca(coll_self_priority=1):
        return world.dup()


# ---------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------
def phase_device(chk, log, MPI, platform: str, count: int):
    """Init binds COMM_WORLD to the devices, one rank per device."""
    import jax
    from ompi_tpu.native import loader
    if not MPI.Initialized():
        MPI.Init()
    world = MPI.get_comm_world()
    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)} world_size={world.size} rank_devices="
        f"{[dv.id for dv in world.devices]}")
    chk.true(d.platform == platform,
             f"platform {d.platform}, expected {platform}")
    chk.true(len(devs) >= count and world.size == count,
             f"{len(devs)} devices, world size {world.size}, "
             f"expected {count}")
    chk.true(len({dv.id for dv in world.devices}) == world.size,
             "two ranks share a device")
    t0 = time.perf_counter()
    lib = loader.get_lib()
    log("native: " + (f"built {loader._SO}" if lib is not None else
                      "NOT BUILT (no g++?): numpy/ctypes fallbacks")
        + f" in {time.perf_counter() - t0:.3f} s")


def _check_five(chk, MPI, comm, elems: int, tag: str):
    """allreduce, bcast, allgather, alltoall and reduce_scatter_block on
    stacked device buffers of ``elems`` f32 per rank."""
    n = comm.size
    root = n - 1
    x = pattern(comm, (elems,), salt=1)
    xs = pattern_np(n, (elems,), salt=1)
    total = xs.sum(axis=0)
    y = comm.allreduce(x, MPI.SUM)
    chk.true({s.device for s in y.addressable_shards} == set(comm.devices),
             f"{tag} allreduce result is not one shard per rank device")
    for r, row in rows(y):
        chk.equal(row, total, f"{tag} allreduce rank {r}")
    for r, row in rows(comm.bcast(x, root)):
        chk.equal(row, xs[root], f"{tag} bcast rank {r}")
    for r, row in rows(comm.allgather(x)):
        chk.equal(row, xs, f"{tag} allgather rank {r}")
    del x
    c = max(elems // n, 1)
    y = pattern(comm, (n, c), salt=2)
    ys = pattern_np(n, (n, c), salt=2)
    for r, row in rows(comm.alltoall(y)):
        chk.equal(row, ys[:, r], f"{tag} alltoall rank {r}")
    rs = ys.sum(axis=0)
    for r, row in rows(comm.reduce_scatter_block(y, MPI.SUM)):
        chk.equal(row, rs[r], f"{tag} reduce_scatter_block rank {r}")


def phase_default(chk, log, MPI, elems: int):
    """What a user gets from COMM_WORLD as selected (coll/self on one
    chip), on world.alloc'd buffers."""
    world = MPI.get_comm_world()
    n = world.size
    log(f"default selection: {sorted(set(world._coll_winners.values()))}")
    x = world.alloc((elems,), np.float32, fill=3.0)
    want = np.full((elems,), 3.0 * n, np.float32)
    for r, row in rows(world.allreduce(x, MPI.SUM)):
        chk.equal(row, want, f"allreduce rank {r}")
    for r, row in rows(world.bcast(x, 0)):
        chk.equal(row, np.full((elems,), 3.0, np.float32), f"bcast {r}")
    for r, row in rows(world.allgather(x)):
        chk.equal(row, np.full((n, elems), 3.0, np.float32),
                  f"allgather rank {r}")
    y = world.alloc((n, max(elems // n, 1)), np.float32, fill=2.0)
    for r, row in rows(world.alltoall(y)):
        chk.equal(row, np.full(y.shape[1:], 2.0, np.float32),
                  f"alltoall rank {r}")
    for r, row in rows(world.reduce_scatter_block(y, MPI.SUM)):
        chk.equal(row, np.full(y.shape[2:], 2.0 * n, np.float32),
                  f"reduce_scatter_block rank {r}")


def phase_xla(chk, log, MPI, sizes, alg_elems: int, algorithms):
    """coll/xla on the chip: the five collectives at each size, each
    allreduce lowering forced through the MCA variable, a nonblocking
    and a persistent allreduce."""
    comm = xla_comm(MPI.get_comm_world())
    mod = xla_module(comm)
    log(f"coll/xla comm: winners {sorted(set(comm._coll_winners.values()))}"
        f", device buffers -> {type(mod).__name__}")
    for elems in sizes:
        _check_five(chk, MPI, comm, elems, f"{elems * F32} B")
    n = comm.size
    x = pattern(comm, (alg_elems,), salt=3)
    total = pattern_np(n, (alg_elems,), salt=3).sum(axis=0)
    for alg in algorithms:
        with mca(coll_xla_allreduce_algorithm=alg):
            y = comm.allreduce(x, MPI.SUM)
        for r, row in rows(y):
            chk.equal(row, total, f"allreduce[{alg}] rank {r}")
        chk.true(any(k[0] == "allreduce" and k[1] == x.shape
                     and alg in k[3:] for k in mod._cache),
                 f"allreduce[{alg}] did not compile its own schedule")
    for r, row in rows(comm.iallreduce(x, MPI.SUM).get()):
        chk.equal(row, total, f"iallreduce rank {r}")
    req = comm.allreduce_init(x, MPI.SUM)
    for _ in range(2):
        req.start()
        req.wait()
        for r, row in rows(req.get()):
            chk.equal(row, total, f"persistent allreduce rank {r}")
    req.free()


def phase_host(chk, log, MPI, elems: int):
    """A numpy-input allreduce staged through coll/tuned onto the
    device path (accelerator.to_device / to_host)."""
    comm = xla_comm(MPI.get_comm_world())
    xs = pattern_np(comm.size, (elems,), salt=4)
    with mca(coll_tuned_stage_min_bytes=min(xs.nbytes, MB)):
        mod, staged = comm.c_coll["allreduce"]._decide("allreduce", xs)
        chk.true(staged, "host buffer was not staged to the device")
        y = comm.allreduce(xs, MPI.SUM)
    chk.true(isinstance(y, np.ndarray), "staged result is not on the host")
    want = np.broadcast_to(xs.sum(axis=0), xs.shape)
    chk.equal(y, want, "staged allreduce")


def phase_train(chk, log, MPI, steps: int, batch: int):
    """sgd_train_step of the flagship transformer at the repo's Config,
    gradients through BucketedGradSync's persistent allreduces, against
    the same steps with no sync (every rank holds the same replica and
    batch, so the mean gradient is the replica's); then one forward
    with the flash fold against dense attention."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from ompi_tpu.models import transformer as T
    comm = xla_comm(MPI.get_comm_world())
    n = comm.size
    cfg = T.Config()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (batch, cfg.seq + 1),
                              0, cfg.vocab)
    data = (toks[:, :-1], toks[:, 1:])

    def stack(tree):
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (n,) + a.shape), tree)
    step = jax.jit(lambda p, b: T.sgd_train_step(p, b, cfg, 1e-2))
    ref_p, ref_losses = params, []
    for _ in range(steps):
        ref_p, loss = step(ref_p, data)
        ref_losses.append(float(loss))
    p, sdata = stack(params), stack(data)
    sync = T.BucketedGradSync(comm, p)
    for i in range(steps):
        p, loss = T.sgd_train_step(p, sdata, cfg, 1e-2, grad_sync=sync)
        chk.close(loss, np.full((n,), ref_losses[i]), f"step {i} loss",
                  rtol=1e-3, atol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(p),
                    jax.tree_util.tree_leaves(ref_p)):
        chk.close(np.asarray(a)[0], b, "synced params", rtol=1e-2,
                  atol=1e-3)
    log(f"train: {steps} steps, losses {ref_losses}")
    dense = dataclasses.replace(cfg, dtype=jnp.float32)
    flash = dataclasses.replace(dense, use_flash=True)
    with jax.default_matmul_precision("float32"):
        a = T.forward(params, data[0], flash)
        b = T.forward(params, data[0], dense)
    chk.close(a, b, "flash forward vs dense", rtol=1e-4, atol=1e-4)


def phase_flash(chk, log, MPI, shape, interpret: bool):
    """The Pallas flash block fold against the jnp fold (f32 matmuls)
    for a (BH, S, D) block, full and causal-diagonal masks; on the chip
    the kernel is compiled by Mosaic (``interpret=False``)."""
    import jax
    import jax.numpy as jnp
    from ompi_tpu.ops.flash_attention import flash_block_update, fold_jnp
    bh, s, d = shape
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(kq, (bh, s, d), jnp.float32) * d ** -0.5
    k = jax.random.normal(kk, (bh, s, d), jnp.float32)
    v = jax.random.normal(kv, (bh, s, d), jnp.float32)
    acc = (jnp.zeros((bh, s, d), jnp.float32),
           jnp.full((bh, s), -1e30, jnp.float32),
           jnp.zeros((bh, s), jnp.float32))
    for mode in (0, 1):
        o, m, l = flash_block_update(q, k, v, *acc, jnp.int32(mode),
                                     interpret=interpret)
        with jax.default_matmul_precision("float32"):
            ro, rm, rl = fold_jnp(q, k, v, *acc, jnp.int32(mode))
        out = np.asarray(o / l[..., None])
        ref = np.asarray(ro / rl[..., None])
        log(f"flash mode {mode}: max |out - ref| "
            f"{float(np.max(np.abs(out - ref)))}")
        chk.close(out, ref, f"flash mode {mode} output", rtol=1e-3,
                  atol=1e-3)
        chk.close(m, rm, f"flash mode {mode} max", rtol=1e-4, atol=1e-4)
        chk.close(l, rl, f"flash mode {mode} denominator", rtol=1e-3,
                  atol=1e-3)


def phase_collectives(chk, log, MPI, elems: int):
    """The five collectives on COMM_WORLD as selected."""
    world = MPI.get_comm_world()
    log(f"selection: {sorted(set(world._coll_winners.values()))}, device "
        f"buffers -> {type(xla_module(world)).__name__}")
    _check_five(chk, MPI, world, elems, f"{elems * F32} B")


def phase_algorithms(chk, log, MPI, elems: int, algorithms):
    """Every coll/xla allreduce lowering, and the root-targeted
    reduce/gather/scatter schedules for every root."""
    comm = MPI.get_comm_world()
    mod = xla_module(comm)
    n = comm.size
    x = pattern(comm, (elems,), salt=5)
    xs = pattern_np(n, (elems,), salt=5)
    total = xs.sum(axis=0)
    for alg in algorithms:
        with mca(coll_xla_allreduce_algorithm=alg):
            y = comm.allreduce(x, MPI.SUM)
        for r, row in rows(y):
            chk.equal(row, total, f"allreduce[{alg}] rank {r}")
        chk.true(any(k[0] == "allreduce" and alg in k[3:]
                     for k in mod._cache),
                 f"allreduce[{alg}] did not compile its own schedule")
    c = elems // n
    sc = pattern(comm, (n, c), salt=6)
    scs = pattern_np(n, (n, c), salt=6)
    for func, alg in ROOT_ALGORITHMS:
        for root in range(n):
            with mca(**{f"coll_xla_{func}_algorithm": alg}):
                if func == "reduce":
                    y = comm.reduce(x, MPI.SUM, root)
                    want = total
                elif func == "gather":
                    y = comm.gather(x, root)
                    want = xs
                else:
                    y = comm.scatter(sc, root)
                    want = None
            for r, row in rows(y):
                if func == "scatter":
                    chk.equal(row, scs[root, r], f"scatter root {root} "
                              f"rank {r}")
                elif r == root:
                    chk.equal(row, want, f"{func}[{alg}] root {root}")
            chk.true(any(k[0] == func and alg in k[3:] and root in k[3:]
                         for k in mod._cache),
                     f"{func}[{alg}] root {root} did not compile its own "
                     "schedule")


def phase_split(chk, log, MPI, elems: int):
    """split into two halves, each running its own allreduce."""
    world = MPI.get_comm_world()
    n = world.size
    colors = [r * 2 // n for r in range(n)]
    subs = list({id(c): c for c in world.split(colors)}.values())
    chk.true(len(subs) == 2, f"split gave {len(subs)} communicators")
    for i, sub in enumerate(subs):
        x = pattern(sub, (elems,), salt=7 + i)
        want = pattern_np(sub.size, (elems,), salt=7 + i).sum(axis=0)
        for r, row in rows(sub.allreduce(x, MPI.SUM)):
            chk.equal(row, want, f"sub {i} allreduce rank {r}")
        log(f"split: sub {i} on devices {[d.id for d in sub.devices]}")


def phase_flagship(chk, log, MPI, n: int):
    """The flagship pp x dp x tp step of __graft_entry__ on the chips,
    dp=2 against dp=1 on the same batch."""
    import __graft_entry__ as G
    losses = G.dryrun_multichip(n)
    dp1, dp2 = losses["dp1"], losses["dp2"]
    chk.close(dp2[0], dp1[0], "flagship dp=2 vs dp=1 step 1", rtol=1e-4,
              atol=1e-5)
    chk.close(dp2[1], dp1[1], "flagship dp=2 vs dp=1 step 2", rtol=2e-3,
              atol=1e-4)


# ---------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------
def one_chip_phases(MPI, platform: str = "tpu", count: int = 1,
                    small: int = 2, big: int = 256 * MB // F32,
                    alg: int = 64 * MB // F32, host: int = 64 * MB // F32,
                    steps: int = 3, batch: int = 8,
                    flash=(16, 2048, 128), interpret: bool = False):
    from ompi_tpu.coll import decision
    rules = decision.effective_rules("allreduce", platform=platform)
    algorithms = tuple(dict.fromkeys(str(r[2]) for r in rules))
    p = functools.partial
    return [
        ("device", p(phase_device, MPI=MPI, platform=platform,
                     count=count)),
        ("default_selection", p(phase_default, MPI=MPI, elems=small)),
        ("coll_xla", p(phase_xla, MPI=MPI, sizes=(small, big),
                       alg_elems=alg, algorithms=algorithms)),
        ("host_staging", p(phase_host, MPI=MPI, elems=host)),
        ("train_step", p(phase_train, MPI=MPI, steps=steps, batch=batch)),
        ("flash_kernel", p(phase_flash, MPI=MPI, shape=flash,
                           interpret=interpret)),
    ]


def four_chip_phases(MPI, platform: str = "tpu", count: int = 4,
                     big: int = 256 * MB // F32, alg: int = 64 * MB // F32,
                     flagship: int = 4):
    p = functools.partial
    phases = [
        ("device", p(phase_device, MPI=MPI, platform=platform,
                     count=count)),
        ("collectives", p(phase_collectives, MPI=MPI, elems=big)),
        ("algorithms", p(phase_algorithms, MPI=MPI, elems=alg,
                         algorithms=XLA_ALLREDUCE_ALGORITHMS)),
        ("split", p(phase_split, MPI=MPI, elems=alg)),
    ]
    if flagship:
        phases.append(("flagship", p(phase_flagship, MPI=MPI, n=flagship)))
    return phases


def run(phases, log=print) -> dict:
    """Run each phase in order; raise at the first failure. Returns
    {phase: (checks, wall s, compiles, compile s, cache hits)}."""
    stats = CompileStats()
    out = {}
    for name, fn in phases:
        chk = Checks()
        c0 = stats.snapshot()
        t0 = time.perf_counter()
        fn(chk, log)
        wall = time.perf_counter() - t0
        if chk.n == 0:
            raise SmokeError(f"phase {name} checked nothing")
        c1 = stats.snapshot()
        out[name] = (chk.n, wall, c1[0] - c0[0], c1[1] - c0[1],
                     c1[2] - c0[2])
        log(f"phase {name}: ok, {chk.n} checks, wall {wall:.3f} s, "
            f"compiles {out[name][2]} ({out[name][3]:.3f} s), "
            f"persistent-cache hits {out[name][4]}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the cross-chip path, on four chips")
    args = ap.parse_args(argv)
    try:
        import jax
        import ompi_tpu as MPI
        from ompi_tpu.runtime.init import compile_cache_dir
        print(f"compile cache: {compile_cache_dir()}", flush=True)
        phases = (four_chip_phases(MPI) if args.chips == 4
                  else one_chip_phases(MPI))
        t0 = time.perf_counter()
        out = run(phases, log=lambda s: print(s, flush=True))
        print(f"total: {time.perf_counter() - t0:.3f} s, compiles "
              f"{sum(v[2] for v in out.values())} "
              f"({sum(v[3] for v in out.values()):.3f} s), "
              f"persistent-cache hits {sum(v[4] for v in out.values())}",
              flush=True)
        d = jax.devices()[0]
        verdict = {"ok": True, "device": {"platform": d.platform,
                                          "kind": d.device_kind,
                                          "count": len(jax.devices())}}
        MPI.Finalize()
    except Exception:                    # noqa: BLE001 — the verdict
        traceback.print_exc()            # boundary: report, exit 1
        return 1
    print(json.dumps(verdict), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
