"""Auxiliary subsystems: MPI_T tool interface, monitoring interposition,
profiling hooks, SHMEM-lite, Sessions, tools."""
import numpy as np
import pytest

import ompi_tpu as MPI


def test_mpi_t_cvars_pvars(world):
    from ompi_tpu.api import tool
    tool.init_thread()
    assert tool.cvar_get_num() > 5
    assert tool.cvar_read("coll_xla_priority") == 40
    world.barrier()
    names = [p["name"] for p in tool.pvar_list()]
    assert "spc_coll_barrier" in names
    assert tool.pvar_read("spc_coll_barrier") >= 1


def test_profiling_hooks(world):
    from ompi_tpu.utils import hooks
    events = []
    h = hooks.register_profiler(lambda ev, c, info: events.append(ev))
    try:
        world.barrier()
        world.allreduce(world.alloc((2,), np.float32), MPI.SUM)
    finally:
        hooks.unregister_profiler(h)
    assert "coll_barrier" in events and "coll_allreduce" in events
    world.barrier()
    assert events.count("coll_barrier") == 1      # unregistered


def test_monitoring_component(world, monkeypatch):
    from ompi_tpu.coll import monitoring
    from ompi_tpu.mca import var
    var.var_register("coll", "monitoring", "enable", vtype="bool",
                     default=False)
    var.var_set("coll_monitoring_enable", True)
    try:
        monitoring.reset()
        d = world.dup()            # re-selects with monitoring enabled
        assert isinstance(d.c_coll["allreduce"],
                          monitoring.MonitoringCollModule)
        x = d.alloc((8,), np.float32, fill=1.0)
        d.allreduce(x, MPI.SUM)
        d.allreduce(x, MPI.SUM)
        snap = monitoring.snapshot()
        calls, nbytes = snap[(d.cid, "allreduce")]
        assert calls == 2 and nbytes == 2 * x.nbytes
        # interposes over whatever selection would otherwise pick,
        # per function (backfill preserved)
        from ompi_tpu.coll.tuned import TunedCollModule
        assert isinstance(d.c_coll["allreduce"].vtable["allreduce"],
                          TunedCollModule)
    finally:
        var.var_set("coll_monitoring_enable", False)


def test_shmem_lite(world):
    from ompi_tpu.shmem import ShmemCtx
    ctx = ShmemCtx(world, heap_size=64)
    assert ctx.n_pes == world.size
    a = ctx.malloc(4)
    b = ctx.malloc(2)
    assert (a, b) == (0, 4)
    ctx.put(1, a, np.arange(4, dtype=np.float32))
    np.testing.assert_array_equal(ctx.get(1, a, 4), np.arange(4))
    ctx.p(2, b, 7.0)
    assert ctx.g(2, b) == 7.0
    ctx.atomic_add(2, b, 3.0)
    assert ctx.atomic_fetch_add(2, b, 1.0) == 10.0
    old = ctx.atomic_compare_swap(2, b, cond=11.0, value=99.0)
    assert old == 11.0 and ctx.g(2, b) == 99.0
    # collectives over the heap
    for pe in range(ctx.n_pes):
        ctx.put(pe, a, np.full(4, float(pe), np.float32))
    ctx.reduce(a, 4, MPI.SUM)
    expect = sum(range(ctx.n_pes))
    np.testing.assert_array_equal(ctx.get(0, a, 4), expect)
    ctx.broadcast(b, 1, root_pe=2)
    assert ctx.g(0, b) == 99.0
    ctx.barrier_all()


def test_sessions(world):
    from ompi_tpu.runtime.session import Session
    with Session() as s:
        assert s.get_num_psets() >= 2
        names = [s.get_nth_pset(i) for i in range(s.get_num_psets())]
        assert "mpi://WORLD" in names and "mpi://SELF" in names
        g = s.group_from_pset("mpi://WORLD")
        c = s.comm_create_from_group(g, tag="from_session")
        assert c.size == world.size
        y = c.allreduce(c.alloc((2,), np.float32, fill=1.0), MPI.SUM)
        np.testing.assert_allclose(np.asarray(y)[0], float(c.size))


def test_info_tool(world):
    from ompi_tpu.tools.info import collect
    data = collect(all_vars=True)
    assert "xla" in data["frameworks"]["coll"]
    assert "tuned" in data["frameworks"]["coll"]
    assert any(v["name"] == "coll_xla_priority" for v in data["mca_vars"])


def test_mpirun_env_translation():
    from ompi_tpu.tools.mpirun import build_env, parse
    args = parse(["-n", "4", "--mca", "coll_base_include", "xla,basic",
                  "--coordinator", "10.0.0.1:1234", "--num-hosts", "2",
                  "--host-id", "1", "prog.py"])
    env = build_env(args, {})
    assert env["OMPI_TPU_MCA_mpi_base_num_ranks"] == "4"
    assert env["OMPI_TPU_MCA_coll_base_include"] == "xla,basic"
    assert env["OMPI_TPU_MCA_mpi_base_distributed"] == "1"
    assert env["OMPI_TPU_MCA_mpi_base_coordinator"] == "10.0.0.1:1234"
    assert env["OMPI_TPU_MCA_mpi_base_num_processes"] == "2"
    assert env["OMPI_TPU_MCA_mpi_base_process_id"] == "1"
    # one controller per host brings up that host's own chips
    assert "JAX_PLATFORMS" not in env
    assert args.program == ["prog.py"]
