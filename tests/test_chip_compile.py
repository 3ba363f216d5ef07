"""The chip's compiler accepts coll/xla's lowerings and the flash kernel.

Compiles for a described TPU v5e (``v5e:2x2``, four chips), not an
attached one: nothing runs, so this proves only that the TPU compiler
takes each program at the size users run and puts in the expected
collective or kernel. The topology is described inside a fixture, never
at import: only one process may load the TPU library, and pytest-xdist
workers all import this file.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from ompi_tpu.mca import var

MB = 1 << 20
PER_RANK = 256 * MB // 4                 # f32 elements: 256 MB per rank


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:               # noqa: BLE001 — skip reason
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables land in the persistent cache but
    # cannot be read back without one: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


@pytest.fixture(scope="module")
def xla(topo, mpi):
    """coll/xla's module on a communicator over the four described
    chips. Its executable cache hands back the compiled program instead
    of calling it (there is no device to run on)."""
    from ompi_tpu.coll.xla import XlaCollModule
    from ompi_tpu.core.communicator import Communicator
    from ompi_tpu.core.group import Group
    comm = Communicator(Group(range(4)), topo.devices, name="v5e_2x2")
    mod = XlaCollModule(comm)
    mod.compiled = {}
    compiled = mod._compiled

    def keep(key, build, *args):
        mod.compiled[key[0]] = compiled(key, build, *args)
        return lambda *_a: None
    mod._compiled = keep
    mod._to_mesh = lambda x: x
    return mod


class _Spec(jax.ShapeDtypeStruct):
    @property
    def nbytes(self):
        return math.prod(self.shape) * np.dtype(self.dtype).itemsize


def _stacked(mod, *local, dtype=jnp.float32):
    return _Spec((mod.comm.size,) + local, dtype,
                 sharding=mod.comm.sharding)


def _hlo_ops(compiled):
    return set(re.findall(r"\b(all-reduce|all-gather|all-to-all|"
                          r"reduce-scatter|collective-permute)\b",
                          compiled.as_text()))


def _entry_ops(compiled):
    """The opcodes of the compiled program's entry computation (not of
    the fusions it calls)."""
    text = compiled.as_text()
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    return set(re.findall(r"\s([a-z][a-z0-9-]*)\(", entry))


def _lower(mod, func, *args, **mca):
    scope = var.VarScope()
    for k, v in mca.items():
        scope.set(k, v)
    with var.scope(scope):
        getattr(mod, func)(*args)
    return mod.compiled.pop(func)


@pytest.mark.parametrize("alg,expect", [
    ("direct", {"all-reduce"}),
])
def test_allreduce_compiles(xla, mpi, alg, expect):
    c = _lower(xla, "allreduce", _stacked(xla, PER_RANK), mpi.SUM,
               coll_xla_allreduce_algorithm=alg)
    assert expect <= _hlo_ops(c)


def test_allreduce_default_is_one_all_reduce(xla, mpi):
    """The default selection, no MCA variable, with the described
    chips' own platform string reaching the decision table: 256 MB per
    rank is served by one all-reduce, not a two-phase schedule."""
    assert xla.comm.devices[0].platform == "tpu"
    c = _lower(xla, "allreduce", _stacked(xla, PER_RANK), mpi.SUM)
    ops = _hlo_ops(c)
    assert "all-reduce" in ops and "all-gather" not in ops


@pytest.mark.parametrize("func,expect", [
    ("allgather", {"all-gather"}),
    ("alltoall", {"all-to-all"}),
])
def test_collective_compiles(xla, mpi, func, expect):
    n = xla.comm.size
    if func == "allgather":
        args = (_stacked(xla, PER_RANK),)
    else:
        args = (_stacked(xla, n, PER_RANK // n),)
    assert expect <= _hlo_ops(_lower(xla, func, *args))


@pytest.mark.parametrize("dtype,extra,alltoall", [
    (jnp.float32, 0, True),
    (jnp.bfloat16, 0, False),
    (jnp.float32, 3, False),
], ids=["f32", "bf16", "f32-ragged"])
def test_reduce_scatter_block_default_lowering(xla, mpi, dtype, extra,
                                               alltoall):
    """The default SUM at 256 MB per rank in 1-D blocks: f32 rows of
    whole 128-lane tiles are served by one all-to-all and a local sum,
    and no all-reduce of the whole buffer; bf16 (whose relayout into an
    all-to-all takes minutes to compile) and rows of any other length
    keep psum_scatter's all-reduce plus a slice."""
    n = xla.comm.size
    c = 256 * MB // np.dtype(dtype).itemsize // n + extra
    ops = _entry_ops(_lower(xla, "reduce_scatter_block",
                            _stacked(xla, n, c, dtype=dtype), mpi.SUM))
    assert ("all-to-all" in ops, "all-reduce" in ops) == (alltoall,
                                                          not alltoall)


def test_bcast_default_is_one_all_reduce(xla):
    """The default bcast at 256 MB per rank, with the described chips'
    own platform string reaching the decision table: the root-masked
    psum, one all-reduce and no all-gather."""
    assert xla.comm.devices[0].platform == "tpu"
    c = _lower(xla, "bcast", _stacked(xla, PER_RANK), 0)
    assert _hlo_ops(c) == {"all-reduce"}


def test_root_targeted_reduce_compiles(xla, mpi):
    """The schedule the TPU decision table picks for reduce above
    64 KiB: psum_scatter plus a binomial collect over ppermute."""
    c = _lower(xla, "reduce", _stacked(xla, 64 * MB // 4), mpi.SUM, 1,
               coll_xla_reduce_algorithm="rabenseifner_root")
    assert "collective-permute" in _hlo_ops(c)


def test_flash_kernel_compiles(topo):
    from ompi_tpu.ops.flash_attention import flash_block_update
    one = SingleDeviceSharding(topo.devices[0])
    bh, s, d = 16, 2048, 128

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one)
    mode = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    c = jax.jit(flash_block_update).lower(
        f32(bh, s, d), f32(bh, s, d), f32(bh, s, d), f32(bh, s, d),
        f32(bh, s), f32(bh, s), mode).compile()
    assert "tpu_custom_call" in c.as_text()
