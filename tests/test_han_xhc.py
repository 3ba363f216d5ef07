"""coll/han (2-level sub-communicator composition) and coll/xhc
(n-level ladder) hierarchical collectives."""
import json

import numpy as np
import pytest

import ompi_tpu as MPI
from ompi_tpu.coll import han as han_mod
from ompi_tpu.coll.han import HanModule
from ompi_tpu.coll.xhc import XhcModule, build_levels


from ompi_tpu.mca import var


@pytest.fixture()
def _vars():
    """Set MCA vars programmatically (env resolution happens once, at
    registration) and restore afterwards."""
    saved = {}

    def set_(name, value):
        saved.setdefault(name, var.var_get(name))
        var.var_set(name, value)

    yield set_
    for name, value in saved.items():
        var.var_set(name, value)


@pytest.fixture()
def han_world(world, _vars):
    """A dup of COMM_WORLD with a synthetic 2-node hierarchy (low
    groups of 4 — the ICI/DCN boundary stand-in) and han priority
    raised above every data-plane component."""
    _vars("coll_han_priority", 80)
    _vars("coll_han_split", 4)
    han_mod._reset_rules_for_tests()
    c = world.dup()
    yield c
    han_mod._reset_rules_for_tests()


def test_han_wins_with_hierarchy(han_world):
    assert han_world._coll_winners["allreduce"] == "han"
    assert isinstance(han_world.c_coll["allreduce"], HanModule)


def test_han_not_selected_without_hierarchy(world, _vars):
    _vars("coll_han_priority", 80)
    _vars("coll_han_split", 0)
    c = world.dup()          # flat CPU mesh: one process = no hierarchy
    assert c._coll_winners["allreduce"] != "han"


def test_han_allreduce(han_world, rng):
    n = han_world.size
    x = rng.standard_normal((n, 300)).astype(np.float32)  # > 256 B: hier
    out = np.asarray(han_world.allreduce(han_world.stack(list(x)),
                                         MPI.SUM))
    for r in range(n):
        np.testing.assert_allclose(out[r], x.sum(0), rtol=1e-4)
    # the tiers actually exist and were selected independently
    m = han_world.c_coll["allreduce"]
    assert len(m.h.low) == 2 and m.h.up.size == 2
    assert all(getattr(c, "_han_inner", False)
               for c in m.h.low + [m.h.up])


def test_han_allreduce_max(han_world, rng):
    n = han_world.size
    x = rng.standard_normal((n, 130)).astype(np.float32)
    out = np.asarray(han_world.allreduce(han_world.stack(list(x)),
                                         MPI.MAX))
    np.testing.assert_allclose(out[0], x.max(0), rtol=1e-5)


def test_han_bcast_reduce(han_world, rng):
    n = han_world.size
    x = rng.standard_normal((n, 65)).astype(np.float32)
    buf = han_world.stack(list(x))
    out = np.asarray(han_world.bcast(buf, root=5))
    for r in range(n):
        np.testing.assert_allclose(out[r], x[5], rtol=1e-6)
    red = np.asarray(han_world.reduce(buf, MPI.SUM, root=6))
    np.testing.assert_allclose(red[6], x.sum(0), rtol=1e-4)


def test_han_allgather(han_world, rng):
    n = han_world.size
    x = rng.standard_normal((n, 7)).astype(np.float32)
    out = np.asarray(han_world.allgather(han_world.stack(list(x))))
    for r in range(n):
        np.testing.assert_allclose(out[r], x, rtol=1e-6)


def test_han_barrier(han_world):
    han_world.barrier()      # composes low/up barriers without error


def test_han_small_message_goes_flat(han_world, rng):
    """Default dynamic table: <= 256 B skips the hierarchy (level
    latency dominates) and delegates to the next component."""
    n = han_world.size
    x = rng.standard_normal((n, 4)).astype(np.float32)   # 16 B
    m = han_world.c_coll["allreduce"]
    assert m._strategy("allreduce", 16) == "flat"
    out = np.asarray(han_world.allreduce(han_world.stack(list(x)),
                                         MPI.SUM))
    np.testing.assert_allclose(out[0], x.sum(0), rtol=1e-4)


def test_han_dynamic_rules_file(world, _vars, tmp_path, rng):
    rules = {"allreduce": [{"max_bytes": 10**9, "algorithm": "flat"}]}
    path = tmp_path / "han_rules.json"
    path.write_text(json.dumps(rules))
    _vars("coll_han_priority", 80)
    _vars("coll_han_split", 4)
    _vars("coll_han_dynamic_rules", str(path))
    han_mod._reset_rules_for_tests()
    c = world.dup()
    m = c.c_coll["allreduce"]
    assert m._strategy("allreduce", 1 << 20) == "flat"
    x = rng.standard_normal((c.size, 1000)).astype(np.float32)
    out = np.asarray(c.allreduce(c.stack(list(x)), MPI.SUM))
    np.testing.assert_allclose(out[0], x.sum(0), rtol=1e-4)
    han_mod._reset_rules_for_tests()


# ---------------------------------------------------------------------
def test_build_levels():
    lv = build_levels(8, [2, 2])
    assert lv[0] == [[0, 1], [2, 3], [4, 5], [6, 7]]
    assert lv[1] == [[0, 2], [4, 6]]
    assert lv[2] == [[0, 4]]
    assert build_levels(4, [4]) == [[[0, 1, 2, 3]]]
    assert build_levels(1, [2]) == []


@pytest.fixture()
def xhc_world(world, _vars):
    _vars("coll_xhc_priority", 80)
    _vars("coll_xhc_levels", "2,2")
    return world.dup()


def test_xhc_wins_and_ladder(xhc_world):
    assert xhc_world._coll_winners["allreduce"] == "xhc"
    m = xhc_world.c_coll["allreduce"]
    assert isinstance(m, XhcModule)
    assert len(m.levels) == 3    # pairs, pairs-of-leaders, top


def test_xhc_allreduce_ops(xhc_world, rng):
    n = xhc_world.size
    x = rng.standard_normal((n, 50)).astype(np.float32)
    buf = xhc_world.stack(list(x))
    for op, ref in ((MPI.SUM, x.sum(0)), (MPI.MAX, x.max(0)),
                    (MPI.MIN, x.min(0))):
        out = np.asarray(xhc_world.allreduce(buf, op))
        for r in range(n):
            np.testing.assert_allclose(out[r], ref, rtol=1e-4)


def test_xhc_bcast_reduce_barrier(xhc_world, rng):
    n = xhc_world.size
    x = rng.standard_normal((n, 9)).astype(np.float32)
    buf = xhc_world.stack(list(x))
    out = np.asarray(xhc_world.bcast(buf, root=3))
    np.testing.assert_allclose(out[7], x[3], rtol=1e-6)
    red = np.asarray(xhc_world.reduce(buf, MPI.SUM, root=1))
    np.testing.assert_allclose(red[1], x.sum(0), rtol=1e-4)
    xhc_world.barrier()


@pytest.fixture()
def xhc_auto_world(world, _vars):
    """xhc preferred but NO explicit level list — the ladder must come
    from synthesized locality."""
    _vars("coll_xhc_priority", 80)
    return world.dup()


def test_xhc_ladder_without_levels_var(xhc_auto_world, rng):
    """The hwloc-depth walk: with coll_xhc_levels UNSET on this flat
    8-device CPU mesh, xhc still builds a >= 2-level ladder (OS
    topology when the host has depth, labeled synthetic factorization
    otherwise) and the collectives stay correct."""
    w = xhc_auto_world
    assert w._coll_winners["allreduce"] == "xhc"
    m = w.c_coll["allreduce"]
    assert isinstance(m, XhcModule)
    assert len(m.levels) >= 2, m.levels
    assert getattr(m, "level_basis", "") in (
        "os-topology", "synthetic-mesh", "device-locality")
    n = w.size
    x = rng.standard_normal((n, 17)).astype(np.float32)
    out = np.asarray(w.allreduce(w.stack(list(x)), MPI.SUM))
    np.testing.assert_allclose(out[0], x.sum(0), rtol=1e-4)


def test_ladder_sizes_provenance():
    from ompi_tpu.utils.locality import ladder_sizes
    sizes, basis = ladder_sizes(8)
    assert sizes and basis in ("os-topology", "synthetic-mesh")
    assert ladder_sizes(2)[0] is None          # trivial stays trivial
    assert ladder_sizes(7)[0] is None          # prime, single level
