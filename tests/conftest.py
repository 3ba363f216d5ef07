"""Test environment: 8 virtual CPU devices standing in for TPU chips.

Mirrors the reference's test strategy (SURVEY.md §4): multi-rank tests run
on one node over a real local backend (the reference uses btl self/sm via
``mpirun -n N``; we use an 8-device host-platform mesh — same idea, the
collectives are real XLA programs, just on CPU).
"""
import os

# Must be set before jax initializes its backends. Tests run on the CPU
# even on a host with a chip, so clobber rather than setdefault, and
# also force via jax.config below.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "1")

import jax              # noqa: E402
jax.config.update("jax_platforms", "cpu")

import numpy as np      # noqa: E402
import pytest           # noqa: E402


@pytest.fixture(scope="session")
def mpi():
    import ompi_tpu as MPI
    if not MPI.Initialized():
        MPI.Init()
    yield MPI
    if not MPI.Finalized():
        MPI.Finalize()


@pytest.fixture(scope="session")
def world(mpi):
    return mpi.get_comm_world()


@pytest.fixture()
def rng():
    return np.random.default_rng(1234)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-process / long-running jobs excluded from the "
        "tier-1 '-m \"not slow\"' run (tools/checkparity audits that "
        "subprocess-spawning compress tests carry this)")
