"""The benchmark's CPU tests: 4 virtual devices stand in for the 2x2
host. Run them with ``python3 -m pytest benchmark/tests``."""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402

# sizes a CPU test run can hold: bytes per rank of each phase
SMALL = {"lat": 8, "bw": 1 << 16}


def shrink(cell):
    """The cell at CPU-test sizes: the same calls, cases and sampling,
    smaller buffers and fewer warm-up calls."""
    for ph in cell.traffic["phases"]:
        ph["bytes_per_rank"] = SMALL[ph["role"]]
        ph["warmup_calls"] = min(ph["warmup_calls"], 4)
        ph["sample_within"] = min(ph["sample_within"], 64)


@pytest.fixture(scope="session")
def mpi():
    import jax
    jax.config.update("jax_platforms", "cpu")
    import ompi_tpu as MPI
    if not MPI.Initialized():
        MPI.Init()
    yield MPI
