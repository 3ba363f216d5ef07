"""MPI_Reduce_local on device-resident operands through
``ompi_tpu.reduce_local(inbuf, inoutbuf, op)``, np=1, on one chip. The
reference applies the op to the host copies of the operands."""
from __future__ import annotations

import numpy as np

from benchmark import data


class Target:
    def __init__(self, device):
        from jax.sharding import SingleDeviceSharding
        self.device = device
        self.devices = [device]
        self.size = 1
        self.sharding = SingleDeviceSharding(device)


def setup(MPI, config):
    import jax
    if not MPI.Initialized():
        MPI.Init()
    return Target(jax.devices()[0])


def make_entries(MPI, target, phase, seed: int, salt: int):
    """Args of each entry: entry e runs case e mod len(cases)."""
    cases = phase["cases"]
    specs, ops = [], []
    for _ in range(phase["pool"]):
        for case in cases:
            elems = (phase["bytes_per_rank"]
                     // data.dtype(case["dtype"]).itemsize)
            specs += [((elems,), case["dtype"], case["amax"])] * 2
            ops.append(getattr(MPI, case["op"].upper()))
    bufs = data.make(seed, salt, specs, target.sharding)
    return [(bufs[2 * i], bufs[2 * i + 1], op) for i, op in enumerate(ops)]


def function(MPI, target):
    return MPI.reduce_local


def inputs(args):
    return np.asarray(args[0]), np.asarray(args[1])


def output(target, y) -> np.ndarray:
    return np.asarray(y)


_NP = {"sum": np.add, "prod": np.multiply, "max": np.maximum,
       "min": np.minimum, "band": np.bitwise_and, "bor": np.bitwise_or,
       "bxor": np.bitwise_xor}


def reference(host_inputs, case) -> np.ndarray:
    """inbuf op inoutbuf, in float64 or int64 (exact for the traffic's
    data)."""
    wide = np.int64 if data.dtype(case["dtype"]).kind in "iu" else np.float64
    a, b = (x.astype(wide) for x in host_inputs)
    return _NP[case["op"]](a, b)


def roofline_bytes(phase, n: int):
    """Two operands read and one result written, over HBM's peak."""
    return 3 * phase["bytes_per_rank"], "hbm_bytes_per_s"


def served(MPI, target) -> str:
    from ompi_tpu.native import loader
    return ("reduce_local on device arrays: op.fn (jax.numpy), the "
            "native table takes numpy only; native library "
            + ("loaded" if loader.get_lib() is not None else "absent"))
