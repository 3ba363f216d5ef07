"""Inputs made on the device from the seed, and the sample of calls
whose outputs are compared.

Every value is an integer held in the case's type, small enough that
the call's result is exact in that type in any order of combining
(``amax`` in the traffic file), and too wide for the next type down,
so a lower-precision path cannot match it. ``amax: null`` means
random bits (the bitwise ops).
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import numpy as np

U32 = 0xFFFFFFFF


def dtype(name: str) -> np.dtype:
    """A numpy dtype by name, bfloat16 included."""
    import ml_dtypes
    return np.dtype(getattr(ml_dtypes, name, name))


def key_data(seed: int, salt: int) -> np.ndarray:
    """A threefry key for (seed, salt) as host data: the seed is any
    non-negative integer, so its bits above 32 are folded in, and the
    key is an argument of the maker, which compiles once for all seeds."""
    return np.array([(seed >> 32) & U32 ^ (salt * 0x9E3779B1 & U32),
                     seed & U32], np.uint32)


def _values(k, shape, dtype, amax):
    import jax
    import jax.numpy as jnp
    if amax is None:
        bits = jax.random.bits(k, shape, jnp.uint32)
        return jax.lax.bitcast_convert_type(bits, jnp.dtype(dtype))
    ints = jax.random.randint(k, shape, -amax, amax + 1, jnp.int32)
    return ints.astype(jnp.dtype(dtype))


@functools.lru_cache(maxsize=None)
def _maker(specs: Tuple[Tuple[tuple, str, object], ...], shardings):
    import jax

    def make(kd):
        k = jax.random.wrap_key_data(kd, impl="threefry2x32")
        return tuple(_values(jax.random.fold_in(k, i), shape, dtype, amax)
                     for i, (shape, dtype, amax) in enumerate(specs))
    return jax.jit(make, out_shardings=shardings)


def make(seed: int, salt: int, specs: Sequence[Tuple[tuple, str, object]],
         sharding) -> List:
    """One jitted call: an array for each (shape, dtype, amax) of
    ``specs``, all placed in ``sharding``."""
    specs = tuple((tuple(s), str(d), a) for s, d, a in specs)
    out = _maker(specs, (sharding,) * len(specs))(key_data(seed, salt))
    return list(out)


def sample_plan(seed: int, salt: int, blocks: int, per_block: int,
                within: int, entries: int, sample_blocks=None
                ) -> List[dict]:
    """For each block, {call index: entry} of the calls whose outputs
    are kept. Sample j of block b takes entry (b * per_block + j) mod
    ``entries`` at a repetition drawn from the seed, so every entry is
    compared as the blocks go by. With ``sample_blocks``, only that
    many of the blocks, drawn from the seed, keep any."""
    rng = np.random.default_rng([seed, salt])
    reps = max(within // entries, 1)
    chosen = (range(blocks) if sample_blocks is None else
              rng.choice(blocks, min(sample_blocks, blocks), replace=False))
    plan = [{} for _ in range(blocks)]
    for b in sorted(int(b) for b in chosen):
        for j in range(per_block):
            e = (b * per_block + j) % entries
            plan[b][int(rng.integers(reps)) * entries + e] = e
    return plan
