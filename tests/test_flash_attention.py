"""The flash block-update Pallas kernel against its jnp oracle.

The kernel runs here in the Pallas interpreter (``interpret=True``);
tests/test_chip_compile.py compiles the same kernel for a v5e chip.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.ops.flash_attention import flash_block_update, fold_jnp


def _inputs(bh, sq, sk, d, seed=0):
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.standard_normal(s).astype(np.float32))
               for s in ((bh, sq, d), (bh, sk, d), (bh, sk, d)))
    q = q * d ** -0.5
    o = jnp.zeros((bh, sq, d), jnp.float32)
    m = jnp.full((bh, sq), -1e30, jnp.float32)
    l = jnp.zeros((bh, sq), jnp.float32)
    return q, k, v, o, m, l


@pytest.mark.parametrize("mode", [0, 1, 2])
def test_kernel_matches_fold(mode):
    args = _inputs(2, 16, 128, 128)
    got = flash_block_update(*args, jnp.int32(mode), interpret=True)
    want = fold_jnp(*args, jnp.int32(mode))
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-5, atol=2e-5)


def test_kernel_two_k_tiles_accumulate():
    """Sk = 256 runs two k-tiles through the VMEM accumulators: a
    second fold over fresh K/V must match the oracle's."""
    args = _inputs(1, 128, 256, 128, seed=1)
    got = flash_block_update(*args, jnp.int32(0), interpret=True)
    want = fold_jnp(*args, jnp.int32(0))
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("sq,sk,d", [(16, 64, 128), (12, 128, 128),
                                     (16, 128, 64)])
def test_unaligned_shape_raises(sq, sk, d):
    args = _inputs(1, sq, sk, d)
    with pytest.raises(ValueError, match="do not tile"):
        flash_block_update(*args, jnp.int32(0), interpret=True)
