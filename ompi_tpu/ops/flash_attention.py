"""Flash-attention block update — the ring-attention hot op, in Pallas.

The ring schedule (``parallel/ring_attention.py``) rotates K/V blocks
around the sequence-parallel axis and folds each block into running
flash accumulators (o, m, l). This module owns that fold:

- ``_block_kernel`` — the Pallas TPU kernel: per (batch*head, q-tile)
  program, loop K-tiles in VMEM, compute q·kᵀ on the MXU, apply the
  online-softmax update without ever materializing the (S, S) score
  matrix in HBM — the memory behavior flash attention exists for
  (HBM-bandwidth note in SURVEY §"Design for TPU").
- ``flash_block_update`` — the kernel's public entry. It refuses shapes
  Mosaic cannot tile; ``interpret=True`` runs the same kernel on CPU for
  tests.
- ``fold_jnp`` — the same online-softmax math in jnp: the kernel's
  numerical oracle, and the differentiable path training asks for.

Mask ``mode`` (traced scalar, SMEM): 0 = attend fully (earlier ring
block), 1 = causal diagonal (the resident block), 2 = fully masked
(later block). Fully-masked folds are identity by construction:
``exp(-inf - m)`` is 0 once ``m`` holds a real row max, which the
diag-first ring ordering guarantees.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

_NEG = -1e30
_F32_DOT = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------
# the jnp fold (training path + numerical oracle for the kernel tests)
# ---------------------------------------------------------------------
def fold_jnp(q, k, v, o, m, l, mode):
    """q: (BH, Sq, D) pre-scaled; k/v: (BH, Sk, D); o: (BH, Sq, D);
    m/l: (BH, Sq); mode: scalar int32."""
    s = jnp.einsum("bqd,bkd->bqk", q, k)
    Sq, Sk = q.shape[1], k.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1)
    allow = jnp.where(mode == 0, True,
                      jnp.where(mode == 1, row >= col, False))
    s = jnp.where(allow[None], s, _NEG)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + p.sum(axis=-1)
    o_new = o * corr[..., None] + jnp.einsum("bqk,bkd->bqd", p, v)
    return o_new, m_new, l_new


# ---------------------------------------------------------------------
# the Pallas kernel
# ---------------------------------------------------------------------
_LANES = 128     # m/l ride lane-replicated (bq, 128) tiles: Mosaic's
                 # minimum lane width — the official TPU flash kernels'
                 # scratch layout for the running max/denominator


def _block_kernel(mode_ref, q_ref, k_ref, v_ref, oi_ref, mi_ref, li_ref,
                  oo_ref, mo_ref, lo_ref, o_acc, m_acc, l_acc, *,
                  bq: int, bk: int, nk: int):
    """One (bh, q-tile, k-tile) program: fold this K/V tile into the
    q-tile's accumulators (VMEM scratch carries them across the k grid
    dimension, which Mosaic pipelines — K/V tile DMA overlaps compute).
    Score tiles live only in VMEM/registers, never HBM."""
    import jax.experimental.pallas as pl  # noqa: F401

    mode = mode_ref[0, 0]
    qi = pl.program_id(1)
    kt = pl.program_id(2)

    @pl.when(kt == 0)
    def _init():
        o_acc[...] = oi_ref[0].astype(jnp.float32)
        m_acc[...] = mi_ref[0].astype(jnp.float32)   # (bq, 128) repl.
        l_acc[...] = li_ref[0].astype(jnp.float32)

    q = q_ref[0].astype(jnp.float32)          # (bq, D)
    ks = k_ref[0].astype(jnp.float32)         # (bk, D)
    vs = v_ref[0].astype(jnp.float32)
    o, m, l = o_acc[...], m_acc[...], l_acc[...]
    # fp32 contraction: Mosaic's default for f32 operands is a reduced
    # bf16 precision (1e-3 off the f32 fold on a v5e at S=2048)
    s = jnp.dot(q, ks.T, precision=_F32_DOT,
                preferred_element_type=jnp.float32)
    row = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    col = kt * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    # boolean algebra (a scalar-condition select does not legalize
    # in Mosaic): full -> all, diag -> lower triangle, else none
    allow = (mode == 0) | ((mode == 1) & (row >= col))
    s = jnp.where(allow, s, jnp.float32(_NEG))   # f32 under x64 too
    m_new = jnp.maximum(m, s.max(axis=-1)[:, None])       # replicated
    p = jnp.exp(s - m_new[:, 0:1])
    corr = jnp.exp(m - m_new)                             # replicated
    l_new = l * corr + p.sum(axis=-1)[:, None]
    o_new = o * corr[:, 0:1] + jnp.dot(
        p, vs, precision=_F32_DOT, preferred_element_type=jnp.float32)
    o_acc[...], m_acc[...], l_acc[...] = o_new, m_new, l_new

    @pl.when(kt == nk - 1)
    def _flush():
        oo_ref[0] = o_acc[...]
        mo_ref[0] = m_acc[...]
        lo_ref[0] = l_acc[...]


def _i32(*idx):
    """Block indices as int32: under x64 a literal 0 traces as int64,
    which Mosaic cannot return from an index map."""
    return tuple(jnp.asarray(i, jnp.int32) for i in idx)


@functools.partial(jax.jit,
                   static_argnames=("bq", "bk", "interpret"))
def _pallas_fold(q, k, v, o, m, l, mode, *, bq: int, bk: int,
                 interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, Sq, D = q.shape
    Sk = k.shape[1]
    nk = Sk // bk
    grid = (BH, Sq // bq, nk)
    kern = functools.partial(_block_kernel, bq=bq, bk=bk, nk=nk)
    mode_arr = jnp.asarray(mode, jnp.int32).reshape(1, 1)
    # lane-replicate the running stats to the Mosaic-tileable layout
    m3 = jnp.broadcast_to(m[..., None], (BH, Sq, _LANES))
    l3 = jnp.broadcast_to(l[..., None], (BH, Sq, _LANES))

    qo_spec = pl.BlockSpec((1, bq, D), lambda bh, qi, kt: _i32(bh, qi, 0))
    kv_spec = pl.BlockSpec((1, bk, D), lambda bh, qi, kt: _i32(bh, kt, 0))
    ml_spec = pl.BlockSpec((1, bq, _LANES),
                           lambda bh, qi, kt: _i32(bh, qi, 0))
    specs = [
        pl.BlockSpec((1, 1), lambda bh, qi, kt: _i32(0, 0),
                     memory_space=pltpu.SMEM),                 # mode
        qo_spec, kv_spec, kv_spec, qo_spec, ml_spec, ml_spec,
    ]
    oo, mo, lo = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=specs,
        out_specs=[qo_spec, ml_spec, ml_spec],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, D), jnp.float32),
            jax.ShapeDtypeStruct((BH, Sq, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((BH, Sq, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),        # o accumulator
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running max
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running denom
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(mode_arr, q, k, v, o, m3, l3)
    return oo, mo[..., 0], lo[..., 0]


def _tile_sizes(Sq: int, Sk: int) -> Tuple[int, int]:
    bq = Sq if Sq <= 128 else 128
    bk = Sk if Sk <= 128 else 128
    return bq, bk


def flash_block_update(q, k, v, o, m, l, mode, *,
                       interpret: bool = False):
    """Fold one K/V block into the flash accumulators with the Pallas
    kernel.

    Args (all float32, q pre-scaled):
      q: (BH, Sq, D); k, v: (BH, Sk, D); o: (BH, Sq, D); m, l: (BH, Sq)
      mode: traced int — 0 full, 1 causal diagonal, 2 fully masked
      interpret: run the kernel in the Pallas interpreter (CPU tests)
    Returns (o, m, l) updated. Raises ValueError for a shape Mosaic
    cannot tile: q/o blocks are (bq, D) and score tiles (bq, bk), whose
    last two dims must be multiples of (8, 128).
    """
    Sq, D = q.shape[1:]
    Sk = k.shape[1]
    bq, bk = _tile_sizes(Sq, Sk)
    if not (Sq % bq == 0 and Sk % bk == 0 and bq % 8 == 0
            and bk % 128 == 0 and D % 128 == 0):
        raise ValueError(
            f"flash_block_update: Sq={Sq}, Sk={Sk}, D={D} do not tile "
            f"as ({bq}, {bk}) blocks; need Sq % 8, Sk % 128 and D % 128 "
            "all zero (use fold_jnp for other shapes)")
    return _pallas_fold(q, k, v, o, m, l, mode,
                        bq=bq, bk=bk, interpret=interpret)
