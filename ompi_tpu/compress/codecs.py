"""Block-scaled quantization codecs — the EQuARX kernel layer.

A codec maps a float payload to (codes, scales): ``codes`` is the
1-byte-per-element wire representation, ``scales`` one float32 per
block of ``block`` elements (the max-abs of the block divided by the
code range), so dequantization is a single fused multiply. Two real
codecs plus the null codec:

- ``int8_block``: symmetric round-to-nearest int8; per-element error
  is bounded by ``scale / 2 = block_maxabs / 254``.
- ``fp8_block``: scale-to-448 then cast to float8_e4m3fn (3 mantissa
  bits); per-element error bounded by ``block_maxabs / 16`` (worst
  relative error 2^-4 on the largest element), much tighter for small
  elements — the trade EQuARX §4 describes (uniform vs logarithmic
  code spacing).
- ``null``: identity (codes are the raw bytes; for wiring tests and
  as the fallback the registry hands out for unknown names).

Non-finite policy (tested): a block containing any inf/nan gets a
non-finite scale, so the whole block dequantizes to NaN — quantization
*poisons the block* rather than silently laundering an overflow into a
finite value. MPI reduction semantics already propagate NaN through
sums, so a poisoned block behaves like the uncompressed path at block
granularity.

Both a NumPy implementation (the host/per-rank wire path — pml staging)
and a jittable jnp implementation (composed into the XLA ring/hier
schedules by coll/compressed) are provided; the property tests assert
the two round-trip within the same bound.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np
from ml_dtypes import float8_e4m3fn as _f8

DEFAULT_BLOCK = 256

_INT8_RANGE = 127.0
_F8_RANGE = 448.0                        # e4m3fn max finite


def _pad_blocks(flat: np.ndarray, block: int) -> Tuple[np.ndarray, int]:
    nb = -(-flat.size // block) if flat.size else 1
    pad = nb * block - flat.size
    if pad:
        flat = np.concatenate([flat, np.zeros(pad, flat.dtype)])
    return flat.reshape(nb, block), pad


class Codec:
    """Base: name, wire cost model, numpy encode/decode, jnp kernels."""

    name = "base"
    code_bytes = 1                       # wire bytes per element

    def wire_bytes(self, nelems: int, block: int) -> int:
        """Wire bytes for ``nelems`` payload elements (codes + scales)."""
        nb = -(-nelems // block) if nelems else 1
        return nelems * self.code_bytes + nb * 4

    # -- numpy (host / per-rank wire path) -----------------------------
    def encode(self, arr: np.ndarray, block: int = DEFAULT_BLOCK
               ) -> Tuple[np.ndarray, np.ndarray]:
        raise NotImplementedError

    def decode(self, codes: np.ndarray, scales: np.ndarray,
               shape: Tuple[int, ...], dtype: Any,
               block: int = DEFAULT_BLOCK) -> np.ndarray:
        raise NotImplementedError

    # -- jnp (device path; shapes static at trace time) ----------------
    def jnp_quant(self, x, block: int):
        raise NotImplementedError

    def jnp_dequant(self, codes, scales, total: int, dtype, block: int):
        raise NotImplementedError

    def error_bound(self, block_maxabs):
        """Per-element absolute error bound given the block max-abs."""
        raise NotImplementedError


class NullCodec(Codec):
    """Identity codec: full-width wire, zero error. Exists so the
    compressed schedules can be exercised (and A/B'd) with compression
    arithmetic removed from the comparison."""

    name = "null"

    def wire_bytes(self, nelems: int, block: int) -> int:
        return nelems * 4                # payload travels full width

    def encode(self, arr, block=DEFAULT_BLOCK):
        flat = np.ascontiguousarray(arr).reshape(-1)
        return flat.copy(), np.ones(1, np.float32)

    def decode(self, codes, scales, shape, dtype, block=DEFAULT_BLOCK):
        return np.asarray(codes, dtype=dtype).reshape(shape)

    def jnp_quant(self, x, block):
        import jax.numpy as jnp
        return jnp.asarray(x), jnp.ones((1,), jnp.float32)

    def jnp_dequant(self, codes, scales, total, dtype, block):
        import jax.numpy as jnp
        return jnp.asarray(codes, dtype)[:total]

    def error_bound(self, block_maxabs):
        return np.zeros_like(np.asarray(block_maxabs, np.float64))


class Int8BlockCodec(Codec):
    """Symmetric per-block int8: scale = maxabs/127, codes = rint(x/s)."""

    name = "int8_block"

    def encode(self, arr, block=DEFAULT_BLOCK):
        # pass-lean hot path (the wire layer calls this on multi-MB
        # payloads): no-copy f32 view when possible, one abs/max pass,
        # one fused multiply into a reusable temp, in-place rint, one
        # int8 store — the naive astype/where chain cost 4x
        flat = np.ascontiguousarray(arr, dtype=np.float32).reshape(-1)
        blocks, _pad = _pad_blocks(flat, block)
        maxabs = np.abs(blocks).max(axis=1)
        scales = np.maximum(maxabs, 1e-30) * np.float32(1 / _INT8_RANGE)
        # non-finite blocks: scale -> NaN poisons the whole block on
        # dequant (the documented policy); the codes' values there are
        # irrelevant, so the payload-wide sanitize pass only runs when
        # some block actually held inf/nan (the finite check is on the
        # tiny per-block scale vector, not the payload)
        finite = np.isfinite(maxabs)
        all_finite = bool(finite.all())
        if not all_finite:
            scales[~finite] = np.nan
        scales = scales.astype(np.float32, copy=False)
        with np.errstate(invalid="ignore", over="ignore"):
            tmp = blocks * (np.float32(1.0) / scales)[:, None]
            np.rint(tmp, out=tmp)
            if not all_finite:
                np.nan_to_num(tmp, copy=False, nan=0.0,
                              posinf=_INT8_RANGE, neginf=-_INT8_RANGE)
            codes = tmp.astype(np.int8)
        return codes.reshape(-1), scales

    def decode(self, codes, scales, shape, dtype, block=DEFAULT_BLOCK):
        scales = np.asarray(scales, np.float32)
        out = codes.astype(np.float32).reshape(len(scales), block)
        out *= scales[:, None]
        total = int(np.prod(shape)) if shape else 1
        out = out.reshape(-1)[:total].reshape(shape)
        return out.astype(dtype, copy=False)

    def jnp_quant(self, x, block):
        import jax.numpy as jnp
        flat = x.reshape(-1).astype(jnp.float32)
        nb = -(-flat.shape[0] // block) if flat.shape[0] else 1
        flat = jnp.pad(flat, (0, nb * block - flat.shape[0]))
        blocks = flat.reshape(nb, block)
        maxabs = jnp.max(jnp.abs(blocks), axis=1)
        scales = jnp.where(jnp.isfinite(maxabs),
                           jnp.maximum(maxabs, 1e-30) / _INT8_RANGE,
                           jnp.nan).astype(jnp.float32)
        codes = jnp.rint(blocks / scales[:, None]).astype(jnp.int8)
        return codes.reshape(-1), scales

    def jnp_dequant(self, codes, scales, total, dtype, block):
        import jax.numpy as jnp
        blocks = codes.astype(jnp.float32).reshape(scales.shape[0], block)
        out = blocks * scales[:, None]
        return out.reshape(-1)[:total].astype(dtype)

    def error_bound(self, block_maxabs):
        m = np.asarray(block_maxabs, np.float64)
        # rint is within 0.5 code; the 1e-30 floor adds nothing at
        # these magnitudes but keeps the all-zero block exact
        return m / (2.0 * _INT8_RANGE) + 1e-30


class Fp8BlockCodec(Codec):
    """Per-block scale-to-448 + e4m3 cast: logarithmic code spacing."""

    name = "fp8_block"

    def encode(self, arr, block=DEFAULT_BLOCK):
        flat = np.ascontiguousarray(arr, dtype=np.float32).reshape(-1)
        blocks, _pad = _pad_blocks(flat, block)
        maxabs = np.abs(blocks).max(axis=1)
        scales = np.maximum(maxabs, 1e-30) * np.float32(1 / _F8_RANGE)
        finite = np.isfinite(maxabs)
        all_finite = bool(finite.all())
        if not all_finite:
            scales[~finite] = np.nan
        scales = scales.astype(np.float32, copy=False)
        with np.errstate(invalid="ignore", over="ignore"):
            scaled = blocks * (np.float32(1.0) / scales)[:, None]
            if not all_finite:
                np.nan_to_num(scaled, copy=False, nan=0.0,
                              posinf=_F8_RANGE, neginf=-_F8_RANGE)
            codes = scaled.astype(_f8)
        # int8 view for the wire: a raw byte payload transports
        # identically whatever the receiving numpy knows about fp8
        return codes.reshape(-1).view(np.int8), scales

    def decode(self, codes, scales, shape, dtype, block=DEFAULT_BLOCK):
        scales = np.asarray(scales, np.float32)
        out = np.asarray(codes, np.int8).view(_f8) \
            .astype(np.float32).reshape(len(scales), block)
        out *= scales[:, None]
        total = int(np.prod(shape)) if shape else 1
        out = out.reshape(-1)[:total].reshape(shape)
        return out.astype(dtype, copy=False)

    def jnp_quant(self, x, block):
        import jax
        import jax.numpy as jnp
        flat = x.reshape(-1).astype(jnp.float32)
        nb = -(-flat.shape[0] // block) if flat.shape[0] else 1
        flat = jnp.pad(flat, (0, nb * block - flat.shape[0]))
        blocks = flat.reshape(nb, block)
        maxabs = jnp.max(jnp.abs(blocks), axis=1)
        scales = jnp.where(jnp.isfinite(maxabs),
                           jnp.maximum(maxabs, 1e-30) / _F8_RANGE,
                           jnp.nan).astype(jnp.float32)
        codes = (blocks / scales[:, None]).astype(jnp.float8_e4m3fn)
        # bitcast to int8 so every collective primitive (ppermute,
        # all_gather, all_to_all) moves a plain byte payload
        wire = jax.lax.bitcast_convert_type(codes, jnp.int8)
        return wire.reshape(-1), scales

    def jnp_dequant(self, codes, scales, total, dtype, block):
        import jax
        import jax.numpy as jnp
        f8 = jax.lax.bitcast_convert_type(
            codes.reshape(scales.shape[0], block), jnp.float8_e4m3fn)
        out = f8.astype(jnp.float32) * scales[:, None]
        return out.reshape(-1)[:total].astype(dtype)

    def error_bound(self, block_maxabs):
        # worst relative error 2^-4 lands on the largest element:
        # 448 * 2^-4 * scale = maxabs / 16 (plus the same zero floor)
        return np.asarray(block_maxabs, np.float64) / 16.0 + 1e-30


_REGISTRY: Dict[str, Codec] = {
    "null": NullCodec(),
    "int8_block": Int8BlockCodec(),
    "fp8_block": Fp8BlockCodec(),
}


def get_codec(name: str) -> Codec:
    """Codec by name; unknown names get the null codec (a typo'd MCA
    var must not corrupt data — it just stops compressing)."""
    return _REGISTRY.get(name, _REGISTRY["null"])


def codec_names():
    return sorted(_REGISTRY)
