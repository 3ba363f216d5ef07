"""Datatype engine: predefined + derived datatypes with a device-lowerable
layout description.

Behavioral spec from the reference: ``ompi/datatype`` (MPI layer,
constructors incl. vector/indexed/struct/subarray/resized) over the OPAL
convertor (``opal/datatype/opal_convertor.c`` — iovec-walking pack/unpack
with resumable positioning).

TPU-native re-design: there is no byte-walking convertor on the critical
path. A datatype over a single base element type is described by a *flat
element-index map*: ``indices`` (positions of the datatype's ``count``
base elements within one ``extent``-element window). Pack/unpack then
lower to XLA ``take``/``scatter`` on device (HBM-resident, fused by XLA)
or to NumPy fancy indexing on host (with an optional C++ fast path in
``ompi_tpu.native``). Heterogeneous struct types (mixed base types) are
host-only byte layouts, as device arrays are homogeneous.
"""
from __future__ import annotations

import itertools
from typing import List, Optional, Sequence, Tuple

import ml_dtypes
import numpy as np


def coalesce_runs(idx: np.ndarray):
    """Coalesce a sorted-or-not element-index array into (offsets,
    lengths) of runs of consecutive indices, preserving order."""
    idx = np.asarray(idx, dtype=np.int64)
    if idx.size == 0:
        return np.empty(0, np.int64), np.empty(0, np.int64)
    breaks = np.where(np.diff(idx) != 1)[0]
    starts = np.concatenate(([0], breaks + 1))
    ends = np.concatenate((breaks, [idx.size - 1]))
    return idx[starts], (ends - starts + 1).astype(np.int64)


class Datatype:
    """An MPI datatype.

    Attributes:
      base:     numpy dtype of the underlying elements (None => raw bytes).
      indices:  int64 array of element offsets (in base elements) selected
                by one instance of this type, in *serialization order*.
      extent:   extent in base elements (stride between consecutive
                instances, MPI_Type_get_extent semantics).
      count:    len(indices) — number of base elements per instance.
    """

    _uid_counter = itertools.count(1)

    def __init__(self, base: Optional[np.dtype], indices: np.ndarray,
                 extent: int, *, name: str = "", predefined: bool = False,
                 pair: bool = False, lb: int = 0):
        self.base = np.dtype(base) if base is not None else None
        self.indices = np.asarray(indices, dtype=np.int64)
        self.extent = int(extent)
        self.lb = int(lb)
        self.name = name
        self.predefined = predefined
        self.pair = pair               # MINLOC/MAXLOC pair type
        self._committed = predefined
        # identity for compiled-program caches (datatypes are immutable
        # once committed; names are not unique)
        self.uid = next(Datatype._uid_counter)
        self._flat_cache: dict = {}    # count -> flat index array

    # -- introspection (MPI_Type_get_extent / MPI_Type_size) ---------------
    @property
    def count(self) -> int:
        return int(self.indices.size)

    def get_size(self) -> int:
        """Size in bytes of the data content (MPI_Type_size)."""
        return self.count * (self.base.itemsize if self.base else 1)

    def get_extent(self) -> Tuple[int, int]:
        """(lb, extent) in base-element units (byte-free redesign: the
        framework addresses typed elements, not raw memory)."""
        return (self.lb, self.extent)

    def get_true_extent(self) -> Tuple[int, int]:
        if self.count == 0:
            return (0, 0)
        lo = int(self.indices.min())
        hi = int(self.indices.max()) + 1
        return (lo, hi - lo)

    @property
    def is_contiguous(self) -> bool:
        n = self.count
        return (n == self.extent
                and bool(np.array_equal(self.indices, np.arange(n))))

    def commit(self) -> "Datatype":
        """MPI_Type_commit: finalize and precompute the flat index map."""
        self.indices = np.ascontiguousarray(self.indices, dtype=np.int64)
        self._committed = True
        return self

    def free(self) -> None:
        if self.predefined:
            raise ValueError("cannot free a predefined datatype")
        self._committed = False

    # -- constructors (MPI_Type_*) -----------------------------------------
    def create_contiguous(self, count: int) -> "Datatype":
        idx = (np.arange(count)[:, None] * self.extent
               + self.indices[None, :]).ravel()
        return Datatype(self.base, idx, count * self.extent,
                        name=f"contig({count},{self.name})")

    def create_vector(self, count: int, blocklength: int,
                      stride: int) -> "Datatype":
        """count blocks of blocklength instances, stride instances apart."""
        block = (np.arange(blocklength)[:, None] * self.extent
                 + self.indices[None, :]).ravel()
        idx = (np.arange(count)[:, None] * (stride * self.extent)
               + block[None, :]).ravel()
        extent = ((count - 1) * stride + blocklength) * self.extent
        return Datatype(self.base, idx, extent,
                        name=f"vector({count},{blocklength},{stride})")

    def create_indexed(self, blocklengths: Sequence[int],
                       displacements: Sequence[int]) -> "Datatype":
        parts: List[np.ndarray] = []
        for bl, disp in zip(blocklengths, displacements):
            block = (np.arange(bl)[:, None] * self.extent
                     + self.indices[None, :]).ravel()
            parts.append(disp * self.extent + block)
        idx = np.concatenate(parts) if parts else np.empty(0, np.int64)
        extent = max((d + b for d, b in zip(displacements, blocklengths)),
                     default=0) * self.extent
        return Datatype(self.base, idx, extent, name="indexed")

    def create_indexed_block(self, blocklength: int,
                             displacements: Sequence[int]) -> "Datatype":
        return self.create_indexed([blocklength] * len(displacements),
                                   displacements)

    def create_subarray(self, sizes: Sequence[int], subsizes: Sequence[int],
                        starts: Sequence[int], order: str = "C") -> "Datatype":
        """MPI_Type_create_subarray over a C- or F-ordered array."""
        sizes = list(sizes)
        subsizes = list(subsizes)
        starts = list(starts)
        if order.upper() == "F":
            sizes, subsizes, starts = sizes[::-1], subsizes[::-1], starts[::-1]
        grids = np.meshgrid(*[np.arange(st, st + ss)
                              for st, ss in zip(starts, subsizes)],
                            indexing="ij")
        flat = np.ravel_multi_index([g.ravel() for g in grids], sizes)
        idx = (flat[:, None] * self.extent + self.indices[None, :]).ravel()
        extent = int(np.prod(sizes)) * self.extent
        return Datatype(self.base, idx, extent, name="subarray")

    def create_resized(self, lb: int, extent: int) -> "Datatype":
        return Datatype(self.base, self.indices.copy(), extent,
                        name=f"resized({self.name})", lb=lb)

    @staticmethod
    def create_struct(blocklengths: Sequence[int],
                      displacements: Sequence[int],
                      types: Sequence["Datatype"]) -> "Datatype":
        """Homogeneous struct (all fields share one base dtype) lowers to
        an indexed layout; heterogeneous structs are not representable on
        device (jax arrays are homogeneous) and raise — stage per-field or
        use a pair type instead."""
        bases = {t.base for t in types}
        if len(bases) != 1:
            raise TypeError(
                "heterogeneous MPI_Type_create_struct is host-only; "
                "decompose into per-field messages for device transfer")
        base_t = types[0]
        parts: List[np.ndarray] = []
        for bl, disp, t in zip(blocklengths, displacements, types):
            block = (np.arange(bl)[:, None] * t.extent
                     + t.indices[None, :]).ravel()
            parts.append(disp + block)
        idx = np.concatenate(parts) if parts else np.empty(0, np.int64)
        extent = max((d + bl * t.extent for d, bl, t in
                      zip(displacements, blocklengths, types)), default=0)
        return Datatype(base_t.base, idx, extent, name="struct")

    def runs(self):
        """Coalesce the element-index map into contiguous runs
        (offset, length) — the native convertor's unit of work (the
        re-design of the reference convertor's contiguous-with-gaps
        fast path). Cached after first call."""
        r = getattr(self, "_runs", None)
        if r is None:
            r = self._runs = coalesce_runs(self.indices)
        return r

    def flat_indices(self, count: int) -> np.ndarray:
        """Flat element indices for ``count`` consecutive instances —
        cached per instance (rebuilt index maps were a measured tax on
        the derived-datatype hot path)."""
        got = self._flat_cache.get(count)
        if got is None:
            got = (np.arange(count)[:, None] * self.extent
                   + self.indices[None, :]).ravel()
            if len(self._flat_cache) < 64:
                self._flat_cache[count] = got
        return got

    def __repr__(self):
        return f"Datatype({self.name or self.base}, count={self.count})"


def _predef(np_dtype, name: str, pair: bool = False) -> Datatype:
    return Datatype(np_dtype, np.array([0]), 1, name=name, predefined=True,
                    pair=pair)


# Predefined datatypes (ompi/datatype predefined set; names mirror MPI).
FLOAT = _predef(np.float32, "float")
DOUBLE = _predef(np.float64, "double")
FLOAT16 = _predef(np.float16, "float16")
BFLOAT16 = _predef(ml_dtypes.bfloat16, "bfloat16")
INT = _predef(np.int32, "int")
LONG = _predef(np.int64, "long")
SHORT = _predef(np.int16, "short")
CHAR = _predef(np.int8, "char")
BYTE = _predef(np.uint8, "byte")
UNSIGNED = _predef(np.uint32, "unsigned")
UNSIGNED_LONG = _predef(np.uint64, "unsigned_long")
INT8_T = _predef(np.int8, "int8_t")
INT16_T = _predef(np.int16, "int16_t")
INT32_T = _predef(np.int32, "int32_t")
INT64_T = _predef(np.int64, "int64_t")
UINT8_T = _predef(np.uint8, "uint8_t")
UINT16_T = _predef(np.uint16, "uint16_t")
UINT32_T = _predef(np.uint32, "uint32_t")
UINT64_T = _predef(np.uint64, "uint64_t")
C_BOOL = _predef(np.bool_, "c_bool")
C_FLOAT_COMPLEX = _predef(np.complex64, "c_float_complex")
C_DOUBLE_COMPLEX = _predef(np.complex128, "c_double_complex")
# Pair types for MINLOC/MAXLOC: value/index pairs carried as a trailing
# axis of size 2 in the value dtype (redesign of struct{float;int} pairs).
FLOAT_INT = _predef(np.float32, "float_int", pair=True)
DOUBLE_INT = _predef(np.float64, "double_int", pair=True)
LONG_INT = _predef(np.int64, "long_int", pair=True)
SHORT_INT = _predef(np.int16, "short_int", pair=True)
TWOINT = _predef(np.int32, "2int", pair=True)

_BY_NP: dict = {}
for _t in (FLOAT, DOUBLE, FLOAT16, BFLOAT16, INT, LONG, SHORT, CHAR, BYTE,
           UNSIGNED, UNSIGNED_LONG, C_BOOL, C_FLOAT_COMPLEX,
           C_DOUBLE_COMPLEX):
    _BY_NP.setdefault(np.dtype(_t.base), _t)


def from_numpy_dtype(dt) -> Datatype:
    """Map a numpy dtype to the matching predefined Datatype."""
    dt = np.dtype(dt)
    try:
        return _BY_NP[dt]
    except KeyError:
        raise TypeError(f"no predefined MPI datatype for {dt}") from None
