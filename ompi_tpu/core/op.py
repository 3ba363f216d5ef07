"""Reduction-op framework: MPI_Op -> XLA reduction computation.

Behavioral spec from the reference: predefined ops declared at
``ompi/op/op.c:73-80``; the (op x type) kernel table in
``ompi/mca/op/base/op_base_functions.c`` (2,418 LoC of scalar loops) with
SIMD components (``ompi/mca/op/avx``) selected per (op x type) by
``ompi/mca/op/base/op_base_op_select.c``.

TPU-native re-design: there is no kernel table. An op is (a) a JAX binary
combiner usable in device-side folds, and (b) where XLA has a fused
collective primitive for it (psum/pmax/pmin), a tag the coll component
uses to pick that primitive instead of an allgather+fold. MINLOC/MAXLOC
operate on (value, index) pair types carried as a trailing axis of size 2.
User-defined ops (MPI_Op_create) supply a JAX-traceable combiner; the
``commute`` flag gates algorithm choice exactly as the reference documents
(``coll_base_allreduce.c:291-294``).
"""
from __future__ import annotations

import itertools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ompi_tpu.trace import core as _trace


_op_counter = itertools.count()


class Op:
    """An MPI reduction operator.

    ``fn(a, b)`` must be a JAX-traceable elementwise combiner.
    ``xla_prim`` in {"sum", "max", "min", None}: when set, collectives may
    lower to the corresponding fused XLA collective (psum/pmax/pmin).
    """

    def __init__(self, fn: Callable, *, commute: bool = True,
                 name: str = "user_op", xla_prim: Optional[str] = None,
                 is_loc: bool = False, predefined: bool = False):
        self.fn = fn
        self.commute = commute
        self.name = name
        # Cache identity: distinct user ops share the default name, so
        # executable caches keyed on the name alone would collide.
        self.uid = name if predefined else f"{name}#{next(_op_counter)}"
        self.xla_prim = xla_prim
        self.is_loc = is_loc         # MINLOC/MAXLOC pair semantics
        self.predefined = predefined
        self._frozen = predefined

    def __call__(self, a, b):
        return self.fn(a, b)

    def __repr__(self):
        return f"Op({self.name})"

    def is_commute(self) -> bool:
        return self.commute

    def free(self) -> None:
        if self.predefined:
            raise ValueError("cannot free a predefined op")
        self.fn = None

    def reduce_tree(self, stacked, axis: int = 0):
        """Fold ``stacked`` along ``axis`` with this op.

        For predefined arithmetic ops this is a single jnp reduction (XLA
        emits a tree); for user ops an associative fold via binary
        splitting, preserving rank order for non-commutative ops (the
        reference documents the same ordering constraint at
        ``coll_base_allreduce.c:291-294``).
        """
        n = stacked.shape[axis]
        if n == 1:
            return jax.lax.index_in_dim(stacked, 0, axis, keepdims=False)
        if self.name in _JNP_REDUCERS:
            return _JNP_REDUCERS[self.name](stacked, axis)
        # Ordered binary-splitting fold: combines (0..k) with (k..n) so the
        # result equals left-to-right application for associative ops.
        def fold(lo, hi):
            if hi - lo == 1:
                return jax.lax.index_in_dim(stacked, lo, axis, keepdims=False)
            mid = (lo + hi) // 2
            return self.fn(fold(lo, mid), fold(mid, hi))
        return fold(0, n)


def _land(a, b):
    return jnp.logical_and(a != 0, b != 0).astype(a.dtype)


def _lor(a, b):
    return jnp.logical_or(a != 0, b != 0).astype(a.dtype)


def _lxor(a, b):
    return jnp.logical_xor(a != 0, b != 0).astype(a.dtype)


def _minloc(a, b):
    """Pair reduce on trailing axis [..., 2] = (value, index); ties pick
    the lower index — MPI MINLOC semantics (op_base_functions.c pair ops)."""
    av, ai = a[..., 0], a[..., 1]
    bv, bi = b[..., 0], b[..., 1]
    take_a = (av < bv) | ((av == bv) & (ai <= bi))
    return jnp.stack([jnp.where(take_a, av, bv),
                      jnp.where(take_a, ai, bi)], axis=-1)


def _maxloc(a, b):
    av, ai = a[..., 0], a[..., 1]
    bv, bi = b[..., 0], b[..., 1]
    take_a = (av > bv) | ((av == bv) & (ai <= bi))
    return jnp.stack([jnp.where(take_a, av, bv),
                      jnp.where(take_a, ai, bi)], axis=-1)


_JNP_REDUCERS = {
    "sum": lambda x, ax: jnp.sum(x, axis=ax),
    "prod": lambda x, ax: jnp.prod(x, axis=ax),
    "max": lambda x, ax: jnp.max(x, axis=ax),
    "min": lambda x, ax: jnp.min(x, axis=ax),
    "band": lambda x, ax: jax.lax.reduce(x, jnp.bitwise_not(jnp.zeros((), x.dtype)),
                                         jax.lax.bitwise_and, (ax,)),
    "bor": lambda x, ax: jax.lax.reduce(x, jnp.array(0, x.dtype),
                                        jax.lax.bitwise_or, (ax,)),
    "bxor": lambda x, ax: jax.lax.reduce(x, jnp.array(0, x.dtype),
                                         jax.lax.bitwise_xor, (ax,)),
}

def _np_logical(npfn):
    """MPI logical ops yield 0/1 IN THE OPERAND TYPE (a bool result
    would change the element size under typed byte-window views)."""
    def fn(a, b):
        return npfn(a, b).astype(np.asarray(b).dtype)
    return fn


def _np_minloc(a, b):
    a, b = np.asarray(a), np.asarray(b)
    av, ai = a[..., 0], a[..., 1]
    bv, bi = b[..., 0], b[..., 1]
    take_a = (av < bv) | ((av == bv) & (ai <= bi))
    return np.stack([np.where(take_a, av, bv),
                     np.where(take_a, ai, bi)], axis=-1)


def _np_maxloc(a, b):
    a, b = np.asarray(a), np.asarray(b)
    av, ai = a[..., 0], a[..., 1]
    bv, bi = b[..., 0], b[..., 1]
    take_a = (av > bv) | ((av == bv) & (ai <= bi))
    return np.stack([np.where(take_a, av, bv),
                     np.where(take_a, ai, bi)], axis=-1)


# Dtype-preserving numpy combiners for the predefined ops — the HOST
# fold table (the op/base scalar-loop role). Host tiers must never use
# the jnp combiners on numpy operands: without x64 enabled jax would
# silently downcast 64-bit operands to 32-bit. Shared by the per-rank
# host collectives and the RMA accumulate path.
NP_COMBINERS = {
    "sum": np.add,
    "prod": np.multiply,
    "max": np.maximum,
    "min": np.minimum,
    "band": np.bitwise_and,
    "bor": np.bitwise_or,
    "bxor": np.bitwise_xor,
    "land": _np_logical(np.logical_and),
    "lor": _np_logical(np.logical_or),
    "lxor": _np_logical(np.logical_xor),
    "minloc": _np_minloc,
    "maxloc": _np_maxloc,
}

SUM = Op(jnp.add, name="sum", xla_prim="sum", predefined=True)
PROD = Op(jnp.multiply, name="prod", predefined=True)
MAX = Op(jnp.maximum, name="max", xla_prim="max", predefined=True)
MIN = Op(jnp.minimum, name="min", xla_prim="min", predefined=True)
LAND = Op(_land, name="land", predefined=True)
LOR = Op(_lor, name="lor", predefined=True)
LXOR = Op(_lxor, name="lxor", predefined=True)
BAND = Op(jnp.bitwise_and, name="band", predefined=True)
BOR = Op(jnp.bitwise_or, name="bor", predefined=True)
BXOR = Op(jnp.bitwise_xor, name="bxor", predefined=True)
MINLOC = Op(_minloc, name="minloc", is_loc=True, predefined=True)
MAXLOC = Op(_maxloc, name="maxloc", is_loc=True, predefined=True)
# RMA accumulate ops (MPI-3): REPLACE takes the incoming value, NO_OP keeps
# the target value (osc accumulate semantics, ompi/op/op.c).
REPLACE = Op(lambda a, b: b, name="replace", commute=False, predefined=True)
NO_OP = Op(lambda a, b: a, name="no_op", commute=False, predefined=True)


def op_create(fn: Callable, commute: bool = True, name: str = "user_op") -> Op:
    """MPI_Op_create equivalent: ``fn`` is a JAX-traceable binary combiner."""
    return Op(fn, commute=commute, name=name)


def reduce_local(inbuf, inoutbuf, op: Op):
    """MPI_Reduce_local: combine ``inbuf`` into ``inoutbuf`` with ``op``
    (no communication — the entry point the reference's
    ``test/datatype/check_op.sh`` matrix drives to validate the SIMD
    reduction kernels; here it exercises the same combiner the
    collectives use). Functional: returns the combined array."""
    # the op layer's spans (ring and profiler sink): the whole call,
    # and the device combiner's dispatch inside it
    traced = _trace.active or _trace.recording()
    tok = _trace.begin("op.reduce_local") if traced else None
    try:
        if not isinstance(op, Op) or op.fn is None:
            raise TypeError("invalid reduction op")
        if op.predefined and not op.is_loc:
            from ompi_tpu.native import native_reduce_local
            out = native_reduce_local(op.name, inbuf, inoutbuf)
            if out is not None:       # C++ kernel table (op/avx role)
                return out
        if not traced:
            return op.fn(inbuf, inoutbuf)  # inoutbuf = inbuf op inoutbuf
        launch = _trace.begin("op.launch:" + op.name)
        try:
            return op.fn(inbuf, inoutbuf)
        finally:
            _trace.end(launch)
    finally:
        if tok is not None:
            _trace.end(tok)
