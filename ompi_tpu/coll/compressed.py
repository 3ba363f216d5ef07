"""coll/compressed — quantized collectives as a first-class component.

The MCA face of ``ompi_tpu/compress``: a coll component above the tuned
decision layer (priority 62 > tuned 60) claiming exactly the three
collectives that have a compressed schedule — allreduce, allgather,
reduce_scatter_block. Every call is gated by the decision layer
(``coll/decision.compress_eligible``: the ``mpi_base_compress`` MCA
var, the per-rank size threshold, eligible dtypes f32/f64/bf16, and
sum-only reduction semantics); ineligible calls delegate to the
next-priority provider (han's fallback-module idiom), so with the var
off the framework is byte-identical to a build without this component.

Device schedules (``_CompressedDevice``, an XlaCollModule whose cache
holds only compressed executables):

- allreduce: segmented quantized ring (dequant -> reduce -> requant at
  every reduce-scatter hop, lossless code forwarding in the allgather
  phase — ``_CompressedDevice._ring_allreduce_inner``, this module's
  own schedule), or the two-tier hier schedule on multihost meshes with
  only the slow-tier chunk quantized
  (``XlaCollModule._hier_allreduce_inner(codec=...)``).
- allgather: quantize once, fused ``all_gather`` of codes + scales,
  per-row dequant.
- reduce_scatter_block: per-row quantize, ``all_to_all`` of codes,
  dequant + fixed-rank-order fold (bitwise identical across ranks).

Byte accounting rides the ``compress_bytes_in/out`` pvars: each
compiled entry knows the wire bytes its schedule moves per call and
the bytes the same schedule would move uncompressed.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ompi_tpu.accelerator import LOCUS_DEVICE, check_addr, to_device, to_host
from ompi_tpu.coll import decision
from ompi_tpu.coll.framework import coll_framework
from ompi_tpu.coll.xla import AXIS, XlaCollModule
from ompi_tpu.compress import codecs as _codecs
from ompi_tpu.compress import stats as _stats
from ompi_tpu.mca import var
from ompi_tpu.mca.base import Component

WRAPPED_FUNCS = ("allreduce", "allgather", "reduce_scatter_block")


class _CompressedDevice(XlaCollModule):
    """Device-path compressed schedules. Reached both through the
    owning module's vtable slots and directly by
    ``Communicator.allreduce_bind`` (which unwraps ``.device``), so
    eligibility is re-gated at every entry point."""

    def __init__(self, comm, owner: "CompressedCollModule"):
        super().__init__(comm)
        self._owner = owner

    def _codec(self) -> Tuple[_codecs.Codec, int]:
        from ompi_tpu import compress
        return (_codecs.get_codec(compress.codec_name()),
                compress.block_elems())

    def _account_fn(self, fn: Callable, bytes_in: int, bytes_out: int,
                    dequants: int) -> Callable:
        def run(x):
            _stats.account(bytes_in, bytes_out)
            _stats.account_dequant(dequants)
            return fn(x)
        return run

    # -- entry points reachable from allreduce_bind --------------------
    def allreduce(self, x, op):
        if self._owner._eligible("allreduce", x, op):
            return self.allreduce_compressed(x, op)
        return self._owner._delegate_device("allreduce", x, op)

    def bind_allreduce(self, example, op):
        x = self._to_mesh(example)
        if self._owner._eligible("allreduce", x, op):
            self.allreduce_compressed(x, op)         # warm + memo
            cobj, cblock = self._codec()
            fn = self._fast[("c_allreduce", x.shape, x.dtype, op.uid,
                             cobj.name, cblock)][1]
            return lambda buf: fn(self._to_mesh(buf))
        mod = self._owner._flat_mod("allreduce")
        dev = getattr(mod, "device", mod)
        bind = getattr(dev, "bind_allreduce", None)
        if bind is not None and dev is not self:
            return bind(example, op)
        return lambda buf, _op=op: mod.allreduce(buf, _op)

    # -- compressed schedules ------------------------------------------
    def _ring_allreduce_inner(self, op, n, shape, codec):
        """Quantized ring (2(n-1) ppermute steps, EQuARX's reduction-hop
        structure) over the flattened buffer padded to n chunks; the
        chunk combine is op.fn. The reduce-scatter phase quantizes the
        outgoing partial sum, moves 1-byte codes + per-block scales, and
        the receiver dequantizes before the combine — dequant -> reduce
        -> requant at each hop. The allgather phase quantizes each
        rank's finished chunk ONCE and forwards the codes losslessly, so
        broadcast hops add no further error; the owner's row is its own
        dequantized image so every rank ends bitwise identical."""
        total = int(np.prod(shape))
        chunk = -(-total // n)           # ceil
        perm = [(i, (i + 1) % n) for i in range(n)]
        cobj, cblock = codec

        def inner(b):                    # block (1, *s)
            x = b.reshape(-1)
            x = jnp.pad(x, (0, n * chunk - total))
            buf = x.reshape(n, chunk)
            r = jax.lax.axis_index(AXIS)

            def rs_step(buf, t):
                send_idx = jnp.mod(r - t, n)
                send = jax.lax.dynamic_index_in_dim(buf, send_idx, 0,
                                                    keepdims=False)
                qc, qs = cobj.jnp_quant(send, cblock)
                qc = jax.lax.ppermute(qc, AXIS, perm=perm)
                qs = jax.lax.ppermute(qs, AXIS, perm=perm)
                recvd = cobj.jnp_dequant(qc, qs, chunk, buf.dtype, cblock)
                tgt = jnp.mod(r - t - 1, n)
                cur = jax.lax.dynamic_index_in_dim(buf, tgt, 0,
                                                   keepdims=False)
                buf = jax.lax.dynamic_update_index_in_dim(
                    buf, op.fn(cur, recvd), tgt, 0)
                return buf, None

            buf, _ = jax.lax.scan(rs_step, buf, jnp.arange(n - 1))
            # rank r now owns the fully reduced chunk (r+1) mod n
            own = jnp.mod(r + 1, n)
            cur = jax.lax.dynamic_index_in_dim(buf, own, 0, keepdims=False)
            qc, qs = cobj.jnp_quant(cur, cblock)
            # own row = own dequantized image: what the peers see
            cur_dq = cobj.jnp_dequant(qc, qs, chunk, buf.dtype, cblock)
            buf = jax.lax.dynamic_update_index_in_dim(buf, cur_dq, own, 0)

            def ag_step(carry, t):
                buf, qc, qs = carry
                qc = jax.lax.ppermute(qc, AXIS, perm=perm)
                qs = jax.lax.ppermute(qs, AXIS, perm=perm)
                idx = jnp.mod(r - t, n)
                buf = jax.lax.dynamic_update_index_in_dim(
                    buf, cobj.jnp_dequant(qc, qs, chunk, buf.dtype,
                                          cblock), idx, 0)
                return (buf, qc, qs), None

            (buf, _, _), _ = jax.lax.scan(ag_step, (buf, qc, qs),
                                          jnp.arange(n - 1))
            return buf.reshape(-1)[:total].reshape(b.shape)
        return inner

    def _ring_segmented_allreduce_inner(self, op, n, shape, nseg, codec):
        """Segmented ring (``coll_base_allreduce.c:345-357,622``): the
        payload is split into ``nseg`` segments, each running its OWN
        complete quantized ring chain — the chains share no values, so
        nothing in the program orders segment s+1's collective-permutes
        after segment s's combines, and XLA's async scheduler may
        overlap them."""
        total = int(np.prod(shape))
        seglen = -(-total // nseg)
        ring = self._ring_allreduce_inner(op, n, (seglen,), codec)

        def inner(b):                    # block (1, *s)
            x = b.reshape(1, -1)
            x = jnp.pad(x, ((0, 0), (0, nseg * seglen - total)))
            outs = [ring(x[:, s * seglen:(s + 1) * seglen])
                    for s in range(nseg)]
            return jnp.concatenate(outs, axis=1)[:, :total] \
                      .reshape(b.shape)
        return inner

    def _nseg(self, chunk_bytes: int) -> int:
        """Segment count from the ``coll_xla_segsize`` MCA var (the
        tuned segsize knob); unroll-bounded at 8."""
        segsize = max(1, int(var.var_get("coll_xla_segsize", 1 << 20)))
        return max(1, min(8, -(-chunk_bytes // segsize)))

    def allreduce_compressed(self, x, op):
        x = self._to_mesh(x)
        n = self.comm.size
        cobj, cblock = self._codec()
        fk = ("c_allreduce", x.shape, x.dtype, op.uid, cobj.name, cblock)
        ep = var.epoch()
        hit = self._fast.get(fk)
        if hit is not None and hit[0] == ep:
            return hit[1](x)
        itemsize = np.dtype(x.dtype).itemsize
        total = int(np.prod(x.shape[1:]))            # per-rank elems
        alg = "hier"
        low = high = None
        if self._multihost():
            low, high = self._groups()
        if low is None:
            alg = "ring_segmented"
        nseg = (self._nseg(total * itemsize // max(n, 1))
                if alg == "ring_segmented" else 0)

        def build():
            if alg == "hier":
                inner = self._hier_allreduce_inner(op, low, high,
                                                   (cobj, cblock))
            else:
                inner = self._ring_segmented_allreduce_inner(
                    op, n, x.shape[1:], nseg, (cobj, cblock))
            return self._smap(inner, x.ndim, x.ndim)

        fn = self._compiled(
            self._key("c_allreduce", x, op.uid, n, alg, nseg,
                      cobj.name, cblock), build, x)
        # per-call wire model: every quantized hop of the schedule
        if alg == "hier":
            glen, H = len(low[0]), len(high[0])
            chunk = -(-total // glen)
            hops = H - 1                 # codes received per rank
            b_in = hops * chunk * itemsize
            b_out = hops * cobj.wire_bytes(chunk, cblock)
            deq = H
        else:
            seglen = -(-total // nseg)
            chunk = -(-seglen // n)
            hops = 2 * (n - 1) * nseg
            b_in = hops * chunk * itemsize
            b_out = hops * cobj.wire_bytes(chunk, cblock)
            deq = hops
        fn = self._account_fn(fn, b_in, b_out, deq)
        self._fast[fk] = (ep, fn)
        return fn(x)

    def _compressed_allgather_inner(self, n, shape, dtype, cobj, cblock):
        total = int(np.prod(shape))

        def inner(b):                    # (1, *s) -> (1, n, *s)
            x = b[0]
            qc, qs = cobj.jnp_quant(x.reshape(-1), cblock)
            gc = jax.lax.all_gather(qc, AXIS, tiled=False)
            gs = jax.lax.all_gather(qs, AXIS, tiled=False)
            rows = [cobj.jnp_dequant(gc[i], gs[i], total, dtype,
                                     cblock).reshape(shape)
                    for i in range(n)]
            return jnp.stack(rows)[None]
        return inner

    def allgather_compressed(self, x):
        x = self._to_mesh(x)
        n = self.comm.size
        cobj, cblock = self._codec()
        fk = ("c_allgather", x.shape, x.dtype, cobj.name, cblock)
        ep = var.epoch()
        hit = self._fast.get(fk)
        if hit is not None and hit[0] == ep:
            return hit[1](x)
        itemsize = np.dtype(x.dtype).itemsize
        total = int(np.prod(x.shape[1:]))

        def build():
            inner = self._compressed_allgather_inner(
                n, x.shape[1:], x.dtype, cobj, cblock)
            return self._smap(inner, x.ndim, x.ndim + 1)

        fn = self._compiled(
            self._key("c_allgather", x, n, cobj.name, cblock), build, x)
        hops = n - 1                     # rows received per rank
        fn = self._account_fn(
            fn, hops * total * itemsize,
            hops * cobj.wire_bytes(total, cblock), n)
        self._fast[fk] = (ep, fn)
        return fn(x)

    def _compressed_rsb_inner(self, op, n, shape, dtype, cobj, cblock):
        total = int(np.prod(shape))      # per-row elems

        def inner(b):                    # (1, n, *s) -> (1, *s)
            rows = b[0].reshape(n, -1)
            qc, qs = jax.vmap(lambda v: cobj.jnp_quant(v, cblock))(rows)
            ac = jax.lax.all_to_all(qc, AXIS, split_axis=0,
                                    concat_axis=0, tiled=True)
            asc = jax.lax.all_to_all(qs, AXIS, split_axis=0,
                                     concat_axis=0, tiled=True)
            # fixed rank order: the fold is identical on every rank
            acc = cobj.jnp_dequant(ac[0], asc[0], total, dtype, cblock)
            for i in range(1, n):
                acc = op.fn(acc, cobj.jnp_dequant(ac[i], asc[i], total,
                                                  dtype, cblock))
            return acc.reshape(shape)[None]
        return inner

    def reduce_scatter_block_compressed(self, x, op):
        x = self._to_mesh(x)
        n = self.comm.size
        cobj, cblock = self._codec()
        fk = ("c_rsb", x.shape, x.dtype, op.uid, cobj.name, cblock)
        ep = var.epoch()
        hit = self._fast.get(fk)
        if hit is not None and hit[0] == ep:
            return hit[1](x)
        itemsize = np.dtype(x.dtype).itemsize
        total = int(np.prod(x.shape[2:]))            # per-row elems

        def build():
            inner = self._compressed_rsb_inner(
                op, n, x.shape[2:], x.dtype, cobj, cblock)
            return self._smap(inner, x.ndim, x.ndim - 1)

        fn = self._compiled(
            self._key("c_rsb", x, op.uid, n, cobj.name, cblock),
            build, x)
        hops = n - 1                     # rows shipped per rank
        fn = self._account_fn(
            fn, hops * total * itemsize,
            hops * cobj.wire_bytes(total, cblock), n)
        self._fast[fk] = (ep, fn)
        return fn(x)


class CompressedCollModule:
    """The vtable face: claims allreduce/allgather/reduce_scatter_block
    and nothing else (the framework backfills the rest from tuned/xla
    per function, exactly the per-function composition the selection
    machinery exists for)."""

    def __init__(self, comm):
        self.comm = comm
        self.device = _CompressedDevice(comm, self)
        self._flat_memo: Dict[str, Any] = {}

    # -- delegation (han's fallback-module idiom) ----------------------
    def _flat_mod(self, func: str):
        m = self._flat_memo.get(func)
        if m is None:
            for _prio, comp, module in getattr(self.comm,
                                               "_coll_selected", []):
                if comp.name == "compressed":
                    continue
                if getattr(module, func, None) is not None:
                    m = module
                    break
            if m is None:
                raise RuntimeError(f"no fallback provider for {func}")
            self._flat_memo[func] = m
        return m

    def _delegate_device(self, func: str, *args):
        mod = self._flat_mod(func)
        dev = getattr(mod, "device", mod)
        if dev is self.device:           # paranoia: never self-recurse
            dev = mod
        return getattr(dev, func)(*args)

    def _eligible(self, func: str, buf, op=None) -> bool:
        n = max(self.comm.size, 1)
        nbytes = int(getattr(buf, "nbytes", 0)) // n
        dt = getattr(buf, "dtype", None)
        return decision.compress_eligible(
            func, nbytes, getattr(dt, "name", str(dt)), op)

    def _run(self, func: str, compressed_fn: Callable, buf, *args):
        """Stage eligible host buffers in (tuned's accelerator-bracket
        role), run the compressed schedule, stage back."""
        if check_addr(buf) == LOCUS_DEVICE:
            return compressed_fn(buf, *args)
        y = compressed_fn(to_device(buf, self.comm.sharding), *args)
        return to_host(y)

    # -- vtable slots --------------------------------------------------
    def allreduce(self, x, op):
        if not self._eligible("allreduce", x, op):
            return self._flat_mod("allreduce").allreduce(x, op)
        return self._run("allreduce", self.device.allreduce_compressed,
                         x, op)

    def allgather(self, x):
        if not self._eligible("allgather", x):
            return self._flat_mod("allgather").allgather(x)
        return self._run("allgather", self.device.allgather_compressed,
                         x)

    def reduce_scatter_block(self, x, op):
        if not self._eligible("reduce_scatter_block", x, op):
            return self._flat_mod("reduce_scatter_block") \
                .reduce_scatter_block(x, op)
        return self._run("reduce_scatter_block",
                         self.device.reduce_scatter_block_compressed,
                         x, op)

    # derived-datatype allreduce stays uncompressed (the gather/scatter
    # image is index-sparse; quantizing the packed form is future work)
    def allreduce_dtype(self, *args, **kw):
        return self._flat_mod("allreduce").allreduce_dtype(*args, **kw)

    def bind_allreduce(self, example, op):
        return self.device.bind_allreduce(example, op)


class CompressedCollComponent(Component):
    name = "compressed"

    def register_params(self):
        var.var_register(
            "coll", "compressed", "priority", vtype="int", default=62,
            help="Selection priority of the quantized-collectives "
                 "component (above tuned so eligible large payloads "
                 "are claimed; per-call gating delegates everything "
                 "else — mpi_base_compress off means byte-identical "
                 "behavior)")
        from ompi_tpu import compress
        compress._register_vars()

    def comm_query(self, comm):
        if comm is None or not getattr(comm, "mesh", None):
            return None
        from ompi_tpu import compress
        if not compress.enabled():
            # a disabled component declines selection (the reference's
            # query-time opt-out); comms built while enabled still gate
            # per call, so toggling the var off later is honored too
            return None
        prio = var.var_get("coll_compressed_priority", 62)
        if prio < 0:
            return None
        return (prio, CompressedCollModule(comm))


coll_framework.register(CompressedCollComponent())
