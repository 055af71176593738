"""Device-side cross-process collectives for the eager data plane.

The reference's data plane is ONE bandwidth-optimal collective executed
in place on the (fused) buffer — ``MPI_Allreduce`` at
mpi_operations.cc:48, ``ncclAllReduce`` at nccl_operations.cc:85. The
TPU-native equivalent here: a device mesh with one device per host
process (the reference's one-rank-per-GPU model), per-process
contributions assembled into a global jax.Array, and a jitted
``shard_map`` collective over the ``proc`` axis so XLA lowers to its
ring/tree implementations over ICI/DCN:

  * allreduce      → ``lax.psum``          (O(M) wire bytes, not O(P·M))
  * broadcast      → masked ``lax.psum``
  * allgather      → resharding to replicated (XLA all-gather)
  * reducescatter  → ``lax.psum_scatter``
  * alltoall       → ``lax.all_to_all``
  * quantized allreduce → two-phase reduce-scatter/all-gather over the
    narrow wire dtype (ops/quantization.py): all_to_all the encoded
    payload+scales, dequant→sum in f32, requant the owned chunk,
    all_gather the narrow sum — so every byte that crosses the wire is
    int8/fp8 (+ f32 block scales) while accumulation stays f32

Every process must invoke the same engine call in the same order — the
eager core guarantees that (coordinator-ordered under negotiation,
same-program-order otherwise). Inputs stay on device end to end: fusion
concat, the collective, and the un-fuse slicing are all device-side, so
the host never stages the payload (the reference's fusion-buffer
memcpys, mpi_operations.cc:25-66, are device-side here too).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel import hierarchical as hier_mod
from . import quantization

PROC_AXIS = "proc"

# Two-level factorization of the process axis (HierarchicalProcessEngine):
# the slow inter-host leg and the fast intra-host leg.
HOSTS_AXIS = "hosts"
LOCAL_AXIS = "local"


class ProcessCollectiveEngine:
    """Compiled collectives over a one-device-per-process mesh.

    Construct lazily, after jax.distributed is live; cheap to hold — all
    jitted callables are cached per shape/dtype by jax itself.
    """

    def __init__(self):
        by_proc = {}
        for d in sorted(jax.devices(), key=lambda d: (d.process_index, d.id)):
            by_proc.setdefault(d.process_index, d)
        self.nproc = jax.process_count()
        if len(by_proc) != self.nproc:
            raise RuntimeError(
                f"expected devices from {self.nproc} processes, found "
                f"{sorted(by_proc)}")
        devices = [by_proc[p] for p in range(self.nproc)]
        self.mesh = Mesh(np.asarray(devices), (PROC_AXIS,))
        self._my_device = by_proc[jax.process_index()]
        self._sharded = NamedSharding(self.mesh, P(PROC_AXIS))
        self._replicated = NamedSharding(self.mesh, P())

    # -- global-array assembly ------------------------------------------

    def _stack(self, x):
        """Global [nproc, ...] array whose row p is process p's ``x``.

        Only this process's row is materialized (on its mesh device);
        no host staging, no cross-process traffic yet.
        """
        local = jax.device_put(jnp.asarray(x)[None], self._my_device)
        return jax.make_array_from_single_device_arrays(
            (self.nproc,) + tuple(local.shape[1:]), self._sharded, [local])

    def _local(self, out):
        """This process's addressable piece of a collective's output."""
        return out.addressable_data(0)

    # -- compiled collective bodies (cached by jax.jit on shape/dtype) --

    @functools.cached_property
    def _allreduce_fn(self):
        mesh = self.mesh

        @functools.partial(jax.jit, static_argnums=1)
        def f(x, average):
            def body(s):
                out = lax.psum(s[0], PROC_AXIS)
                return out / self.nproc if average else out
            return jax.shard_map(body, mesh=mesh, in_specs=P(PROC_AXIS),
                                 out_specs=P())(x)
        return f

    @functools.cached_property
    def _broadcast_fn(self):
        mesh = self.mesh

        @functools.partial(jax.jit, static_argnums=1)
        def f(x, root):
            def body(s):
                idx = lax.axis_index(PROC_AXIS)
                masked = jnp.where(idx == root, s[0], jnp.zeros_like(s[0]))
                return lax.psum(masked, PROC_AXIS)
            return jax.shard_map(body, mesh=mesh, in_specs=P(PROC_AXIS),
                                 out_specs=P())(x)
        return f

    @functools.cached_property
    def _allgather_fn(self):
        # resharding sharded → replicated IS the all-gather; XLA emits it
        return jax.jit(lambda x: x, out_shardings=self._replicated)

    @functools.cached_property
    def _reducescatter_fn(self):
        mesh = self.mesh

        @functools.partial(jax.jit, static_argnums=1)
        def f(x, average):
            def body(s):
                out = lax.psum_scatter(s[0], PROC_AXIS,
                                       scatter_dimension=0, tiled=True)
                return out / self.nproc if average else out
            return jax.shard_map(body, mesh=mesh, in_specs=P(PROC_AXIS),
                                 out_specs=P(PROC_AXIS))(x)
        return f

    @functools.cached_property
    def _quantized_rs_fn(self):
        """Phase 1: reduce-scatter over the narrow wire. Every process
        all_to_alls its encoded contribution, dequants the peer chunks
        to f32, sums, and requantizes its owned chunk — output is the
        narrow requantized sum, process-sharded."""
        mesh = self.mesh
        nproc = self.nproc

        @functools.partial(jax.jit, static_argnums=(2, 3))
        def f(q, s, codec, block):
            # q [nproc, m] narrow payload, s [nproc, m // block] f32
            # scales; row p is process p's encoded contribution. m must
            # be a multiple of block * nproc so the per-process chunks
            # land on block boundaries (encode(multiple=block * nproc)).
            def body(qs, ss):
                chunk = qs.shape[-1] // nproc
                qp = lax.all_to_all(
                    qs[0].reshape(nproc, chunk), PROC_AXIS,
                    split_axis=0, concat_axis=0, tiled=True)
                sp = lax.all_to_all(
                    ss[0].reshape(nproc, chunk // block), PROC_AXIS,
                    split_axis=0, concat_axis=0, tiled=True)
                # accumulate in f32: dequant each peer row, sum, requant
                total = jnp.sum(
                    quantization._block_decode(qp, sp, block), axis=0)
                return quantization._block_encode(total, block, codec)
            return jax.shard_map(
                body, mesh=mesh, in_specs=(P(PROC_AXIS), P(PROC_AXIS)),
                out_specs=(P(PROC_AXIS), P(PROC_AXIS)))(q, s)
        return f

    @functools.cached_property
    def _quantized_gather_fn(self):
        # phase 2: resharding the NARROW payload + scales to replicated
        # IS the all-gather; XLA moves the encoded bytes, and the final
        # dequant runs locally on every process
        return jax.jit(lambda q, s: (q, s),
                       out_shardings=(self._replicated, self._replicated))

    @functools.cached_property
    def _alltoall_fn(self):
        mesh = self.mesh

        @jax.jit
        def f(x):
            def body(s):
                return lax.all_to_all(s[0], PROC_AXIS, split_axis=0,
                                      concat_axis=0, tiled=True)
            return jax.shard_map(body, mesh=mesh, in_specs=P(PROC_AXIS),
                                 out_specs=P(PROC_AXIS))(x)
        return f

    # -- public ops ------------------------------------------------------

    def allreduce(self, x, average=False):
        """Sum (or mean) of every process's ``x``; full result on this
        process's device."""
        return self._local(self._allreduce_fn(self._stack(x), bool(average)))

    def allreduce_quantized(self, payload, scales, codec, block,
                            average=False):
        """Sum (or mean) across processes of the block-scaled encoded
        buffers, f32 result on this process's device. ``payload`` length
        must be a multiple of ``block * nproc``; each process passes its
        own (payload, scales) from quantization.encode."""
        q2, s2 = self._quantized_rs_fn(
            self._stack(payload), self._stack(scales), str(codec),
            int(block))
        qg, sg = self._quantized_gather_fn(q2, s2)
        out = quantization.decode(self._local(qg), self._local(sg),
                                  int(block), int(qg.shape[0]))
        return out / self.nproc if average else out

    def broadcast(self, x, root):
        """Process ``root``'s ``x`` on every process."""
        return self._local(self._broadcast_fn(self._stack(x), int(root)))

    def allgather_stacked(self, x):
        """[nproc, ...] stack of every process's equally-shaped ``x``."""
        return self._local(self._allgather_fn(self._stack(x)))

    def reducescatter(self, x, average=False):
        """This process's 1/nproc shard (dim 0) of the elementwise sum."""
        return self._local(self._reducescatter_fn(self._stack(x),
                                                  bool(average)))

    def alltoall(self, x):
        """MPI_Alltoall along dim 0: chunk i of every process's ``x``
        lands on process i, concatenated in rank order."""
        return self._local(self._alltoall_fn(self._stack(x)))


class HierarchicalProcessEngine:
    """Two-level cross-process allreduce over a [hosts, local] mesh —
    the eager data plane's NCCLHierarchicalAllreduce
    (nccl_operations.cc:162-379): intra-host reduce-scatter at full
    width, inter-host exchange of each process's 1/local_size shard,
    intra-host all-gather. On the quantized paths ONLY the inter-host
    leg carries the narrow codec: the shm/ICI legs inside a host have
    bandwidth to burn, the DCN leg is where bytes are scarce (MLPerf
    TPU-v3 pod paper; EQuARX). Process p sits at mesh position
    (p // local_size, p % local_size) — the launcher's contiguous
    ranks-per-host layout (HVD_LOCAL_SIZE).
    """

    def __init__(self, local_size):
        local_size = int(local_size)
        nproc = jax.process_count()
        if local_size < 1 or nproc % local_size:
            raise ValueError(
                f"hierarchical local_size {local_size} must divide the "
                f"process count {nproc}")
        self.local_size = local_size
        self.nhosts = nproc // local_size
        self.nproc = nproc
        by_proc = {}
        for d in sorted(jax.devices(), key=lambda d: (d.process_index, d.id)):
            by_proc.setdefault(d.process_index, d)
        if len(by_proc) != nproc:
            raise RuntimeError(
                f"expected devices from {nproc} processes, found "
                f"{sorted(by_proc)}")
        devices = np.asarray([by_proc[p] for p in range(nproc)])
        self.mesh = Mesh(devices.reshape(self.nhosts, self.local_size),
                         (HOSTS_AXIS, LOCAL_AXIS))
        self._my_device = by_proc[jax.process_index()]
        self._grid = NamedSharding(self.mesh, P(HOSTS_AXIS, LOCAL_AXIS))
        self._replicated = NamedSharding(self.mesh, P())

    def _stack(self, x):
        """Global [hosts, local, ...] array whose (h, l) cell is process
        h*local_size+l's ``x`` — only this process's cell materialized."""
        local = jax.device_put(jnp.asarray(x)[None, None], self._my_device)
        return jax.make_array_from_single_device_arrays(
            (self.nhosts, self.local_size) + tuple(local.shape[2:]),
            self._grid, [local])

    def _local(self, out):
        return out.addressable_data(0)

    @functools.cached_property
    def _allreduce_fn(self):
        """Full-width two-level allreduce — parallel/hierarchical.py's
        reduce_scatter(fast) → psum(slow) → all_gather(fast) schedule,
        run over the [hosts, local] process mesh."""
        mesh = self.mesh

        @functools.partial(jax.jit, static_argnums=1)
        def f(x, average):
            def body(s):
                return hier_mod.hierarchical_allreduce(
                    s[0, 0], fast_axis=LOCAL_AXIS, slow_axis=HOSTS_AXIS,
                    average=average)
            return jax.shard_map(
                body, mesh=mesh, in_specs=P(HOSTS_AXIS, LOCAL_AXIS),
                out_specs=P())(x)
        return f

    @functools.cached_property
    def _quantized_fn(self):
        """Two-level allreduce with the codec on the inter-host leg
        only. Phase A: full-width psum_scatter over LOCAL — each
        process owns a 1/local_size shard of its host's sum. Phase B:
        the shard (error-feedback compensated) is block-encoded and
        allreduced over HOSTS as narrow payload + scales (all_to_all →
        f32 dequant-sum → requant → all_gather — exactly the flat
        engine's two-phase schedule, on the hosts axis). Phase C:
        full-width all_gather over LOCAL rebuilds the buffer. Returns
        (full result replicated, compensated shard, own-wire decode of
        the shard) — the latter two feed the EF residual update."""
        mesh = self.mesh
        nhosts = self.nhosts
        world = self.nproc

        @functools.partial(jax.jit, static_argnums=(2, 3, 4))
        def f(x, r, codec, block, average):
            # x [hosts, local, m] f32, m a multiple of block * nproc;
            # r [hosts, local, m // local] f32 EF residual (zeros when
            # none is carried)
            def body(xs, rs):
                shard = lax.psum_scatter(xs[0, 0], LOCAL_AXIS, tiled=True)
                comp = shard + rs[0, 0]
                q, s = quantization._block_encode(comp, block, codec)
                chunk = q.shape[-1] // nhosts
                qp = lax.all_to_all(
                    q.reshape(nhosts, chunk), HOSTS_AXIS,
                    split_axis=0, concat_axis=0, tiled=True)
                sp = lax.all_to_all(
                    s.reshape(nhosts, chunk // block), HOSTS_AXIS,
                    split_axis=0, concat_axis=0, tiled=True)
                total = jnp.sum(
                    quantization._block_decode(qp, sp, block), axis=0)
                q2, s2 = quantization._block_encode(total, block, codec)
                qg = lax.all_gather(q2, HOSTS_AXIS, tiled=True)
                sg = lax.all_gather(s2, HOSTS_AXIS, tiled=True)
                red = quantization._block_decode(qg, sg, block)
                full = lax.all_gather(red, LOCAL_AXIS, tiled=True)
                if average:
                    full = full / world
                dec_own = quantization._block_decode(q, s, block)
                return full, comp[None, None], dec_own[None, None]
            # check_vma=False: ``full`` IS replicated (it comes off
            # tiled all_gathers over both axes) but the static checker
            # cannot see through the dequant/requant arithmetic.
            return jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(HOSTS_AXIS, LOCAL_AXIS),
                          P(HOSTS_AXIS, LOCAL_AXIS)),
                out_specs=(P(), P(HOSTS_AXIS, LOCAL_AXIS),
                           P(HOSTS_AXIS, LOCAL_AXIS)),
                check_vma=False)(x, r)
        return f

    def allreduce(self, x, average=False):
        """Full-width two-level sum (or mean); full result on this
        process's device."""
        return self._local(self._allreduce_fn(self._stack(x),
                                              bool(average)))

    def allreduce_quantized(self, fused, codec, block, average=False,
                            residual=None):
        """Two-level allreduce of a flat f32 buffer with the quantized
        codec on the inter-host leg only. ``residual`` is this
        process's carried EF residual for its shard (or None). Returns
        (f32 result [padded m], compensated shard, own-wire shard
        decode); slice the result to the true length and hand the
        shards to ErrorFeedback.update."""
        m = quantization.pad_to(int(fused.shape[0]), block * self.nproc)
        x = jnp.asarray(fused, jnp.float32)
        if m != x.shape[0]:
            x = jnp.concatenate([x, jnp.zeros((m - x.shape[0],), x.dtype)])
        shard_len = m // self.local_size
        if residual is None or tuple(residual.shape) != (shard_len,):
            residual = jnp.zeros((shard_len,), jnp.float32)
        full, comp, dec = self._quantized_fn(
            self._stack(x), self._stack(residual), str(codec), int(block),
            bool(average))
        return (self._local(full), self._local(comp)[0, 0],
                self._local(dec)[0, 0])
