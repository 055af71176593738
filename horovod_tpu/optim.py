"""Distributed optimizer and state-consistency primitives.

Parity targets:
  * ``DistributedOptimizer`` — reference horovod/torch/__init__.py:42-198
    (gradient hooks + averaging allreduce before step, with
    ``backward_passes_per_step`` local accumulation, torch:114-130) and
    horovod/tensorflow/__init__.py:141-239 (compute_gradients override).
  * ``broadcast_parameters`` — torch/__init__.py:200-230.
  * ``broadcast_optimizer_state`` — torch/__init__.py:232-348 (the torch
    version wraps scalars in tensors and walks state dicts; in JAX both
    params and optimizer state are pytrees, so one code path serves both).
  * ``DistributedGradientTape`` → ``distributed_grad`` / ``allreduce_gradients``.

TPU-native design: gradients are averaged with bucketed ``lax.psum`` inside
the jitted train step (one fused collective per bucket — the tensor-fusion
analogue), not hooked per-parameter: XLA overlaps the psum with backward
compute where profitable, which is the compiled-graph equivalent of the
reference's backward/allreduce overlap (torch/__init__.py:95-130).
"""

import time

import jax
import optax

from . import mpi_ops
from .common import state as state_mod
from .ops import collective_ops as cops
from .ops.compression import Compression
from .utils import metrics as hvd_metrics


def _account_grad_windows(mode, enqueue_s, drain_s):
    """Host-side timing of one eager gradient reduction, split into the
    enqueue window (where overlap dispatch can hide comm) and the final
    drain (comm still exposed after the last grad exists): exposed
    comm and the overlap fraction come from the framework's own
    dispatch timing rather than being re-derived outside it."""
    reg = hvd_metrics.get_registry()
    if not reg.enabled:
        return
    reg.counter(
        "hvd_grad_reduce_steps_total",
        "Eager gradient reductions, by dispatch mode.",
        labels=("mode",)).labels(mode=mode).inc()
    reg.counter(
        "hvd_grad_enqueue_ms_total",
        "Wall ms spent enqueueing gradient collectives (the window "
        "where readiness-ordered dispatch overlaps comm with grad "
        "production), by dispatch mode.",
        labels=("mode",)).labels(mode=mode).inc(enqueue_s * 1e3)
    reg.counter(
        "hvd_grad_exposed_ms_total",
        "Wall ms spent draining gradient collectives after the last "
        "enqueue — comm the step still pays for serially, by dispatch "
        "mode.",
        labels=("mode",)).labels(mode=mode).inc(drain_s * 1e3)


def allreduce_gradients(grads, compression=Compression.none, average=True,
                        axis_name=None, fusion_threshold=None,
                        sparse_as_dense=False):
    """Average a gradient pytree across workers.

    Inside a traced context this emits one fused psum per fusion bucket;
    outside it delegates to the eager core. Identity when the worker axis is
    absent and there is a single process (matching hvd.size()==1 behaviour,
    torch/__init__.py:77: hooks are only registered when size() > 1).

    ``IndexedSlices`` leaves take the sparse values+indices allgather path
    (reference tensorflow/__init__.py:62-73) unless ``sparse_as_dense=True``,
    which densifies them first (reference _keras/__init__.py:39-46).
    """
    from .ops import sparse as sparse_mod
    # One flatten serves sparse detection, densification, and the dense
    # path — the common all-dense case pays no extra tree traversal.
    leaves, treedef = jax.tree_util.tree_flatten(
        grads, is_leaf=sparse_mod.is_indexed_slices)
    is_sparse = [sparse_mod.is_indexed_slices(l) for l in leaves]
    if sparse_as_dense and any(is_sparse):
        leaves = [sparse_mod.to_dense(l) if s else l
                  for l, s in zip(leaves, is_sparse)]
        is_sparse = [False] * len(leaves)

    def _dense(dense_leaves):
        if not dense_leaves:
            return []
        if cops.in_traced_context(axis_name):
            return cops.grouped_allreduce_traced(
                dense_leaves, average=average, axis_name=axis_name,
                compression=compression, fusion_threshold=fusion_threshold)
        state = state_mod.global_state()
        coord = getattr(state, "coordinator", None)
        if coord is not None and getattr(state.config, "overlap_eager",
                                         False):
            # Overlap plane (docs/tensor-fusion.md): enqueue in reverse
            # tree order — the order backward materializes grads — and
            # drain every fusion bucket that fills while later (earlier-
            # layer) leaves are still being enqueued, so collective
            # dispatch rides inside the backward window instead of after
            # one whole-tree barrier. Results return in original leaf
            # order; at fp32 the reduction is bitwise identical to the
            # barrier path (per-element sums are insensitive to bucket
            # composition and dispatch order).
            t0 = time.perf_counter()
            handles = []
            for t in reversed(dense_leaves):
                handles.append(mpi_ops.allreduce_async(
                    t, average=average, compression=compression))
                coord.flush_ready()
            t1 = time.perf_counter()
            out = [mpi_ops.synchronize(h) for h in reversed(handles)]
            _account_grad_windows("overlap", t1 - t0,
                                  time.perf_counter() - t1)
            return out
        t0 = time.perf_counter()
        handles = [mpi_ops.allreduce_async(t, average=average,
                                           compression=compression)
                   for t in dense_leaves]
        t1 = time.perf_counter()
        out = [mpi_ops.synchronize(h) for h in handles]
        _account_grad_windows("barrier", t1 - t0,
                              time.perf_counter() - t1)
        return out

    if any(is_sparse):
        dense_out = iter(_dense([l for l, s in zip(leaves, is_sparse)
                                 if not s]))
        out = [sparse_mod.sparse_allreduce(l, average=average,
                                           axis_name=axis_name,
                                           compression=compression)
               if s else next(dense_out)
               for l, s in zip(leaves, is_sparse)]
    else:
        out = _dense(leaves)
    return jax.tree_util.tree_unflatten(treedef, out)


def DistributedOptimizer(optimizer, compression=Compression.none,
                         backward_passes_per_step=1, average=True,
                         axis_name=None, fusion_threshold=None,
                         sparse_as_dense=False):
    """Wrap an ``optax.GradientTransformation`` so that ``update()`` first
    averages gradients across all workers.

    An optimizer that averages local gradients over ICI before applying them
    — the role of the reference's ``_DistributedOptimizer``
    (torch/__init__.py:42-198) and ``DistributedOptimizer``
    (tensorflow/__init__.py:141-239).

    ``backward_passes_per_step > 1`` accumulates that many microbatch
    gradients locally before one fused allreduce + apply (reference
    ``backward_passes_per_step`` / ``--batches-per-allreduce``,
    torch/__init__.py:114-130, examples/pytorch_mnist.py:53-62), implemented
    with ``optax.MultiSteps``.
    """
    def _allreduce_updates(updates, state, params=None):
        del params
        from .ops import sparse as sparse_mod
        reduced = allreduce_gradients(
            updates, compression=compression, average=average,
            axis_name=axis_name, fusion_threshold=fusion_threshold,
            sparse_as_dense=sparse_as_dense)
        # IndexedSlices must not reach the inner optax transformation: it
        # would tree-map over (values, indices) and corrupt the integer
        # indices. Sparse leaves ride the allgather wire path above, then
        # densify before apply (sparse_as_dense=True densified pre-wire).
        return jax.tree_util.tree_map(
            lambda l: sparse_mod.to_dense(l)
            if sparse_mod.is_indexed_slices(l) else l,
            reduced, is_leaf=sparse_mod.is_indexed_slices), state

    allreduce_tx = optax.GradientTransformation(
        init=lambda params: optax.EmptyState(),
        update=_allreduce_updates)
    tx = optax.chain(allreduce_tx, optimizer)
    if backward_passes_per_step > 1:
        multi = optax.MultiSteps(tx,
                                 every_k_schedule=backward_passes_per_step)
        # MultiSteps accumulates into dense zeros_like(params) buffers, so
        # IndexedSlices must densify BEFORE the accumulator — local dense
        # accumulation matches the reference's grad buffers
        # (torch/__init__.py:114-130); the allreduce inside still sees
        # dense grads once per k steps.
        from .ops import sparse as sparse_mod

        def _densify_then(updates, state, params=None):
            dense = jax.tree_util.tree_map(
                lambda l: sparse_mod.to_dense(l)
                if sparse_mod.is_indexed_slices(l) else l,
                updates, is_leaf=sparse_mod.is_indexed_slices)
            return multi.update(dense, state, params)

        tx = optax.GradientTransformation(init=multi.init,
                                          update=_densify_then)
    return tx


def distributed_grad(fun, argnums=0, compression=Compression.none,
                     average=True, axis_name=None, has_aux=False,
                     fusion_threshold=None):
    """``jax.grad`` with cross-worker gradient averaging — the JAX analogue
    of ``DistributedGradientTape`` (tensorflow/__init__.py:242-316)."""
    grad_fn = jax.grad(fun, argnums=argnums, has_aux=has_aux)

    def wrapped(*args, **kwargs):
        if cops.in_traced_context(axis_name):
            # see ensure_varying: replicated inputs would make autodiff
            # pre-sum the grads, and the allreduce below would keep the sum
            axis = cops.resolve_axis(axis_name)
            nums = (argnums,) if isinstance(argnums, int) else tuple(argnums)
            args = tuple(jax.tree_util.tree_map(
                lambda x: cops.ensure_varying(x, axis), a)
                         if i in nums else a
                         for i, a in enumerate(args))
        if has_aux:
            grads, aux = grad_fn(*args, **kwargs)
            return allreduce_gradients(
                grads, compression=compression, average=average,
                axis_name=axis_name, fusion_threshold=fusion_threshold), aux
        grads = grad_fn(*args, **kwargs)
        return allreduce_gradients(
            grads, compression=compression, average=average,
            axis_name=axis_name, fusion_threshold=fusion_threshold)
    return wrapped


def broadcast_parameters(params, root_rank=0, axis_name=None):
    """Broadcast a parameter pytree from root_rank to all workers
    (reference torch/__init__.py:200-230, tensorflow broadcast_variables
    tensorflow/__init__.py:95-105). Call once after init and after restoring
    a checkpoint so all workers start from identical weights."""
    if cops.in_traced_context(axis_name):
        return jax.tree_util.tree_map(
            lambda t: cops.broadcast_traced(t, root_rank=root_rank,
                                            axis_name=axis_name), params)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    handles = [mpi_ops.broadcast_async(leaf, root_rank=root_rank)
               for leaf in leaves]
    leaves = [mpi_ops.synchronize(h) for h in handles]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def broadcast_optimizer_state(opt_state, root_rank=0, axis_name=None):
    """Broadcast optimizer state from root_rank (reference
    torch/__init__.py:232-348). Optax state is a pytree of arrays and
    scalars, so this is structurally identical to broadcast_parameters — no
    scalar-wrapping dance needed."""
    return broadcast_parameters(opt_state, root_rank=root_rank,
                                axis_name=axis_name)


def broadcast_object(obj, root_rank=0):
    """Broadcast an arbitrary picklable object from root_rank (used for
    epoch/step on resume, reference examples/pytorch_mnist.py:175-195).
    Single-process: identity. Multi-process: pickle over the process axis."""
    if not state_mod.is_initialized():
        raise mpi_ops.NotInitializedError()
    if jax.process_count() == 1:
        return obj
    import pickle
    import numpy as np
    # Two eager broadcasts through the coordination core (NOT direct
    # multihost calls: under rank-0 negotiation every cross-process
    # collective must originate from the core's background cycle, or its
    # ordering would race the negotiated stream). Non-root ranks learn
    # the payload length from the first broadcast.
    payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
    is_root = jax.process_index() == root_rank
    # int32 hi/lo pair: int64 would be silently truncated by jax without
    # x64, and a single int32 caps the payload at 2 GiB
    hi, lo = divmod(len(payload) if is_root else 0, 1 << 31)
    length = np.asarray([hi, lo], np.int32)
    length = np.asarray(mpi_ops.broadcast(length, root_rank=root_rank,
                                          name=_bcast_object_name("len")))
    buf = np.zeros((int(length[0]) << 31) + int(length[1]), dtype=np.uint8)
    if is_root:
        buf[:] = payload
    buf = np.asarray(mpi_ops.broadcast(buf, root_rank=root_rank,
                                       name=_bcast_object_name("payload")))
    return pickle.loads(buf.tobytes())


_bcast_object_counter = [0]


def _bcast_object_name(part):
    # matched across processes by call order (same program), like every
    # auto-generated collective name
    if part == "len":
        _bcast_object_counter[0] += 1
    return f"hvd.broadcast_object.{_bcast_object_counter[0]}.{part}"
