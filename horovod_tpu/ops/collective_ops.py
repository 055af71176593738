"""Device-level collective operations.

TPU-native replacement for the reference's op layer
(horovod/common/ops/mpi_operations.cc, nccl_operations.cc): collectives are
XLA collectives over the device mesh (ICI), not negotiated MPI/NCCL calls.

Two execution contexts, one API:

  * **Traced (jit) path** — called inside ``shard_map``/``pmap``-traced code
    with the hvd mesh axis bound, these emit ``lax.psum`` /
    ``lax.all_gather`` / etc. directly; XLA lowers them to ICI collectives.
    This is the hot path used by DistributedOptimizer.
  * **Eager path** — called outside a traced context, they delegate to the
    eager coordination core (ops/eager.py), which queues, fuses and executes
    them on the mesh — the analogue of the reference's background thread.

Reference op → TPU mapping (SURVEY.md §2.2):
  MPIAllreduce / NCCLAllreduce (mpi_operations.cc:22-84,
    nccl_operations.cc:53-160)       → lax.psum over the mesh axis
  MPIAllgather (mpi_operations.cc:86-173) → lax.all_gather(tiled=True)
  MPIBroadcast (mpi_operations.cc:331-364) → masked psum from root
  NCCLHierarchicalAllreduce (nccl_operations.cc:162-379)
                                      → two-level ICI/DCN path (parallel/hierarchical.py)
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..common import state as state_mod
from ..utils import metrics as hvd_metrics
from ..utils import tracing as hvd_tracing
from .compression import Compression

# Reduction op names, parity with horovod's average flag plus explicit ops.
SUM = "sum"
AVERAGE = "average"
MIN = "min"
MAX = "max"


def _bound_axis_names():
    """Names of mesh axes currently bound by shard_map/pmap tracing."""
    # private API: an ImportError here must stay loud — swallowing it
    # would resolve every traced collective as eager
    from jax._src.core import get_axis_env
    return [n for n in get_axis_env().axis_sizes if isinstance(n, str)]


def resolve_axis(axis_name=None, prefer_hierarchy=False):
    """Pick the collective axis: explicit > traced mesh axis > None (eager).
    ``axis_name`` may be a tuple of axes (a reduction spanning a whole
    hierarchy, e.g. ("slices", "chips")) — resolved iff every member is
    bound. ``prefer_hierarchy`` (the allreduce entry points) resolves a
    None axis to the full hierarchy pair when both axes are bound and
    HOROVOD_HIERARCHICAL_ALLREDUCE is on, so OperationManager's
    two-level backend — which matches on the exact pair — can actually
    win; single-axis ops (broadcast's axis_index, allgather) never get
    the tuple."""
    bound = _bound_axis_names()
    if isinstance(axis_name, (tuple, list)):
        return tuple(axis_name) if all(a in bound for a in axis_name) \
            else None
    if axis_name is not None:
        return axis_name if axis_name in bound else None
    if not bound:
        return None
    if state_mod.is_initialized():
        state = state_mod.global_state()
        if prefer_hierarchy and getattr(
                state.config, "hierarchical_allreduce", False):
            from .operation_manager import HIER_FAST_AXIS, HIER_SLOW_AXIS
            if HIER_FAST_AXIS in bound and HIER_SLOW_AXIS in bound:
                return (HIER_FAST_AXIS, HIER_SLOW_AXIS)
        for n in state.mesh.axis_names:
            if n in bound:
                return n
    return bound[0]


def ensure_varying(x, axis_names):
    """Return ``x`` typed device-varying over ``axis_names`` (no-op for
    axes it already varies over).

    Differentiating w.r.t. an UNvarying value inside shard_map makes
    autodiff psum the cotangent itself — grads arrive pre-summed and a
    subsequent explicit allreduce silently keeps the sum (psum of identical
    values ÷ size). Casting the differentiated inputs varying first keeps
    grads per-worker, so the framework's fused collective is the one true
    reduction."""
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    vma = jax.typeof(x).vma
    missing = tuple(a for a in axis_names if a not in vma)
    if not missing:
        return x
    return lax.pcast(x, missing, to="varying")


def in_traced_context(axis_name=None):
    return resolve_axis(axis_name) is not None


# ---------------------------------------------------------------------------
# Traced (in-jit) collectives — the SPMD hot path.
# ---------------------------------------------------------------------------

def _count_traced(op, tensors):
    """Trace-time accounting. Runtime counters inside jit are impossible
    (no host side effects in compiled code), but every (re)trace passes
    through here — so these counters surface per-op-class traffic shape
    and, when they keep climbing in steady state, retrace churn."""
    reg = hvd_metrics.get_registry()
    if not reg.enabled:
        return
    nbytes = 0
    for t in tensors:
        try:
            nbytes += int(np.prod(t.shape)) * np.dtype(t.dtype).itemsize
        except (TypeError, ValueError, AttributeError):
            pass  # abstract values without a concrete shape/dtype
    reg.counter(
        "hvd_traced_collective_tensors_total",
        "Tensors passed through traced (jit-path) collectives, counted "
        "at trace time, by op class.", labels=("op",)).labels(
        op=op).inc(len(tensors))
    reg.counter(
        "hvd_traced_collective_bytes_total",
        "Bytes passed through traced (jit-path) collectives, counted "
        "at trace time, by op class.", labels=("op",)).labels(
        op=op).inc(nbytes)
    # flight-recorder breadcrumb: retraces landing right before a failure
    # are a classic divergence cause (shape drift on one rank), so the
    # trace-time pass leaves a cycle record the postmortem can line up
    # against the negotiation history
    hvd_tracing.get_tracer().record_cycle(
        kind="traced_collective", op=op, n_tensors=len(tensors),
        nbytes=nbytes)

def allreduce_traced(tensor, average=True, axis_name=None, op=None,
                     compression=Compression.none):
    """Allreduce inside shard_map/pmap-traced code.

    Parity: allreduce with compression (reference
    horovod/tensorflow/__init__.py:36-83: compress → sum → decompress →
    divide by size when averaging).
    """
    axis = resolve_axis(axis_name, prefer_hierarchy=True)
    assert axis is not None, "allreduce_traced requires a bound mesh axis"
    _count_traced("allreduce", [tensor])
    op = op or (AVERAGE if average else SUM)
    compressed, ctx = compression.compress(tensor)
    if op in (SUM, AVERAGE):
        # backend dispatch (hierarchical/ring/xla) — reference
        # OperationManager priority selection, operation_manager.cc:67-80
        from .operation_manager import get_operation_manager
        reduced = get_operation_manager().allreduce(compressed, axis)
    elif op == MIN:
        reduced = lax.pmin(compressed, axis)
    elif op == MAX:
        reduced = lax.pmax(compressed, axis)
    else:
        raise ValueError(f"Unknown reduction op: {op}")
    reduced = compression.decompress(reduced, ctx)
    if op == AVERAGE:
        reduced = reduced / _axis_total_size(axis)
    return reduced


def _axis_total_size(axis):
    if isinstance(axis, (tuple, list)):
        size = 1
        for a in axis:
            size *= lax.axis_size(a)
        return size
    return lax.axis_size(axis)


def grouped_allreduce_traced(tensors, average=True, axis_name=None,
                             compression=Compression.none,
                             fusion_threshold=None):
    """Fused allreduce of a list/pytree of tensors: one psum per fusion
    bucket (reference FuseResponses, operations.cc:450-573)."""
    from . import fusion as fusion_mod
    axis = resolve_axis(axis_name, prefer_hierarchy=True)
    assert axis is not None
    if fusion_threshold is None:
        fusion_threshold = state_mod.global_state().config.fusion_threshold \
            if state_mod.is_initialized() else 64 * 1024 * 1024
    leaves, treedef = jax.tree_util.tree_flatten(tensors)
    _count_traced("grouped_allreduce", leaves)
    compressed = []
    ctxs = []
    for leaf in leaves:
        c, ctx = compression.compress(leaf)
        compressed.append(c)
        ctxs.append(ctx)
    from .operation_manager import get_operation_manager
    om = get_operation_manager()
    summed = fusion_mod.fused_map(
        lambda flat: om.allreduce(flat, axis), compressed, fusion_threshold)
    out = []
    for s, ctx in zip(summed, ctxs):
        s = compression.decompress(s, ctx)
        if average:
            s = s / _axis_total_size(axis)
        out.append(s)
    return jax.tree_util.tree_unflatten(treedef, out)


def allgather_traced(tensor, axis_name=None):
    """Concatenate each worker's tensor along dim 0 (reference MPIAllgather,
    mpi_operations.cc:86-173; output allocation collective_operations.cc:68)."""
    axis = resolve_axis(axis_name)
    assert axis is not None
    _count_traced("allgather", [tensor])
    return lax.all_gather(tensor, axis, tiled=True)


def broadcast_traced(tensor, root_rank=0, axis_name=None):
    """Every worker gets root_rank's value (reference MPIBroadcast,
    mpi_operations.cc:331-364). Implemented as a masked psum, which XLA
    lowers to an efficient one-to-all over ICI."""
    axis = resolve_axis(axis_name)
    assert axis is not None
    _count_traced("broadcast", [tensor])
    axis_size = lax.axis_size(axis)
    if isinstance(root_rank, int) and not 0 <= root_rank < axis_size:
        raise ValueError(
            f"Invalid root_rank {root_rank}: must be in [0, {axis_size}).")
    idx = lax.axis_index(axis)
    masked = jnp.where(idx == root_rank, tensor,
                       jnp.zeros_like(tensor))
    return lax.psum(masked, axis)


def reducescatter_traced(tensor, axis_name=None, average=False):
    """Reduce-scatter: each worker gets one summed shard (the building block
    of the reference's hierarchical path, nccl_operations.cc:269)."""
    axis = resolve_axis(axis_name)
    assert axis is not None
    _count_traced("reducescatter", [tensor])
    out = lax.psum_scatter(tensor, axis, tiled=True)
    if average:
        out = out / lax.axis_size(axis)
    return out


def alltoall_traced(tensor, axis_name=None, split_axis=0, concat_axis=0):
    """All-to-all over the mesh axis (first-class primitive for sequence
    parallelism; the reference exposes no alltoall — extension noted in
    SURVEY.md §5)."""
    axis = resolve_axis(axis_name)
    assert axis is not None
    _count_traced("alltoall", [tensor])
    return lax.all_to_all(tensor, axis, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)
