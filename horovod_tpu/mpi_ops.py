"""Public collective API: init/rank/size + allreduce/allgather/broadcast.

The single entry point replacing the reference's per-framework op bindings
(horovod/torch/mpi_ops.py, horovod/tensorflow/mpi_ops.py,
horovod/mxnet/mpi_ops.py, horovod/common/basics.py). Each op transparently
dispatches:

  * inside shard_map/pmap-traced code → XLA collectives over the mesh
    (ops/collective_ops.py) — the compiled hot path;
  * outside → the eager coordination core (ops/eager.py) with handles,
    fusion, plan cache, stall detection.

Handle-based async API parity: allreduce_async/poll/synchronize follow
horovod/torch/mpi_ops.py:69-83,406-438. In-place variants (allreduce_ etc.)
exist for signature parity but return the new value — jax.Arrays are
immutable, so "in-place" cannot mutate the argument; callers rebind.
"""

import atexit
import itertools

import jax

from .common import hvd_logging as log
from .common import state as state_mod
from .common.exceptions import NotInitializedError
from .ops import collective_ops as cops
from .ops import eager as eager_mod
from .ops.compression import Compression

_name_counter = itertools.count()

# True iff THIS module called jax.distributed.initialize (shutdown()
# then tears it down, so reused worker processes — Spark keeps Python
# workers alive across jobs — can init() again with a fresh coordinator)
_initialized_jax_distributed = False

# re-exported identity API (reference common/basics.py)
size = state_mod.size
local_size = state_mod.local_size
rank = state_mod.rank
local_rank = state_mod.local_rank
process_rank = state_mod.process_rank
process_count = state_mod.process_count
is_initialized = state_mod.is_initialized
mesh = state_mod.mesh


def init(devices=None, mesh=None, axis_name=state_mod.HVD_AXIS, config=None,
         coordinator_address=None, num_processes=None, process_id=None):
    """Initialize horovod_tpu (reference hvd.init(), common/basics.py:29-56;
    InitializeHorovodOnce, operations.cc:1566-1586).

    Args:
      devices: devices to form the worker mesh over (default: all).
      mesh: a pre-built jax.sharding.Mesh to adopt (multi-axis allowed; the
        first axis is the worker/data-parallel axis).
      axis_name: name for the default 1-D mesh axis.
      config: HorovodConfig override (default: parsed from HOROVOD_* env).
      coordinator_address/num_processes/process_id: multi-host bootstrap,
        forwarded to jax.distributed.initialize — the analogue of mpirun's
        rendezvous (reference run/run.py:458-481). On TPU pods all three are
        auto-detected and may be left None.
    """
    if state_mod.is_initialized():
        return
    # hvdrun exports the rendezvous through env (run/cli.py:_rank_env), the
    # way mpirun exports OMPI_COMM_WORLD_* for the reference
    # (test/common.py:25-57). Explicit args win over env.
    import os
    def _env_first(*names, default):
        for n in names:
            if n in os.environ:
                return int(os.environ[n])
        return default

    def _jax_distributed_live():
        # pre-initialized by the caller (the pods flow)
        return jax.distributed.is_initialized()

    if coordinator_address is None and "HVD_COORDINATOR_ADDR" in os.environ:
        coordinator_address = os.environ["HVD_COORDINATOR_ADDR"]
        if num_processes is None:
            # hvdrun's env first, then mpirun/srun's (reference jobs read
            # OMPI_COMM_WORLD_* / PMI_*, test/common.py:25-57) — so
            # `mpirun -np N` / `srun -nN python train.py` works with only
            # HVD_COORDINATOR_ADDR exported
            num_processes = _env_first("HVD_NUM_PROC",
                                       "OMPI_COMM_WORLD_SIZE", "PMI_SIZE",
                                       "SLURM_STEP_NUM_TASKS",
                                       default=1)
        if process_id is None:
            process_id = _env_first("HVD_PROCESS_ID",
                                    "OMPI_COMM_WORLD_RANK", "PMI_RANK",
                                    "SLURM_PROCID",
                                    default=0)
    elif coordinator_address is None and num_processes is None:
        # mpirun/srun compatibility: reference jobs launch under MPI and
        # read OMPI_COMM_WORLD_* / PMI_* (test/common.py:25-57). MPI
        # exports no rendezvous address, so derive one automatically:
        # rank 0 publishes host:port through the filesystem keyed by the
        # job id (run/mpi.py) — `mpirun -np N python train.py` works with
        # zero extra env on one host or a shared-FS cluster (reference
        # parity: run/run.py:458-481 jobs need nothing extra). Skipped if
        # the caller bootstrapped jax.distributed itself (TPU pods).
        from .run import mpi as mpi_compat
        world = mpi_compat.detect_mpi_world()
        if world is not None and world[0] > 1 and \
                not _jax_distributed_live():
            coordinator_address, num_processes, process_id = \
                mpi_compat.auto_rendezvous(*world)
    if coordinator_address is not None or num_processes is not None:
        if _jax_distributed_live():
            # a previous runtime is still up (caller-bootstrapped, or a
            # reused worker process — e.g. Spark reuses Python workers
            # across jobs); initialize() would raise "should only be
            # called once". shutdown() tears ours down (see below), so
            # reaching here live means the caller owns the runtime.
            log.warning(
                "jax.distributed already initialized; keeping the live "
                "runtime instead of re-initializing with %s",
                coordinator_address)
        else:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes, process_id=process_id)
            global _initialized_jax_distributed
            _initialized_jax_distributed = True
    state = state_mod.init_state(devices=devices, mesh=mesh,
                                 axis_name=axis_name, config=config)
    state.coordinator = eager_mod.EagerCoordinator(state)
    atexit.register(shutdown)
    return


def shutdown():
    """Shut down (reference horovod_shutdown, operations.cc:1101-1122)."""
    global _initialized_jax_distributed
    state = state_mod.global_state()
    if state.coordinator is not None:
        state.coordinator.shutdown()
    state_mod.shutdown_state()
    if _initialized_jax_distributed:
        # only tear down a runtime WE brought up — a caller-bootstrapped
        # jax.distributed (TPU pods) outlives hvd.shutdown()
        _initialized_jax_distributed = False
        try:
            jax.distributed.shutdown()
        except Exception as e:  # noqa: BLE001 — already gone is fine
            log.debug("jax.distributed.shutdown: %s", e)


def mpi_threads_supported():
    """Parity shim (reference operations.cc:1643-1650). There is no MPI; the
    coordination service is always thread-safe."""
    if not state_mod.is_initialized():
        raise NotInitializedError()
    return True


def _coordinator():
    if not state_mod.is_initialized():
        raise NotInitializedError()
    return state_mod.global_state().coordinator


def _auto_name(op, name):
    return name if name is not None else f"{op}.noname.{next(_name_counter)}"


# ---------------------------------------------------------------------------
# allreduce
# ---------------------------------------------------------------------------

def allreduce(tensor, average=True, name=None, compression=Compression.none,
              op=None, axis_name=None):
    """Allreduce a tensor across workers (reference
    horovod/tensorflow/__init__.py:36-83, horovod/torch/mpi_ops.py:85-108).

    In traced code this is a ``lax.psum`` over the mesh axis; eagerly it is
    queued, fused, and executed by the coordination core. An
    ``IndexedSlices`` input takes the sparse allgather path (reference
    tensorflow/__init__.py:62-73).
    """
    # Normalize sum/average into the `average` flag once; after this, op is
    # None or min/max (which only the traced dense branch implements).
    if op in (cops.SUM, cops.AVERAGE):
        average = op == cops.AVERAGE
        op = None
    from .ops import sparse as sparse_mod
    if sparse_mod.is_indexed_slices(tensor):
        if op is not None:
            raise ValueError(
                f"Sparse allreduce supports only sum/average, got op={op!r}")
        return sparse_mod.sparse_allreduce(tensor, average=average,
                                           axis_name=axis_name, name=name,
                                           compression=compression)
    if cops.in_traced_context(axis_name):
        return cops.allreduce_traced(tensor, average=average,
                                     axis_name=axis_name, op=op,
                                     compression=compression)
    if op is not None:
        raise NotImplementedError(
            f"Eager allreduce supports only sum/average, got op={op!r}; "
            "min/max are available inside shard_map-traced code.")
    handle = allreduce_async(tensor, average=average, name=name,
                             compression=compression)
    return synchronize(handle)


def allreduce_async(tensor, average=True, name=None,
                    compression=Compression.none, kind=None):
    """Queue an allreduce; returns a handle (torch/mpi_ops.py:85-130).
    ``kind`` overrides the eager core's stacked/replicated shape heuristic
    for callers that know their tensor's semantics."""
    coord = _coordinator()
    resolved = _auto_name("allreduce", name)
    compressed, ctx = compression.compress(tensor)
    if ctx is not None:
        # lossy wire cast happened: record the norm delta (host-side
        # only — this is the eager path; the traced paths in
        # ops/collective_ops.py stay jit-pure)
        from .utils import numerics as numerics_mod
        numerics_mod.get_monitor().observe_compression(
            resolved, tensor, compressed,
            getattr(compression, "name", "unknown"))
    handle = coord.enqueue(resolved, eager_mod.ALLREDUCE,
                           compressed, average=average, kind=kind)
    if ctx is not None:
        coord.handles.get(handle).postscale = ctx  # dtype to restore
    return handle


# In-place spellings for API parity; jax.Arrays are immutable so these return
# the reduced value (torch/mpi_ops.py:133-178 semantics minus mutation).
allreduce_ = allreduce
allreduce_async_ = allreduce_async


def grouped_allreduce(tensors, average=True, compression=Compression.none,
                      axis_name=None, fusion_threshold=None):
    """Fused allreduce of many tensors at once (explicit tensor fusion).
    ``IndexedSlices`` leaves take the sparse allgather path; their integer
    indices must never enter the dense sum."""
    from .ops import sparse as sparse_mod
    leaves = jax.tree_util.tree_leaves(tensors,
                                       is_leaf=sparse_mod.is_indexed_slices)
    if any(sparse_mod.is_indexed_slices(l) for l in leaves):
        from . import optim
        return optim.allreduce_gradients(
            tensors, compression=compression, average=average,
            axis_name=axis_name, fusion_threshold=fusion_threshold)
    if cops.in_traced_context(axis_name):
        return cops.grouped_allreduce_traced(
            tensors, average=average, axis_name=axis_name,
            compression=compression, fusion_threshold=fusion_threshold)
    handles = [allreduce_async(t, average=average, compression=compression)
               for t in jax.tree_util.tree_leaves(tensors)]
    leaves = [synchronize(h) for h in handles]
    treedef = jax.tree_util.tree_structure(tensors)
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# allgather
# ---------------------------------------------------------------------------

def allgather(tensor, name=None, axis_name=None, kind=None):
    """Concatenate each worker's tensor along dim 0 (reference
    torch/mpi_ops.py:180-232; MPI_Allgatherv mpi_operations.cc:86-173).
    ``kind`` overrides the eager core's stacked/replicated shape heuristic
    for callers that know their tensor's semantics."""
    if cops.in_traced_context(axis_name):
        return cops.allgather_traced(tensor, axis_name=axis_name)
    return synchronize(allgather_async(tensor, name=name, kind=kind))


def allgather_async(tensor, name=None, kind=None):
    coord = _coordinator()
    return coord.enqueue(_auto_name("allgather", name), eager_mod.ALLGATHER,
                         tensor, kind=kind)


# ---------------------------------------------------------------------------
# broadcast
# ---------------------------------------------------------------------------

def broadcast(tensor, root_rank=0, name=None, axis_name=None):
    """Broadcast root_rank's tensor to all workers (reference
    torch/mpi_ops.py:234-310; MPIBroadcast mpi_operations.cc:331-364)."""
    if cops.in_traced_context(axis_name):
        return cops.broadcast_traced(tensor, root_rank=root_rank,
                                     axis_name=axis_name)
    return synchronize(broadcast_async(tensor, root_rank=root_rank,
                                       name=name))


def broadcast_async(tensor, root_rank=0, name=None, kind=None):
    coord = _coordinator()
    return coord.enqueue(_auto_name("broadcast", name), eager_mod.BROADCAST,
                         tensor, root_rank=root_rank, kind=kind)


broadcast_ = broadcast
broadcast_async_ = broadcast_async


# ---------------------------------------------------------------------------
# reducescatter / alltoall — first-class primitives on TPU (the building
# blocks of hierarchical allreduce and sequence parallelism; SURVEY.md §5).
# ---------------------------------------------------------------------------

def reducescatter(tensor, average=False, axis_name=None, name=None):
    if cops.in_traced_context(axis_name):
        return cops.reducescatter_traced(tensor, axis_name=axis_name,
                                         average=average)
    coord = _coordinator()
    handle = coord.enqueue(_auto_name("reducescatter", name),
                           eager_mod.REDUCESCATTER, tensor, average=average)
    return synchronize(handle)


def alltoall(tensor, axis_name=None, split_axis=0, concat_axis=0,
             name=None):
    if cops.in_traced_context(axis_name):
        return cops.alltoall_traced(tensor, axis_name=axis_name,
                                    split_axis=split_axis,
                                    concat_axis=concat_axis)
    if split_axis != 0 or concat_axis != 0:
        raise NotImplementedError(
            "Eager alltoall supports split_axis=concat_axis=0; other axes "
            "are available inside shard_map-traced code.")
    coord = _coordinator()
    handle = coord.enqueue(_auto_name("alltoall", name),
                           eager_mod.ALLTOALL, tensor)
    return synchronize(handle)


# ---------------------------------------------------------------------------
# handle API
# ---------------------------------------------------------------------------

def poll(handle):
    """True if the handle's collective has completed
    (torch/mpi_ops.py:406-420)."""
    return _coordinator().poll(handle)


def synchronize(handle):
    """Block until the handle completes; return the output
    (torch/mpi_ops.py:422-438)."""
    coord = _coordinator()
    entry = coord.handles.get(handle)
    restore_dtype = getattr(entry, "postscale", None)
    result = coord.synchronize(handle)
    if restore_dtype is not None and result is not None:
        result = result.astype(restore_dtype)
    return result
