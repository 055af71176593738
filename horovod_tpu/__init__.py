"""horovod_tpu: a TPU-native distributed training framework.

A ground-up re-design of Horovod (reference: YuanTingHsieh/horovod, Horovod
v0.16.1 + CS744 elastic fork) for TPU hardware: collectives are XLA
collectives over the ICI mesh (``jax.lax.psum``/``all_gather``/... under
``jit``/``shard_map``), not negotiated MPI/NCCL calls; the eager API is
served by a per-process coordination core with tensor fusion, plan caching,
stall detection and timeline tracing — the machinery of the reference's
background thread without its wire protocol.

Public API parity with ``horovod.torch`` / ``horovod.tensorflow``
(reference horovod/torch/__init__.py:30-37, horovod/tensorflow/__init__.py):

    import horovod_tpu as hvd
    hvd.init()
    tx = hvd.DistributedOptimizer(optax.adam(1e-3 * hvd.size()))
    params = hvd.broadcast_parameters(params, root_rank=0)
"""

from .version import __version__  # noqa: F401

from .common.exceptions import (  # noqa: F401
    DuplicateNameError, HorovodError, MismatchError, NotInitializedError,
    RanksLostError, ShutdownError, StalledError)
from .common.config import HorovodConfig  # noqa: F401
from .mpi_ops import (  # noqa: F401
    init, shutdown, is_initialized, mpi_threads_supported,
    size, local_size, rank, local_rank, process_rank, process_count, mesh,
    allreduce, allreduce_, allreduce_async, allreduce_async_,
    grouped_allreduce,
    allgather, allgather_async,
    broadcast, broadcast_, broadcast_async, broadcast_async_,
    reducescatter, alltoall,
    poll, synchronize)
from .ops.collective_ops import ensure_varying  # noqa: F401
from .ops.compression import Compression  # noqa: F401
from .ops.sparse import (  # noqa: F401
    IndexedSlices, sparse_allreduce)
from . import callbacks  # noqa: F401
from .optim import (  # noqa: F401
    DistributedOptimizer, allreduce_gradients, broadcast_object,
    broadcast_optimizer_state, broadcast_parameters, distributed_grad)
