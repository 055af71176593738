"""Global per-process state.

TPU-native analogue of HorovodGlobalState (reference
horovod/common/global_state.h:44-149). The reference keeps a tensor table,
message queue, MPI communicators, fusion buffer and caches, all serviced by a
background thread. Under JAX/XLA none of the wire machinery is needed: the
device mesh plus XLA's compiled collectives replace the MPI communicators, and
ordering is fixed at trace time. What remains per-process is:

  * the device Mesh (GLOBAL communicator analogue, mpi_context.h:40-49)
  * process/local/cross topology info (LOCAL and CROSS communicators)
  * the runtime config (env knobs)
  * the eager coordination core (tensor table + flush loop) — see ops/eager.py
  * timeline / autotuner / stall-detector hooks

Worker model: the reference maps one MPI process to one GPU, so rank == worker
== device. JAX is single-controller-per-host: one process drives all local
devices. We therefore expose BOTH identities:

  * ``rank()/size()/local_rank()/local_size()`` are DEVICE-level, matching the
    reference's worker numbering (size == number of chips). Inside
    ``shard_map``/``pmap`` traced code, ``rank()`` is the traced
    ``lax.axis_index`` of the hvd axis; outside, it is the global index of this
    process's first local device.
  * ``process_rank()/process_count()`` are HOST-level (the reference's CROSS
    communicator, mpi_context.h:47-49).
"""

import threading

import jax
import numpy as np

from . import config as config_mod
from .exceptions import NotInitializedError

# The default mesh axis name used for Horovod-style data parallelism.
HVD_AXIS = "hvd"


class HorovodState:
    def __init__(self):
        self.initialized = False
        self.shut_down = False
        self.mesh = None
        self.config = None
        self.lock = threading.RLock()
        # Lazily constructed subsystems (set by init()):
        self.coordinator = None   # ops.eager.EagerCoordinator
        self.timeline = None      # utils.timeline.Timeline
        self.autotuner = None     # utils.autotune.Autotuner


_state = HorovodState()


def global_state():
    return _state


def _check_initialized():
    if not _state.initialized:
        raise NotInitializedError()


def _open_devices():
    """``jax.devices()``, with the one failure users of several processes
    meet said in this framework's terms: a TPU chip belongs to one
    process at a time (docs/tpus.md, "Chips vs processes")."""
    try:
        return jax.devices()
    except RuntimeError as e:
        if "already in use" not in str(e):
            raise
        raise RuntimeError(
            f"hvd.init(): another process holds this host's TPU chips "
            f"({e}). A chip belongs to one process at a time: run ONE "
            f"process per host driving all its chips, or launch one "
            f"process per chip with `hvdrun -np <chips>` from a parent "
            f"that has not touched JAX (docs/tpus.md, \"Chips vs "
            f"processes\"); pin helper processes to JAX_PLATFORMS=cpu."
        ) from e


def init_state(devices=None, mesh=None, axis_name=HVD_AXIS, config=None):
    """Populate the global state. Called by hvd.init()."""
    with _state.lock:
        if _state.initialized:
            return _state
        if mesh is None:
            if devices is None:
                devices = _open_devices()
            mesh = jax.sharding.Mesh(np.asarray(devices), (axis_name,))
        _state.mesh = mesh
        _state.config = config or config_mod.HorovodConfig.from_env()
        _state.initialized = True
        _state.shut_down = False
        return _state


def shutdown_state():
    with _state.lock:
        _state.initialized = False
        _state.shut_down = True
        _state.mesh = None
        _state.coordinator = None
        _state.timeline = None
        _state.autotuner = None


def mesh():
    _check_initialized()
    return _state.mesh


def hvd_axis_name():
    """Name of the data-parallel (worker) axis of the current mesh.

    For a multi-axis mesh created through parallel.mesh, the worker axis for
    gradient allreduce is the 'dp'-like first axis; for the default init it is
    HVD_AXIS.
    """
    _check_initialized()
    return _state.mesh.axis_names[0]


def _traced_axis_index():
    """Return lax.axis_index(axis) if called under an active axis binding
    (inside shard_map/pmap), else None."""
    from jax._src.core import get_axis_env  # private: a move must be loud
    names = [n for n in get_axis_env().axis_sizes if isinstance(n, str)]
    if not names:
        return None
    if _state.mesh is not None:
        for n in _state.mesh.axis_names:
            if n in names:
                return jax.lax.axis_index(n)
    return jax.lax.axis_index(names[0])


def size():
    """Total number of workers (devices). Reference: horovod_size
    (operations.cc:1612-1617)."""
    _check_initialized()
    return _state.mesh.devices.size


def local_size():
    """Workers (devices) on this host. Reference: horovod_local_size."""
    _check_initialized()
    return jax.local_device_count()


def rank():
    """Worker rank. Under shard_map/pmap tracing this is the traced device
    index along the mesh axis; outside it is the global index of this
    process's first device. Reference: horovod_rank (operations.cc:1620)."""
    _check_initialized()
    traced = _traced_axis_index()
    if traced is not None:
        return traced
    return jax.process_index() * jax.local_device_count()


def local_rank():
    """Rank within this host. Reference: horovod_local_rank."""
    _check_initialized()
    traced = _traced_axis_index()
    if traced is not None:
        return traced % jax.local_device_count()
    return 0


def process_local_rank():
    """This process's rank within its host, from the launcher's env
    (run/cli.py _rank_env); single-host fallback: the global process rank.
    The per-host identity the torch/TF frontends expose as local_rank()
    (reference LOCAL communicator role)."""
    import os
    return int(os.environ.get("HVD_LOCAL_RANK", jax.process_index()))


def process_local_size():
    """Processes on this host (launcher env; fallback: all processes)."""
    import os
    return int(os.environ.get("HVD_LOCAL_SIZE", jax.process_count()))


def process_rank():
    """Host-level rank (CROSS communicator analogue)."""
    _check_initialized()
    return jax.process_index()


def process_count():
    """Number of host processes (CROSS communicator size)."""
    _check_initialized()
    return jax.process_count()


def is_initialized():
    return _state.initialized
