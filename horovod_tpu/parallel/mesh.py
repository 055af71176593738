"""Device-mesh construction for every parallelism strategy.

The reference supports exactly one strategy — synchronous data parallelism
over MPI ranks (SURVEY.md §2.6) — with a two-level intra/inter-node variant
(NCCLHierarchicalAllreduce, nccl_operations.cc:162-379). On TPU the mesh is
the first-class object: all strategies (dp/fsdp/tp/pp/sp/ep) are axes of one
``jax.sharding.Mesh`` and XLA lowers collectives onto ICI (intra-slice) and
DCN (inter-slice) links according to the axis layout.

Axis conventions (leading axis first → slowest-varying over the device
order, which on multi-slice topologies means the DCN dimension):

  dp  — data parallel (gradient allreduce; the Horovod axis)
  pp  — pipeline parallel (stage dimension)
  tp  — tensor/model parallel (weight shards; activation collectives)
  sp  — sequence/context parallel (ring attention / all-to-all)
  ep  — expert parallel (MoE dispatch)
"""

import collections
import os
import threading

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXES = ("dp", "pp", "tp", "sp", "ep")

# The process-global named mesh (docs/mesh.md). One mesh per process, fixed
# for the life of the run: training, checkpointing and serving all place
# arrays through it, so a layout change is a restart (cross-layout restore
# handles the checkpoint side). Guarded by a lock only for the installation
# race; readers see a committed mesh or None.
_GLOBAL_LOCK = threading.Lock()
_GLOBAL_MESH = None


def build_mesh(dp=None, pp=1, tp=1, sp=1, ep=1, devices=None,
               axis_order=AXES):
    """Build a 5-axis mesh; unknown ``dp`` is inferred from device count.

    Size-1 axes are kept so code can be written against the full axis set
    regardless of the actual factorization (collectives over a size-1 axis
    are free).
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    sizes = {"pp": pp, "tp": tp, "sp": sp, "ep": ep}
    explicit = pp * tp * sp * ep
    if dp is None:
        if n % explicit != 0:
            raise ValueError(
                f"{n} devices not divisible by pp*tp*sp*ep={explicit}")
        dp = n // explicit
    sizes["dp"] = dp
    total = dp * explicit
    if total != n:
        raise ValueError(
            f"Mesh {sizes} needs {total} devices, have {n}")
    shape = tuple(sizes[a] for a in axis_order)
    return Mesh(np.asarray(devices).reshape(shape), axis_order)


def build_hierarchical_mesh(num_slices, devices=None,
                            axis_names=("slices", "chips")):
    """Two-level mesh: inter-slice (DCN) x intra-slice (ICI).

    The analogue of the reference's LOCAL/CROSS communicator split
    (MPI_Comm_split_type SHARED + cross split, operations.cc:890-959):
    ``chips`` is the fast intra-slice axis, ``slices`` the slow inter-slice
    axis. Used by the hierarchical allreduce (parallel/hierarchical.py).
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if n % num_slices != 0:
        raise ValueError(f"{n} devices not divisible into {num_slices} slices")
    arr = np.asarray(devices).reshape(num_slices, n // num_slices)
    return Mesh(arr, axis_names)


def infer_slice_structure(devices=None):
    """Group devices by their physical slice/host so the hierarchical path
    can lay the slow axis over DCN. Falls back to a single slice when the
    platform exposes no slice/process structure."""
    if devices is None:
        devices = jax.devices()
    groups = collections.defaultdict(list)
    for d in devices:
        key = getattr(d, "slice_index", None)
        if key is None:
            key = getattr(d, "process_index", 0)
        groups[key].append(d)
    return [groups[k] for k in sorted(groups)]


def mesh_axis_size(mesh, name):
    return mesh.shape[name] if name in mesh.shape else 1


def parse_mesh_spec(spec):
    """Parse a ``HOROVOD_MESH`` spec string into an axis-size dict.

    Grammar: comma-separated ``axis=size`` pairs over the named axes
    (``"dp=2,tp=4"``). ``dp`` may be omitted — ``build_mesh`` infers it
    from the device count. Unknown axes and non-positive sizes fail loud
    (a silent typo here would train on the wrong layout).
    """
    sizes = {}
    for part in str(spec).split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(
                f"HOROVOD_MESH entry {part!r} is not axis=size (axes: {AXES})")
        name, _, val = part.partition("=")
        name = name.strip()
        if name not in AXES:
            raise ValueError(
                f"HOROVOD_MESH axis {name!r} unknown (axes: {AXES})")
        if name in sizes:
            raise ValueError(f"HOROVOD_MESH axis {name!r} given twice")
        try:
            size = int(val)
        except ValueError:
            raise ValueError(
                f"HOROVOD_MESH size for {name!r} is not an int: {val!r}")
        if size < 1:
            raise ValueError(f"HOROVOD_MESH size for {name!r} must be >= 1")
        sizes[name] = size
    return sizes


def mesh_from_env(devices=None, environ=None):
    """Build the data-plane mesh from the environment knobs.

    ``HOROVOD_MESH`` (full ``axis=size`` spec) wins; otherwise the
    per-axis integer knobs ``HOROVOD_MESH_TP`` / ``HOROVOD_MESH_SP`` /
    ``HOROVOD_MESH_PP`` / ``HOROVOD_MESH_EP`` fill in and ``dp`` absorbs
    the remaining devices. With nothing set this is the pure-dp mesh the
    pre-mesh data plane always ran on, so dp-only runs are unchanged.
    """
    env = os.environ if environ is None else environ
    spec = env.get("HOROVOD_MESH", "")
    if spec:
        sizes = parse_mesh_spec(spec)
    else:
        sizes = {}
        for axis, var in (("tp", "HOROVOD_MESH_TP"), ("sp", "HOROVOD_MESH_SP"),
                          ("pp", "HOROVOD_MESH_PP"), ("ep", "HOROVOD_MESH_EP")):
            raw = env.get(var, "")
            if raw:
                sizes[axis] = int(raw)
    return build_mesh(dp=sizes.get("dp"),
                      pp=sizes.get("pp", 1), tp=sizes.get("tp", 1),
                      sp=sizes.get("sp", 1), ep=sizes.get("ep", 1),
                      devices=devices)


def _publish_axis_gauges(mesh):
    from ..utils import metrics
    gauge = metrics.get_registry().gauge(
        "hvd_mesh_axis_size",
        "Size of each named axis of the process-global mesh (docs/mesh.md)",
        labels=("axis",))
    for axis in mesh.axis_names:
        gauge.labels(axis=axis).set(mesh.shape[axis])


def set_global_mesh(mesh):
    """Install ``mesh`` as the process-global data-plane mesh.

    Idempotent for the same mesh; replacing a different committed mesh is
    an error — arrays already placed on the old mesh would silently
    cross-reshard on the next collective. Tests use
    ``reset_global_mesh()`` between layouts.
    """
    global _GLOBAL_MESH
    with _GLOBAL_LOCK:
        if _GLOBAL_MESH is not None and _GLOBAL_MESH is not mesh \
                and dict(_GLOBAL_MESH.shape) != dict(mesh.shape):
            raise RuntimeError(
                f"global mesh already set to {dict(_GLOBAL_MESH.shape)}; "
                f"refusing to replace with {dict(mesh.shape)} "
                "(reset_global_mesh() first)")
        _GLOBAL_MESH = mesh
    _publish_axis_gauges(mesh)
    return mesh


def global_mesh(devices=None):
    """The process-global mesh, lazily built from the env knobs.

    First call wins: it builds from ``HOROVOD_MESH`` (or the per-axis
    knobs) over ``devices`` and installs the result; later calls return
    the committed mesh regardless of env changes.
    """
    with _GLOBAL_LOCK:
        if _GLOBAL_MESH is not None:
            return _GLOBAL_MESH
    return set_global_mesh(mesh_from_env(devices=devices))


def global_mesh_if_set():
    """The committed global mesh, or None — never triggers a lazy build."""
    return _GLOBAL_MESH


def reset_global_mesh():
    """Drop the committed global mesh (test isolation between layouts)."""
    global _GLOBAL_MESH
    with _GLOBAL_LOCK:
        _GLOBAL_MESH = None


def _resolve(mesh):
    return global_mesh() if mesh is None else mesh


def axis_size(name, mesh=None):
    return mesh_axis_size(_resolve(mesh), name)


def mesh_layout(mesh=None):
    """Plain ``{axis: size}`` dict — the form checkpoint manifests record."""
    return {a: int(s) for a, s in _resolve(mesh).shape.items()}


def spec_shard_shape(shape, spec, mesh=None):
    """Per-chip shard shape of ``shape`` under a PartitionSpec — pure
    axis-size math, no arrays placed. This is what
    ``NamedSharding.shard_shape`` computes for a committed array, made
    available for *abstract* leaves so the memory plane's ledger and
    pre-flight planner (utils/memory.py, docs/memory.md) attribute
    bytes from a spec tree alone. Indivisible dims stay whole,
    mirroring the replicate-don't-rag rule of ``kv_cache_spec``."""
    if spec is None:
        return tuple(shape)
    sizes = mesh_layout(mesh) if not isinstance(mesh, dict) else mesh
    entries = tuple(spec)
    out = []
    for i, dim in enumerate(shape):
        part = entries[i] if i < len(entries) else None
        if part is None:
            out.append(dim)
            continue
        names = part if isinstance(part, (tuple, list)) else (part,)
        div = 1
        for name in names:
            div *= int(sizes.get(name, 1))
        out.append(dim // div if div and dim % div == 0 else dim)
    return tuple(out)


def named_sharding(spec, mesh=None):
    """The one sanctioned ``NamedSharding`` constructor (hvdlint HVD019).

    Every placement in trainer/serving/ops goes through here (or the
    tree-wide wrappers below) so the whole data plane shares a single
    mesh contract instead of scattering inline ``NamedSharding(mesh, ...)``
    constructions that drift when the layout changes.
    """
    return NamedSharding(_resolve(mesh), spec if spec is not None else P())


def tree_shardings(spec_tree, mesh=None):
    """Map a PartitionSpec tree to a matching NamedSharding tree."""
    mesh = _resolve(mesh)
    return jax.tree_util.tree_map(lambda s: named_sharding(s, mesh),
                                  spec_tree)


def device_put_tree(tree, spec_tree, mesh=None):
    """Tree-wide ``device_put``: place every leaf of ``tree`` on the mesh
    according to the matching leaf of ``spec_tree`` (one transfer batch,
    not a per-leaf python loop)."""
    return jax.device_put(tree, tree_shardings(spec_tree, mesh))


def replicate_tree(tree, mesh=None):
    """Place every leaf fully replicated (spec ``P()``) on the mesh."""
    shard = named_sharding(P(), mesh)
    return jax.device_put(
        tree, jax.tree_util.tree_map(lambda _: shard, tree))


def kv_cache_spec(num_heads, mesh=None):
    """PartitionSpec for the serving KV cache ``[layers, slots, len,
    heads, head_dim]``: heads sharded over tp when tp divides them,
    replicated otherwise (docs/serving.md, docs/mesh.md). ``num_heads``
    is the head count THE CACHE HOLDS: the key/value heads of a
    grouped-query model, not its query heads."""
    mesh = _resolve(mesh)
    tp = mesh_axis_size(mesh, "tp")
    if tp > 1 and num_heads % tp == 0:
        return P(None, None, None, "tp", None)
    return P()


def decode_head_sharding(num_heads):
    """Trace-time hint for the fused decode step: the head-sharded
    NamedSharding for ``[batch, s, heads, head_dim]`` activations when a
    global mesh with tp>1 dividing ``num_heads`` is committed, else None
    (dp-only engines stay byte-identical). ``num_heads`` is the cache's
    head count (the key/value heads under grouped-query attention: the
    constraint lands on q, k and v alike, and the query heads are a
    multiple). Reads the committed mesh only — never triggers a lazy env
    build from inside a trace."""
    mesh = global_mesh_if_set()
    if mesh is None:
        return None
    tp = mesh_axis_size(mesh, "tp")
    if tp > 1 and num_heads % tp == 0:
        return named_sharding(P(None, None, "tp", None), mesh)
    return None


def account_axis_bytes(axis, nbytes, codec="none"):
    """Attribute collective payload bytes to a mesh axis on the
    ``hvd_wire_bytes_total{codec,axis}`` counter so ``hvd_top`` and the
    roofline decomposition can split tp-axis comm from dp (docs/metrics.md).
    The mesh path is uncompressed, so raw == wire."""
    from ..ops import quantization
    quantization.account(codec, int(nbytes), int(nbytes), axis=axis)
