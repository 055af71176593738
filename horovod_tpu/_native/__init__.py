"""ctypes binding to the native runtime core (libhvd_core.so).

The reference loads its native core the same way — ctypes.CDLL on the built
extension (horovod/common/basics.py:25-28, util.py check_extension). Build
with ``python setup.py build_native`` (or the Makefile in this directory);
if the library cannot be built or loaded, ``LIB`` is None, the cause is
logged and kept in ``LOAD_ERROR``, and callers fall back to the
pure-Python implementations, so the framework works (slower) without a
toolchain.
"""

import ctypes
import hashlib
import os
import subprocess

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_DIR, "libhvd_core.so")

LIB = None
_LOAD_FAILED = False  # negative cache: never retry a failed build/load
LOAD_ERROR = None  # why load() returned None, for callers that must know


def _configure(lib):
    c = ctypes
    lib.hvd_core_version.restype = c.c_char_p
    lib.hvd_log.argtypes = [c.c_int, c.c_char_p]
    lib.hvd_log_set_level.argtypes = [c.c_int]
    lib.hvd_log_get_level.restype = c.c_int

    lib.hvd_plan_buckets.restype = c.c_int64
    lib.hvd_plan_buckets.argtypes = [
        c.c_int64, c.POINTER(c.c_int64), c.POINTER(c.c_int32), c.c_int64,
        c.POINTER(c.c_int32)]

    lib.hvd_cache_create.restype = c.c_void_p
    lib.hvd_cache_create.argtypes = [c.c_int64]
    lib.hvd_cache_destroy.argtypes = [c.c_void_p]
    lib.hvd_cache_lookup.restype = c.c_int64
    lib.hvd_cache_lookup.argtypes = [c.c_void_p, c.c_uint64]
    lib.hvd_cache_insert.argtypes = [c.c_void_p, c.c_uint64, c.c_int64]
    for fn in (lib.hvd_cache_hits, lib.hvd_cache_misses, lib.hvd_cache_size):
        fn.restype = c.c_int64
        fn.argtypes = [c.c_void_p]
    lib.hvd_cache_clear.argtypes = [c.c_void_p]

    lib.hvd_table_create.restype = c.c_void_p
    lib.hvd_table_destroy.argtypes = [c.c_void_p]
    lib.hvd_table_add.restype = c.c_int
    lib.hvd_table_add.argtypes = [c.c_void_p, c.c_char_p, c.c_int64,
                                  c.c_double]
    lib.hvd_table_remove.restype = c.c_int
    lib.hvd_table_remove.argtypes = [c.c_void_p, c.c_char_p]
    lib.hvd_table_count.restype = c.c_int64
    lib.hvd_table_count.argtypes = [c.c_void_p]
    lib.hvd_table_stalled.restype = c.c_int64
    lib.hvd_table_stalled.argtypes = [c.c_void_p, c.c_double, c.c_double,
                                      c.c_char_p, c.c_int64]

    lib.hvd_timeline_create.restype = c.c_void_p
    lib.hvd_timeline_create.argtypes = [c.c_char_p, c.c_int]
    lib.hvd_timeline_destroy.argtypes = [c.c_void_p]
    lib.hvd_timeline_event.argtypes = [c.c_void_p, c.c_char_p, c.c_char_p,
                                       c.c_int]
    lib.hvd_timeline_cycle.argtypes = [c.c_void_p]
    lib.hvd_timeline_pending.restype = c.c_int64
    lib.hvd_timeline_pending.argtypes = [c.c_void_p]

    lib.hvd_autotune_create.restype = c.c_void_p
    lib.hvd_autotune_create.argtypes = [c.c_double, c.c_double, c.c_double,
                                        c.c_double, c.c_uint64]
    lib.hvd_autotune_destroy.argtypes = [c.c_void_p]
    lib.hvd_autotune_record.argtypes = [c.c_void_p, c.c_double, c.c_double,
                                        c.c_double]
    lib.hvd_autotune_suggest.argtypes = [c.c_void_p, c.POINTER(c.c_double),
                                         c.POINTER(c.c_double)]
    lib.hvd_autotune_num_samples.restype = c.c_int64
    lib.hvd_autotune_num_samples.argtypes = [c.c_void_p]
    lib.hvd_autotune_best.restype = c.c_int
    lib.hvd_autotune_best.argtypes = [c.c_void_p, c.POINTER(c.c_double),
                                      c.POINTER(c.c_double),
                                      c.POINTER(c.c_double)]

    lib.hvd_hash_bytes.restype = c.c_uint64
    lib.hvd_hash_bytes.argtypes = [c.c_void_p, c.c_int64]
    return lib


def _source_digest(deps, cmd):
    """sha256 over the build command and the CONTENT of every dependency.
    Content, not mtime: a copied or freshly checked-out tree carries
    arbitrary timestamps, and a stale library must not outlive its
    sources because the copy happened to touch it last."""
    h = hashlib.sha256(" ".join(cmd).encode())
    for path in deps:
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _build(lib_path, cmd, deps, force):
    """Run ``cmd + ["-o", lib_path]`` unless a library built from exactly
    these ``deps`` with exactly this command is already there (the
    digest is kept in ``<lib>.stamp`` next to it). The compiler writes
    to a private name and the result is renamed into place, so a
    concurrent process (hvdrun ranks on a fresh checkout) never loads a
    half-written library."""
    stamp_path = lib_path + ".stamp"
    digest = _source_digest(deps, cmd)
    if not force and os.path.exists(lib_path) and \
            os.path.exists(stamp_path):
        with open(stamp_path) as f:
            if f.read().strip() == digest:
                return lib_path
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    try:
        subprocess.run(cmd + ["-o", tmp], check=True)
        os.replace(tmp, lib_path)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    with open(stamp_path, "w") as f:
        f.write(digest + "\n")
    return lib_path


def _src(*names):
    return [os.path.join(_DIR, "src", n) for n in names]


def build(force=False):
    """Compile libhvd_core.so with g++ (no external deps)."""
    sources = _src("hvd_core.cc", "timeline.cc", "autotune.cc")
    cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread",
           "-fvisibility=hidden"] + sources
    return _build(_LIB_PATH, cmd, sources + _src("hvd_core.h"), force)


_PLANE_LIB_PATH = os.path.join(_DIR, "libhvd_plane.so")


def build_plane(force=False):
    """Compile the framework-agnostic collective plane's C API
    (libhvd_plane.so from plane.h + plane_c.cc — no TensorFlow linkage;
    the ctypes surface for the torch frontend)."""
    sources = _src("plane_c.cc")
    # -fvisibility=hidden: the inline Plane singleton must not merge
    # with libhvd_tf.so's copy when both are loaded (plane.h note)
    cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread",
           "-fvisibility=hidden"] + sources
    # shm_ring.h is included by plane.h: both are dependencies
    return _build(_PLANE_LIB_PATH, cmd,
                  sources + _src("plane.h", "shm_ring.h"), force)


_TF_LIB_PATH = os.path.join(_DIR, "libhvd_tf.so")


def build_tf(force=False):
    """Compile the native TensorFlow custom ops (libhvd_tf.so) against the
    installed TF's headers (tf.sysconfig — the reference builds its TF
    extension the same way, setup.py build_tf_extension). Raises if
    TensorFlow is not importable; callers treat that as 'unavailable'."""
    import tensorflow as tf  # deferred: TF is an optional frontend dep

    sources = _src("tf_ops.cc")
    # -fvisibility=hidden: see build_plane (shared singleton hazard)
    cmd = (["g++", "-O2", "-shared", "-fPIC", "-pthread",
            "-fvisibility=hidden"] + sources
           + tf.sysconfig.get_compile_flags()
           + tf.sysconfig.get_link_flags())
    return _build(_TF_LIB_PATH, cmd,
                  sources + _src("plane.h", "shm_ring.h"), force)


def load(auto_build=True):
    """Load (building if needed) the native core; returns the lib or None.
    A failed build/load is cached so the hot path never re-spawns g++;
    the cause is logged once and kept in ``LOAD_ERROR``."""
    global LIB, _LOAD_FAILED, LOAD_ERROR
    if LIB is not None:
        return LIB
    if _LOAD_FAILED:
        return None
    if os.environ.get("HVD_DISABLE_NATIVE", "") in ("1", "true"):
        _LOAD_FAILED = True
        LOAD_ERROR = "disabled by HVD_DISABLE_NATIVE"
        return None
    try:
        if auto_build:
            build()  # no-op when the .so matches its sources' digest
        elif not os.path.exists(_LIB_PATH):
            raise FileNotFoundError(_LIB_PATH)
        LIB = _configure(ctypes.CDLL(_LIB_PATH))
    except (OSError, subprocess.CalledProcessError, AttributeError) as e:
        # no toolchain (FileNotFoundError is an OSError), a failed
        # compile, an unloadable library or one missing a symbol: the
        # pure-Python implementations carry on, but never silently
        LIB = None
        _LOAD_FAILED = True
        LOAD_ERROR = f"{type(e).__name__}: {e}"
        from ..common import hvd_logging
        hvd_logging.warning(
            "native core unavailable (%s); using the Python fallbacks",
            LOAD_ERROR)
    return LIB


def available():
    return load() is not None
