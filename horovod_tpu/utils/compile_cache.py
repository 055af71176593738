"""JAX's persistent compilation cache, placed once for every entry point.

Entry points (chip_smoke.py, the examples) call ``configure()``
before their first compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and nothing is set in code — whoever runs the program
owns the location. Where it is not, the cache lives at one fixed path
inside the checkout, ``<repo>/.jax_cache`` (ignored by git): the
directory is part of the cache key, so a path built from ``tempfile``, a
pid or a time would never hit.
"""

import os

import jax

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def configure():
    """Point JAX's persistent compile cache somewhere durable; returns the
    directory in use. Touches configuration only — no backend is created."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
