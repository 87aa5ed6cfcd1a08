"""Where the persistent XLA compile cache lives: one resolver for every
process that compiles.

``JAX_COMPILATION_CACHE_DIR`` wins when it is set — jax reads it itself,
and nothing in this package sets another directory over it. Unset, the
cache is a fixed directory inside the checkout (git-ignored): the path is
part of what makes a cache persistent, so it never depends on the temp
dir, a uid, a pid or the time. A worker restarted after a crash, a
serving replica coming back and the next phase of the chip smoke all
find what the process before them compiled.

This module stays importable without touching jax, so the launcher's
agent (which must not hold the chip) can resolve the directory for the
workers it spawns.
"""

import os

ENV = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_compile_cache")


def compile_cache_dir(configured: str = "") -> str:
    """The directory to cache in: the environment's, else ``configured``
    (the launcher's ``--compile-cache-dir``), else the fixed in-checkout
    path."""
    return os.environ.get(ENV) or configured or DEFAULT_DIR


def enable_compile_cache() -> str:
    """Point this process's jax at the resolved directory and return it.
    With the environment variable set jax already has it; nothing is
    overridden."""
    path = compile_cache_dir()
    if not os.environ.get(ENV):
        import jax

        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
