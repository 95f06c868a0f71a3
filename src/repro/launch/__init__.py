"""Launchers: mesh, dryrun, train, serve."""
import os

# <repo>/.jax_cache: a fixed path, because the path is part of the
# cache key — a directory that moves between runs never hits
_REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX, which reads
    it itself; otherwise the cache lives at ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", _REPO_CACHE)
    return _REPO_CACHE
