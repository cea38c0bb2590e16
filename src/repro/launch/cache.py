"""Where JAX keeps its persistent compilation cache.

One rule for every entry point (`chip_smoke.py`, `repro.launch.serve`,
`repro.launch.dryrun`): if `JAX_COMPILATION_CACHE_DIR` is set, JAX reads
it itself and nothing is set here; otherwise the cache lives at one
fixed path inside the checkout, `<repo>/.jax_cache` (listed in
`.gitignore`), so repeated runs of the same checkout hit it.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
