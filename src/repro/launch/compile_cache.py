"""Where the launchers keep JAX's persistent compilation cache.

A cold start compiles the full-width GAN step for minutes; the persistent
cache lets the next process from the same checkout load it instead.
Called once at the top of each entry point's ``main`` (never on import,
never from tests, which must not write into the checkout).
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when it is set: JAX reads it
    itself, so nothing is set here.  Otherwise the cache lives at the
    fixed path ``<checkout>/.jax_cache`` — never a temporary or per-run
    name, since a directory that moves between runs never hits.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
