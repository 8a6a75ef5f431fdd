"""Placement of JAX's persistent compilation cache.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory. Otherwise the cache goes to the fixed
``<repo>/.jax_cache`` (listed in ``.gitignore``): the directory is part of
what a later run has to find again, so it is never a temp or per-process
path. :func:`stats` counts the cache's hits and misses in this process.
"""
from __future__ import annotations

import collections
import os
import pathlib
from typing import Dict

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"

_EVENTS: collections.Counter = collections.Counter()
_listening = False


def _count(event: str, **_) -> None:
    if event.startswith("/jax/compilation_cache/"):
        _EVENTS[event.rsplit("/", 1)[1]] += 1


def enable() -> str:
    """Place the persistent compilation cache; returns its directory."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_listener(_count)
        _listening = True
    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def stats() -> Dict[str, int]:
    """Persistent-cache lookups in this process since :func:`enable`."""
    return {"hits": _EVENTS["cache_hits"], "misses": _EVENTS["cache_misses"],
            "requests": _EVENTS["compile_requests_use_cache"]}
