"""Placement of JAX's persistent compilation cache.

The entry scripts (chip_smoke.py, benchmark/run.py) call `enable()`
before their first trace so a second run in the same checkout does not
compile the train step, the engine's prefill/decode families and the
kernels from nothing. Library code sets no cache on import.

The directory is part of the cache key, so it must not move between
runs: either the operator places it with `JAX_COMPILATION_CACHE_DIR`
(JAX reads that itself; nothing is set here), or it is
`<checkout>/.jax_cache`, derived from this file's location.

What the key leaves out: JAX strips an op's debug info, its name stack
among it, from the key (`jax._src.cache_key`, unless
`jax_compilation_cache_include_metadata_in_key`), and `telemetry.scope`
IS a name in that stack. A cache written by a tree with other scopes,
or none, serves executables whose ops carry that tree's names, and a
device trace then reads the old layers (`benchmark/readers/
device_scope.py` reads None for every layer after a scope-less tree).
Two checkouts never share `<checkout>/.jax_cache`; an operator who
places one directory for several trees with `JAX_COMPILATION_CACHE_DIR`
clears it when the scopes change.
"""
import os

import jax

__all__ = ["enable", "CacheCounter"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable():
    """Turn the persistent compile cache on; returns its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class CacheCounter:
    """Counts persistent-cache hits and misses from JAX's own
    monitoring events, so an entry script can say whether a run reused
    what an earlier one compiled."""

    def __init__(self):
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1
