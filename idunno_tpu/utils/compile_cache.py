"""Persistent XLA compile cache.

A cold TPU compile of a serving program takes seconds to tens of seconds; the
reference's analogue cost — torch.hub model download + load on EVERY task
(`alexnet_resnet.py:17-22`) — is exactly what the engine eliminates by
keeping weights resident. The compile cache finishes the job across
*processes*: executables land on disk keyed by HLO, so node restarts and
repeat runs skip straight to run.

The directory is part of the cache key, so it must not move between runs:
where ``JAX_COMPILATION_CACHE_DIR`` is set the operator placed the cache and
JAX reads the variable itself; otherwise it is the fixed in-checkout
``.jax_cache``.
"""
from __future__ import annotations

import os

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "misses"}
_counts = dict.fromkeys(_EVENTS.values(), 0)
_listening = False


def enable_persistent_cache(min_compile_secs: float = 2.0) -> str:
    """Turn on the on-disk compilation cache (idempotent; safe before or
    after backend init). Returns the directory in use."""
    global _listening
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    if not _listening:
        _listening = True

        def count(event: str, **_kw) -> None:
            if event in _EVENTS:
                _counts[_EVENTS[event]] += 1
        jax.monitoring.register_event_listener(count)
    return jax.config.jax_compilation_cache_dir


def cache_counters() -> dict:
    """Persistent-cache hits and misses of this process since
    `enable_persistent_cache`, plus the directory (the `device` verb)."""
    import jax

    return {"dir": jax.config.jax_compilation_cache_dir, **_counts}
