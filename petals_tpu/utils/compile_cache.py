"""Where XLA's persistent compilation cache lives — one rule for every entry
point (run_server, run_worker, Server.start, chip_smoke.py, perf/, benchmarks/).

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own handling of that variable
is the whole story and nothing here sets a directory. Otherwise the cache is
``<checkout>/.jax_cache``, resolved from this package's location: the path is
part of every cache key, so it must not move between runs (no home
directory, no temp name, no pid, no time). ``PETALS_TPU_NO_COMPILATION_CACHE``
turns the default off (multi-process test swarms must not share one).
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Dict, Optional

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "writes",
}


def enable_compilation_cache() -> Optional[str]:
    """Apply the rule above; returns the directory in effect (None = off).
    Call before the first compilation."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    if os.environ.get("PETALS_TPU_NO_COMPILATION_CACHE"):
        return None
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


def count_cache_events() -> Dict[str, int]:
    """Start counting this process's persistent-cache traffic; the returned
    dict is live: ``requests`` (compilations that consulted the cache),
    ``hits`` (executables loaded from it) and ``writes`` (new entries)."""
    import jax.monitoring

    counts = dict.fromkeys(_EVENTS.values(), 0)

    def on_event(event: str, **_kwargs) -> None:
        name = _EVENTS.get(event)
        if name is not None:
            counts[name] += 1

    jax.monitoring.register_event_listener(on_event)
    return counts
