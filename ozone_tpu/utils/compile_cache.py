"""Where compiled programs are kept between processes, and how many
this process built.

The served path is many short-lived CLI processes running the same few
jitted programs; without a persistent cache each one recompiles them.
The rule, applied by every entry point that dispatches (tools/cli.py
main; chip_smoke.py's children inherit it by environment):

- ``JAX_COMPILATION_CACHE_DIR`` set: touch nothing. JAX reads the
  variable itself, and no code names another directory.
- unset: ``<checkout>/.jax_cache`` (git-ignored). The path is part of
  the cache key, so it is fixed — never a tempfile/pid/time name. Every
  program is cached there, however quickly it compiled: with JAX's
  default 1 s floor a program near the floor is written on one run and
  not the next, and "a second run adds no entries" stops being a check.
  (``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` in the environment
  wins.)

Imports no jax at module level: launchers that must stay off the chip
call ensure_compile_cache too.
"""

from __future__ import annotations

import os
import sys
import threading
from pathlib import Path

_DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def ensure_compile_cache() -> str:
    """Apply the rule above; returns the directory in force."""
    preset = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if preset:
        return preset
    # (env var, jax config name, value): the environment carries each
    # to child processes and to a jax import still to come; a jax
    # already imported read its config at import, so it is told too
    wanted = [("JAX_COMPILATION_CACHE_DIR",
               "jax_compilation_cache_dir", str(_DEFAULT_DIR)),
              ("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
               "jax_persistent_cache_min_compile_time_secs", 0.0)]
    jax = sys.modules.get("jax")
    for env, name, value in wanted:
        if os.environ.get(env):
            continue
        os.environ[env] = str(value)
        if jax is not None:
            jax.config.update(name, value)
    return os.environ["JAX_COMPILATION_CACHE_DIR"]


_LOCK = threading.Lock()
_COUNTS: dict[str, int] = {}
_EVENT_KEYS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    # recorded when a freshly compiled program is WRITTEN to the
    # persistent cache: a warm second run must count zero of these
    "/jax/compilation_cache/cache_misses": "cache_writes",
}


def count_compiles() -> None:
    """Start counting the programs this process builds — every jitted
    program, whoever compiles it. Called once, before anything compiles,
    by the entry points that report the counts (`freon`, chip_smoke's
    kernels child); a second call changes nothing."""
    import jax  # registering a listener initialises no backend

    def on_event(event: str, **_kw) -> None:
        key = _EVENT_KEYS.get(event)
        if key is not None:
            with _LOCK:
                _COUNTS[key] += 1

    def on_duration(event: str, _secs: float, **_kw) -> None:
        # fires once per program built, persistent-cache hit or not
        if event == "/jax/core/compile/backend_compile_duration":
            with _LOCK:
                _COUNTS["compiles"] += 1

    with _LOCK:
        if _COUNTS:
            return
        _COUNTS.update(compiles=0, cache_hits=0, cache_writes=0)
    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)


def compile_counts() -> dict:
    """{compiles, cache_hits, cache_writes} since count_compiles();
    empty where nothing asked for the count."""
    with _LOCK:
        return dict(_COUNTS)
