"""Where JAX keeps its persistent compilation cache, and a count of the
programs compiled.

The cache is keyed partly by its own path, so it lives at one fixed
place: the directory ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that
variable itself), else ``<repo root>/.jax_cache`` (git-ignored).
"""
from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point the persistent compile cache at its fixed directory and
    return that directory.  Call it from a ``main()`` before the first
    compile, never at import."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return jax.config.jax_compilation_cache_dir


class CompileLog:
    """Programs compiled (or loaded from the persistent cache) and the
    seconds spent on it, from JAX's own monitoring events."""

    def __init__(self):
        self.n, self.seconds, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def line(self) -> str:
        return (f"compiles: {self.n} programs in {self.seconds:.3f} s "
                f"({self.cache_hits} loaded from the persistent cache)")
