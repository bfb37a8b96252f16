"""JAX's persistent compilation cache at one fixed place, and a counter of
what the process compiles.

``enable()`` is called by every command that runs on the chip
(``chip_smoke.py``, ``benchmarks/run.py``) before its first compile.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing
else is set here. Otherwise the cache lives in ``.jax_cache/`` at the
root of the checkout: a fixed path, because the path is part of the
cache key and a directory that moves never hits.
"""
from __future__ import annotations

import os
import threading
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        DEFAULT_DIR.mkdir(exist_ok=True)
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # Keep every executable, small kernels included: a cold call on the
    # chip otherwise recompiles them all.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts executables the process builds, the seconds spent building
    them, and how many of them the persistent cache supplied."""

    _lock = threading.Lock()
    _installed = False
    compiles = 0
    compile_s = 0.0
    cache_hits = 0

    @classmethod
    def install(cls) -> None:
        from jax import monitoring
        with cls._lock:
            if cls._installed:
                return
            cls._installed = True

        def on_duration(event, duration, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                with cls._lock:
                    cls.compiles += 1
                    cls.compile_s += duration

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                with cls._lock:
                    cls.cache_hits += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    @classmethod
    def snapshot(cls) -> dict:
        with cls._lock:
            return {"executables": cls.compiles,
                    "cache_hits": cls.cache_hits,
                    "compiled": cls.compiles - cls.cache_hits,
                    "compile_s": cls.compile_s}

    @classmethod
    def since(cls, before: dict) -> dict:
        now = cls.snapshot()
        return {k: now[k] - before[k] for k in now}
