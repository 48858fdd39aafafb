"""The persistent compilation cache: where it lives, and a crash-safe
writer for it.

:func:`enable_compile_cache` is the ONE place the program decides where
compiled executables are kept.  Where ``JAX_COMPILATION_CACHE_DIR`` is
set, jax already honours it and nothing is set in code; otherwise the
cache is ``<checkout>/.jax_cache`` — a fixed path (the path is part of
the cache key's environment, so a directory that moves never hits),
git-ignored, shared by every process started from this checkout.
Every entry point that compiles calls it before its first jit
(``python -m multiraft_tpu serve-*``, ``cluster._server_main``,
``bench.py``, ``benchmarks/*``, ``chip_smoke.py``'s children,
``tests/conftest.py``).

jax 0.9.0's file-system cache writes entries IN PLACE
(``LRUCache.put`` → ``Path.write_bytes``): a process SIGKILLed
mid-write leaves a truncated serialized executable under the final
name, and a concurrent reader can observe the same torn state while a
sibling writes.  Deserializing a truncated executable does not fail
cleanly — it SEGFAULTS the process (observed: a chaos-restarted engine
server dying with SIGSEGV inside its first cached tick dispatch,
tests/test_chaos.py).  Multi-process engine fleets hit both windows:
several servers share one cache dir, and the nemesis kills them at
arbitrary points.

:func:`harden_persistent_cache` swaps the write for the standard
crash-safe idiom — temp file in the same directory, then an atomic
``os.replace`` — so the final name only ever points at a complete
entry.  :func:`enable_compile_cache` calls it."""

from __future__ import annotations

import os
import time
import warnings

__all__ = [
    "CACHE_ENV",
    "cache_dir",
    "enable_compile_cache",
    "harden_persistent_cache",
]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def cache_dir() -> str:
    """Where this process keeps compiled executables: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``<checkout>/.jax_cache``."""
    return os.environ.get(CACHE_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on for this process and
    make its writes atomic.  Call before the first jit.  Returns the
    cache directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set jax has already read it and
    this sets nothing; unset, the cache is ``<checkout>/.jax_cache``,
    exported so that child processes (servers, examples) inherit the
    same directory."""
    import jax

    path = cache_dir()
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
        os.environ[CACHE_ENV] = path
    harden_persistent_cache()
    return path


def harden_persistent_cache() -> None:
    """Make jax's on-disk compilation-cache writes atomic (idempotent).
    Raises when the installed jax has no ``LRUCache.put`` to replace:
    carrying on would bring the torn-entry segfault back silently."""
    from jax._src import lru_cache as _m

    cls = _m.LRUCache
    if getattr(cls, "_mrt_atomic_put", False):
        return
    if not callable(getattr(cls, "put", None)):
        raise RuntimeError(
            "jax._src.lru_cache.LRUCache.put is gone: the crash-safe "
            "cache writer in utils/jaxcache.py must be ported to this jax"
        )

    def put(self, key: str, val: bytes) -> None:
        if not key:
            raise ValueError("key cannot be empty")
        if self.eviction_enabled and len(val) > self.max_size:
            warnings.warn(
                f"Cache value for key {key!r} of size {len(val)} bytes "
                f"exceeds the maximum cache size of {self.max_size} bytes"
            )
            return
        cache_path = self.path / f"{key}{_m._CACHE_SUFFIX}"
        if self.eviction_enabled:
            self.lock.acquire(timeout=self.lock_timeout_secs)
        try:
            if cache_path.exists():
                return
            self._evict_if_needed(additional_size=len(val))
            # The one behavioral change vs upstream: write to a
            # pid-unique temp name, publish with an atomic rename.  A
            # crash mid-write strands a temp file; it never produces a
            # truncated entry under the final name.
            tmp = cache_path.with_name(f"{cache_path.name}.tmp{os.getpid()}")
            try:
                tmp.write_bytes(val)
                os.replace(tmp, cache_path)
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
            if self.eviction_enabled:
                timestamp = time.time_ns().to_bytes(8, "little")
                atime_path = self.path / f"{key}{_m._ATIME_SUFFIX}"
                atime_path.write_bytes(timestamp)
        finally:
            if self.eviction_enabled:
                self.lock.release()

    cls.put = put
    cls._mrt_atomic_put = True
