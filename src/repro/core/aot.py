"""AOT executable cache for the device-resident epoch pipeline.

``jax.jit`` hides a compile stall inside the first call for every new
(shape, static) combination — fatal for a serving loop that must never
pause mid-stream.  :class:`AotDispatchCache` owns the executables
explicitly: dispatch sites build them with ``jit(...).lower(...).compile()``
under a key of their choosing (dispatch fingerprint + bucketed shapes +
mesh), so

  * a cache hit is a dict lookup — zero lowerings, observable via the
    ``lowerings``/``hits`` counters (the AOT-cache tests and the
    ``epoch_pipeline`` benchmark assert ``lowerings`` stays flat across a
    steady-state serving loop);
  * a miss can be taken *ahead of time* (:meth:`warm`), at attach or
    engine start, so the first real dispatch already finds a compiled
    executable;
  * the compile cost is measured where it happens and reported as
    ``compile_s`` in :class:`~repro.core.analyzer.DispatchStats` instead
    of silently inflating one dispatch's latency.

Note that ``.lower().compile()`` does **not** populate ``jit``'s own
python-level cache — a site that sometimes calls the jitted wrapper and
sometimes the AOT executable would compile twice.  Pipeline dispatch
therefore always routes through this cache.

:func:`install_persistent_cache` additionally wires JAX's on-disk
compilation cache so executables survive process restarts.
"""

from __future__ import annotations

import os
import threading
import weakref
from pathlib import Path
from typing import Any, Callable, Dict, Hashable, Tuple

import jax

__all__ = ["AotDispatchCache", "install_persistent_cache"]

_REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


class AotDispatchCache:
    """Thread-safe map from dispatch key to a compiled XLA executable.

    ``get`` returns ``(executable, hit)``; ``lowerings`` counts how many
    times a build actually ran (the steady-state invariant is that it
    stops growing), ``hits`` counts lookups served without one.
    """

    # every live cache, so RecompileSanitizer can snapshot/diff the
    # process-wide lowering count without threading a handle everywhere
    _instances: "weakref.WeakSet[AotDispatchCache]" = weakref.WeakSet()
    _instances_lock = threading.Lock()

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._cache: Dict[Hashable, Any] = {}
        self.lowerings = 0
        self.hits = 0
        with AotDispatchCache._instances_lock:
            AotDispatchCache._instances.add(self)

    @classmethod
    def total_lowerings(cls) -> int:
        """Sum of ``lowerings`` across every live cache (sanitizer probe)."""
        with cls._instances_lock:
            caches = list(cls._instances)
        return sum(c.lowerings for c in caches)

    def __len__(self) -> int:
        return len(self._cache)

    def get(
        self, key: Hashable, build: Callable[[], Any]
    ) -> Tuple[Any, bool]:
        with self._lock:
            exe = self._cache.get(key)
            if exe is not None:
                self.hits += 1
                return exe, True
        # build outside the lock: lowering can take seconds and other
        # dispatch keys must not queue behind it
        exe = build()
        with self._lock:
            won = self._cache.setdefault(key, exe)
            if won is exe:
                self.lowerings += 1
            else:
                self.hits += 1
            return won, won is not exe

    def warm(self, key: Hashable, build: Callable[[], Any]) -> bool:
        """Ensure ``key`` is compiled; returns True if this call built it."""
        _, hit = self.get(key, build)
        return not hit


def install_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already taken the
    directory from it and this sets none.  Otherwise the cache goes to the
    fixed path ``<repo>/.jax_cache`` (listed in ``.gitignore``): the path is
    part of the cache key, so a directory that moved would never hit.
    Compiled modules are then reloaded across process restarts, so a fresh
    server's first dispatch skips XLA compilation for shapes it has served
    before.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(_REPO_CACHE))
    # default thresholds skip "cheap" compiles; a serving loop wants every
    # executable persisted
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
