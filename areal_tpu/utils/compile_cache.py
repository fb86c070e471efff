"""Persistent XLA compile-cache placement, one rule for every entrypoint.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already uses that
directory and this module sets nothing else. Where it is not, the cache
lives at the fixed ``<checkout>/.jax_cache`` (git-ignored): the path is
part of the cache key, so a directory derived from a temporary name, a
pid or the time would never hit.

The cache is enabled ONLY when the initialized backend is really TPU. A
CPU run must not write into a directory TPU runs share: this repo compiles
TPU programs on the CPU backend for a described chip (tests, rehearsals),
such entries cannot be read back without a chip, and every later run would
warn and recompile; CPU AOT entries are also specific to the machine's
CPU features, and the sandbox and the chip host differ.

This module also owns the XLA compile COUNTERS
(``install_compile_counters``): a jax monitoring listener that feeds every
backend compilation into ``areal_xla_compiles_total`` /
``areal_xla_compile_seconds`` (+ persistent-cache hits), so recompile
storms — a drifting jit shape key recompiling the train program every
step — show up as a climbing counter on the trainer dashboard instead of
as mystery wall time (docs/observability.md "Trainer observatory").
"""

import os
import threading

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

# jax monitoring event names: one duration event per backend compile, one
# point event per persistent-cache hit
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_stats_lock = threading.Lock()
_COMPILE_STATS = {"compiles": 0, "compile_seconds": 0.0, "cache_hits": 0}
_INSTALLED = False


def enable_persistent_cache() -> str | None:
    """Enable the persistent compile cache if (and only if) backend==tpu.

    Returns the cache dir in effect, or None when disabled. Safe to call
    repeatedly. ``JAX_COMPILATION_CACHE_DIR`` (which jax reads itself)
    wins; otherwise the fixed ``<checkout>/.jax_cache``.
    """
    import jax

    if jax.default_backend() != "tpu":
        return None
    if jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    # cache even sub-second programs — whatever dir is in effect: the
    # serving path replays dozens of small chunk/scatter variants whose
    # compiles sum to the cold-start cost
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # op names are part of the program: by default jax strips them from the
    # cache key, and an executable cached before a ``jax.named_scope`` or a
    # kernel name changed would then be served with the old names in every
    # device trace (docs/observability.md "Spans and scopes")
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return jax.config.jax_compilation_cache_dir


def install_compile_counters() -> bool:
    """Feed every XLA backend compilation into the catalogued compile
    metrics. Idempotent; returns False when the jax monitoring hook is
    unavailable (the observatory then simply shows no compile rows)."""
    global _INSTALLED
    if _INSTALLED:
        return True
    try:
        from jax._src import monitoring
    except ImportError:
        return False
    from areal_tpu.observability import catalog as obs_catalog

    obs = obs_catalog.train_obs_metrics()

    def _on_duration(event: str, duration: float, **_kw) -> None:
        if event != _COMPILE_EVENT:
            return
        with _stats_lock:
            _COMPILE_STATS["compiles"] += 1
            _COMPILE_STATS["compile_seconds"] += duration
        obs.compiles.inc()
        obs.compile_seconds.observe(duration)

    def _on_event(event: str, **_kw) -> None:
        if event != _CACHE_HIT_EVENT:
            return
        with _stats_lock:
            _COMPILE_STATS["cache_hits"] += 1
        obs.compile_cache_hits.inc()

    try:
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
    except Exception:  # noqa: BLE001 — monitoring API drift: degrade quiet
        return False
    _INSTALLED = True
    return True


def compile_stats() -> dict:
    """Process-lifetime compile counters (also mirrored in the metric
    registry): compiles, total compile seconds, persistent-cache hits."""
    with _stats_lock:
        return dict(_COMPILE_STATS)
