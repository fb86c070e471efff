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

The same listener writes what it hears into the span record
(``utils/perf_tracer.py``; docs/observability.md "Spans and scopes"), each
duration event as a span that has just ended on the calling thread:

    areal.xla.trace       a function traced to a jaxpr
    areal.xla.lower       the jaxpr lowered to an MLIR module
    areal.xla.compile     XLA's backend compile OR, on a hit, the read of the
                          persistent cache in its place (jax 0.9.0 times
                          ``compile_or_get_cached`` as a whole)
    areal.xla.cache_load  the persistent cache's read, on a hit only, and
                          then inside the ``areal.xla.compile`` it served

A program's first call fires trace, lower and compile whether or not the
persistent cache serves it; a later call of the same shapes fires none.
``FirstCall`` puts that first call inside the span ``areal.program.build``,
named by the program and its shape key, so the four nest under it by time.
"""

import os
import threading
import time

from areal_tpu.utils import perf_tracer

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

# jax monitoring event names: one duration event per backend compile, one
# point event per persistent-cache hit
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
# every duration event that becomes a span of the record
_SPAN_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "areal.xla.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "areal.xla.lower",
    _COMPILE_EVENT: "areal.xla.compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "areal.xla.cache_load",
}

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

    def _on_duration(event: str, duration: float, **kw) -> None:
        name = _SPAN_OF_EVENT.get(event)
        if name is None:
            return
        end = time.monotonic_ns()
        fun = kw.get("fun_name")
        perf_tracer.get_tracer().add_span(
            name, end - int(duration * 1e9), end, perf_tracer.Category.INSTR, {"fun": fun} if fun else None
        )
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


class FirstCall:
    """A jitted program (``fn``, just cached under ``key``, its kind first) as
    its builder hands it to the caller that saw the miss: the call through it
    is the program's first, the one that traces, lowers and compiles or loads
    it, and runs inside the span ``areal.program.build``. Inside a measured
    window that span says which program was built, by name. Everything else
    (``lower`` for an ahead-of-time compile) is the jitted function's own;
    the cache keeps the plain jitted function."""

    __slots__ = ("_fn", "_key", "__weakref__")  # jax keys its caches by weak references to callables

    def __init__(self, fn, key: tuple):
        self._fn, self._key = fn, key

    def __call__(self, *args, **kwargs):
        key = self._key
        with perf_tracer.trace_scope(
            "areal.program.build", perf_tracer.Category.INSTR, {"program": str(key[0]), "key": repr(key[1:])}
        ):
            return self._fn(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._fn, name)


def compile_stats() -> dict:
    """Process-lifetime compile counters (also mirrored in the metric
    registry): compiles, total compile seconds, persistent-cache hits."""
    with _stats_lock:
        return dict(_COMPILE_STATS)
