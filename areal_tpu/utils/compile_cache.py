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

A program's first call, through ``FirstCall``, runs inside the span
``areal.program.build``, named by the program and its shape key, so what fires
nests under it by time; a later call of the same shapes fires none.

THE PROGRAM STORE (``ProgramStore``, entered at ``FirstCall``). jax's
persistent cache is keyed by the LOWERED module, so a process has to trace
and lower a program's Python (two thirds of a warm build) to learn that it
need not compile it. The store sits beside that cache, at ``<cache
dir>/programs/``, under the same rule (TPU backend only; the XLA cache's
lifetime; ``JAX_COMPILATION_CACHE_DIR`` moves both), and is keyed by what the
first call holds WITHOUT tracing:

    the contents of every ``*.py`` under ``areal_tpu/`` by relative path (any
    edit is a cold start, as it is for the XLA cache, whose key holds op
    metadata); the versions of jax, jaxlib and the device runtime; the device
    kind and count, and this process's place among the job's; ``XLA_FLAGS``
    and ``LIBTPU_INIT_ARGS``; jax's trace context (x64,
    matmul precision, the context mesh); the builder's description of what
    its programs close over (``describe``: model and shape-bearing
    configuration, the mesh's shape, axis names and devices, the loss
    function and the loaded modules outside ``areal_tpu/`` where a program
    calls one) and its key (kind and shape fields); and the arguments' tree
    structure, shapes, dtypes, weak types and shardings.

A host constant of the traced form (numpy: a tile list, a rotary table, jax's
``TypedNdArray`` in ``traced.jaxpr.consts``) is in that key already: the
traced code MADE it from the source, the configuration and the arguments'
shapes, so such constants pass up to ``_MAX_HOST_CONST_BYTES`` in all. A
device array (``jax.Array``) was made outside the trace, by some process, and
its values are in no key: more than ``_MAX_CONST_ELEMS`` elements of them
refuse the program (``constants_not_in_the_key``).

The first call computes that key and, on a HIT, reads the entry and loads its
executable (``jax.experimental.serialize_executable``): no trace, no
lowering. The read and load is an ``areal.xla.cache_load`` span inside the
build: the same act as the persistent cache's hit, an executable read from
disk and loaded. It stays AT the first call, on the caller's thread: a loader
thread fed at ``initialize()`` was built and measured (PERF.md, PR 56) and
gave 1 s of 26 where the warm-up asks for every program at once, because two
loads do not overlap and a load waits for the program running on the chip.
On a MISS (and wherever the store is off: every CPU run) it
traces, lowers and compiles once, explicitly (``trace().lower().compile()``:
jax's persistent cache still serves the compile), runs the executable, and
writes the entry (packed as jax's cache packs an executable: zstandard, else
zlib) under a temporary name and a rename. Either way the
builder's cache then keeps the loaded executable (``BuiltProgram``), so no
later call traces either. ``areal.program.build`` says which in ``served``:

    store    read from the store
    jit      traced, lowered and compiled (or loaded by jax's cache) here
    refused  as ``jit``, and the store will not hold it: its traced form
             closes over device arrays or too many bytes of host constants
             (values in no key), or what its builder closes over has no
             process-independent description

A later call whose arguments the executable does not take (another tree,
shape, dtype or sharding: an error before execution, so nothing was donated)
goes to the jitted function, which that key keeps from then on. A payload
that does not load is a miss with one WARNING. Counters:
``areal_program_store_hits_total``, ``_misses_total``, ``_refused_total``
(closures and mismatched calls). One log line says, once no program has been
built for a few seconds (set-up's end), how many programs were built, how
many the store served, and the seconds in loads and writes. An entry is a
pickle this program wrote: the directory is trusted as the XLA cache's is
(who can write there can already hand the server an executable).
"""

import dataclasses
import enum
import functools
import hashlib
import os
import pickle
import re
import sys
import sysconfig
import threading
import time
import types
import zlib

import numpy as np

from areal_tpu.utils import logging as alog
from areal_tpu.utils import perf_tracer

logger = alog.getLogger("compile_cache")

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


# -- the program store --------------------------------------------------------
_PACKAGE_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PROGRAMS = "programs"  # the store's directory inside the XLA cache's
_MAX_CONST_ELEMS = 16  # the DEVICE array elements a stored program's traced form may close over: a few scalars
_MAX_HOST_CONST_BYTES = 8 << 10  # and the bytes of host tables its traced code made (tile lists, a rotary table: under 1 KiB a program today)
_SETTLE_S = 5.0  # no program built for this long: set-up has ended, say what it built
_ADDRESS = re.compile(r" at 0x[0-9a-f]+")
_STORE_STATS = {"built": 0, "hits": 0, "misses": 0, "refused": 0, "load_seconds": 0.0, "write_seconds": 0.0}
_summary_timer: threading.Timer | None = None


def _digest_files(paths_by_name: dict[str, str]) -> str:
    h = hashlib.sha256()
    for name in sorted(paths_by_name):
        try:
            with open(paths_by_name[name], "rb") as f:
                body = f.read()
        except OSError:
            body = b"?"
        h.update(name.encode() + b"\0" + body + b"\0")
    return h.hexdigest()


@functools.cache
def source_digest() -> str:
    """The contents of every ``*.py`` under ``areal_tpu/``, by relative path."""
    files = {}
    for dirpath, _dirs, names in os.walk(_PACKAGE_DIR):
        for n in names:
            if n.endswith(".py"):
                path = os.path.join(dirpath, n)
                files[os.path.relpath(path, _PACKAGE_DIR)] = path
    return _digest_files(files)


def _outside_digest() -> str:
    """The source of every loaded module that is neither this package's (the
    tree digest has it) nor an installed one: what a loss function handed in
    from a script can call. Read when asked: modules load as a program runs."""
    installed = tuple(
        {os.path.realpath(sysconfig.get_path(k)) + os.sep for k in ("stdlib", "platstdlib", "purelib", "platlib")}
    )
    files = {}
    for name, mod in list(sys.modules.items()):
        path = getattr(mod, "__file__", None)
        if path and path.endswith(".py"):
            real = os.path.realpath(path)
            if not real.startswith(installed) and not real.startswith(_PACKAGE_DIR + os.sep):
                files[name] = real
    return _digest_files(files)


def describe(x, _depth: int = 0) -> str | None:
    """What a program's builder closes over, as a string that reads the same
    in every process that would build the same program; None where ``x`` has
    no such description (an array, an object that prints its address), and
    the store then leaves the program alone. Plain values, containers,
    dataclasses (the configurations), modules, dtypes, meshes, and functions
    by module, name, closure and defaults; a function from outside this
    package brings the digest of the loaded modules outside it."""
    if x is None or isinstance(x, (bool, int, float, complex, str, bytes, enum.Enum, np.dtype)):
        return repr(x)
    if _depth > 8:
        return None
    if isinstance(x, (tuple, list, set, frozenset)):
        parts = [describe(v, _depth + 1) for v in x]
        if None in parts:
            return None
        return f"{type(x).__name__}({', '.join(sorted(parts) if isinstance(x, (set, frozenset)) else parts)})"
    if isinstance(x, dict):
        parts = [(describe(k, _depth + 1), describe(v, _depth + 1)) for k, v in x.items()]
        if any(k is None or v is None for k, v in parts):
            return None
        return "{" + ", ".join(f"{k}: {v}" for k, v in sorted(parts)) + "}"
    if isinstance(x, types.ModuleType):
        return f"module {x.__name__}"
    if isinstance(x, type):
        return f"{x.__module__}.{x.__qualname__}"
    if isinstance(x, functools.partial):
        return describe(("partial", x.func, x.args, x.keywords), _depth + 1)
    if isinstance(x, types.MethodType):
        return describe(("method", x.__func__, x.__self__), _depth + 1)
    if isinstance(x, types.FunctionType):
        try:
            cells = [c.cell_contents for c in x.__closure__ or ()]
        except ValueError:  # a cell not yet filled
            return None
        inner = describe((cells, x.__defaults__, x.__kwdefaults__), _depth + 1)
        if inner is None:
            return None
        here = os.path.realpath(x.__code__.co_filename).startswith(_PACKAGE_DIR + os.sep)
        return f"function {x.__module__}.{x.__qualname__}:{x.__code__.co_firstlineno} {'' if here else _outside_digest()} {inner}"
    if type(x).__name__ == "Mesh" and hasattr(x, "axis_names"):
        return f"Mesh({dict(x.shape)}, {x.axis_names}, {[d.id for d in x.devices.flat]}, {x.devices.flat[0].device_kind})"
    if dataclasses.is_dataclass(x):
        said = repr(x)
        return None if _ADDRESS.search(said) else said
    return None


def _leaf_signature(x, said: dict):
    """(shape, dtype, weak type, sharding) as jit would take the argument;
    None for what is no array (a tracer: the call is inside a transformation)."""
    import jax

    if isinstance(x, jax.core.Tracer):
        return None
    if isinstance(x, jax.Array):
        sharding = x.sharding if x.committed else None
    elif isinstance(x, jax.ShapeDtypeStruct):
        sharding = x.sharding
    elif isinstance(x, (np.ndarray, np.generic)):
        return (x.shape, x.dtype.name, False, None)
    elif type(x) in (bool, int, float, complex):
        return ((), type(x).__name__, True, None)
    else:
        return None
    if sharding is not None:  # a few hundred leaves share a handful of shardings: each is printed once
        sharding = said.get(sharding) or said.setdefault(sharding, repr(sharding))
    return (tuple(x.shape), str(x.dtype), bool(getattr(x, "weak_type", False)), sharding)


def arguments_signature(args: tuple, kwargs: dict) -> str | None:
    """The flattened arguments' tree structure, shapes, dtypes, weak types and
    shardings; None where a leaf is no concrete array or scalar."""
    import jax

    leaves, tree = jax.tree.flatten((args, kwargs))
    said: dict = {}
    sigs = [_leaf_signature(x, said) for x in leaves]
    return None if None in sigs else f"{tree} {sigs}"


def _packing():
    """(pack, unpack) of an entry's bytes, as jax's own cache packs an
    executable: zstandard where it is installed, else zlib. A serialized
    executable is 9-24 MB a program of the hybrid cells unpacked."""
    try:
        import zstandard
    except ImportError:
        return zlib.compress, zlib.decompress
    return zstandard.ZstdCompressor().compress, zstandard.ZstdDecompressor().decompress


class ProgramStore:
    """Built programs' executables on disk, one file a program, named by the
    digest of everything the executable is a function of (the module's
    docstring has the list). ``directory`` None: the store is off, nothing is
    read or written (every backend but the TPU, by ``default_store``)."""

    def __init__(self, directory: str | None):
        self.directory = directory
        self._process: str | None = None

    def _environment(self) -> str:
        """What every program of this process is a function of, and jax's
        trace context of this call (x64, matmul precision, the context mesh)."""
        if self._process is None:
            import jax
            import jaxlib

            dev = jax.devices()[0]
            self._process = repr(
                (
                    source_digest(), jax.__version__, jaxlib.__version__, dev.client.platform_version, dev.device_kind,
                    len(jax.devices()), jax.process_index(), jax.process_count(),
                    os.environ.get("XLA_FLAGS"), os.environ.get("LIBTPU_INIT_ARGS"),
                )
            )
        try:
            from jax._src import config as jax_config

            context = _ADDRESS.sub("", repr(jax_config.trace_context()))
        except Exception:  # noqa: BLE001 — a private name: the rest of the key stands without it
            context = ""
        return self._process + context

    def entry(self, described: str | None, args: tuple, kwargs: dict) -> str | None:
        """The file name of the entry for a program its builder ``described``
        (what it closes over, and its key) on these arguments; None where the
        store is off or either has no description."""
        if self.directory is None or described is None:
            return None
        sig = arguments_signature(args, kwargs)
        if sig is None:
            return None
        return hashlib.sha256("\n".join((self._environment(), described, sig)).encode()).hexdigest()

    def load(self, entry: str, program: str):
        """The entry's executable, loaded (a ``jax.stages.Compiled``), inside
        an ``areal.xla.cache_load`` span; None on a miss. A payload that does
        not load (another runtime, a cut file) is a miss with one WARNING; the
        build that follows writes the entry anew."""
        import jax
        from jax.experimental import serialize_executable

        path = os.path.join(self.directory, entry)
        if not os.path.exists(path):
            return None
        t0 = time.monotonic_ns()
        try:
            with open(path, "rb") as f:
                devices, payload, in_tree, out_tree = pickle.loads(_packing()[1](f.read()))
            by_id = {d.id: d for d in jax.devices()}
            compiled = serialize_executable.deserialize_and_load(
                payload, in_tree, out_tree, execution_devices=[by_id[i] for i in devices]
            )
        except Exception as e:  # noqa: BLE001 — whatever a foreign or cut payload raises: a miss
            logger.warning(f"program store: the entry of {program} did not load ({type(e).__name__}: {e}); building it here ({path})")
            return None
        t1 = time.monotonic_ns()
        perf_tracer.get_tracer().add_span(
            "areal.xla.cache_load", t0, t1, perf_tracer.Category.INSTR, {"fun": program, "from": "program_store"}
        )
        with _stats_lock:
            _STORE_STATS["load_seconds"] += (t1 - t0) * 1e-9
        return compiled

    def save(self, entry: str, compiled, program: str) -> None:
        """Write the entry: a temporary name, then a rename. A failure to
        serialise or to write costs the next start its hit, nothing else."""
        from jax.experimental import serialize_executable

        t0 = time.monotonic()
        path = os.path.join(self.directory, entry)
        tmp = f"{path}.{os.getpid()}.tmp"
        try:
            os.makedirs(self.directory, exist_ok=True)
            # the devices it runs on, by id: an executable loads onto the devices it is told
            devices = [d.id for d in compiled._executable._unloaded_executable.device_list]
            whole = pickle.dumps((devices, *serialize_executable.serialize(compiled)), protocol=pickle.HIGHEST_PROTOCOL)
            with open(tmp, "wb") as f:
                f.write(_packing()[0](whole))
            os.replace(tmp, path)
        except Exception as e:  # noqa: BLE001
            logger.warning(f"program store: {program} was not written ({type(e).__name__}: {e})")
            if os.path.exists(tmp):
                os.remove(tmp)
        with _stats_lock:
            _STORE_STATS["write_seconds"] += time.monotonic() - t0


@functools.cache
def default_store() -> ProgramStore:
    """The process's store: ``<XLA cache dir>/programs`` where
    ``enable_persistent_cache`` enables that cache (the TPU backend), off
    everywhere else."""
    cache_dir = enable_persistent_cache()
    return ProgramStore(os.path.join(cache_dir, _PROGRAMS) if cache_dir else None)


@functools.cache
def _store_counters():
    from areal_tpu.observability import catalog as obs_catalog

    obs = obs_catalog.train_obs_metrics()
    return {"hits": obs.program_store_hits, "misses": obs.program_store_misses, "refused": obs.program_store_refused}


def _count(what: str) -> None:
    with _stats_lock:
        _STORE_STATS[what] += 1
    _store_counters()[what].inc()


def _say_when_settled(arm: bool) -> None:
    """Where the store is on, set-up's end gets one log line that says what it
    built: ``_SETTLE_S`` after a build ended with no other begun (a build
    inside a measured window brings the line again). A build that begins
    calls this with ``arm`` False, one that ends with True."""
    global _summary_timer
    with _stats_lock:
        if _summary_timer is not None:
            _summary_timer.cancel()
            _summary_timer = None
        if arm:
            _summary_timer = threading.Timer(_SETTLE_S, lambda: logger.info(store_summary()))
            _summary_timer.daemon = True
            _summary_timer.start()


def store_stats() -> dict:
    """Process-lifetime counts of the program store: programs built (first
    calls), hits, misses, refusals, seconds in its loads and in its writes."""
    with _stats_lock:
        return dict(_STORE_STATS)


def store_summary() -> str:
    s = store_stats()
    return (
        f"programs built: {s['built']}, {s['hits']} of them served from the program store "
        f"({s['misses']} missed and written, {s['refused']} refused), "
        f"{s['load_seconds']:.2f} s in its loads, {s['write_seconds']:.2f} s in its writes"
    )


def constants_not_in_the_key(consts) -> str | None:
    """Why a program whose traced form closes over ``consts``
    (``traced.jaxpr.consts``) may not be stored, or None. A device array
    (``jax.Array``) was made OUTSIDE the trace (a weight, a table some process
    computed): its values are in no key, and more than a few scalars of them
    refuse the program. A host constant (numpy: jax's ``TypedNdArray``) was
    made BY the traced code from the configuration and the arguments' shapes,
    all in the key with the source; the byte bound keeps a weight handed in as
    numpy out."""
    import jax

    device = [c for c in consts if isinstance(c, jax.Array)]
    closed = sum(int(np.size(c)) for c in device)
    host = sum(int(np.size(c)) * np.dtype(getattr(c, "dtype", np.float64)).itemsize for c in consts if not isinstance(c, jax.Array))
    if closed <= _MAX_CONST_ELEMS and host <= _MAX_HOST_CONST_BYTES:
        return None
    over, what = (device, f"{closed} array elements") if closed > _MAX_CONST_ELEMS else (consts, f"{host} bytes of host constants (over {_MAX_HOST_CONST_BYTES})")
    shapes = ", ".join(f"{getattr(c, 'dtype', type(c).__name__)}{list(np.shape(c))}" for c in over)
    return f"its traced form closes over {what}, whose values are in no key (pass arrays as arguments): {shapes}"


class BuiltProgram:
    """A program after its first call, as its builder's cache keeps it: the
    loaded executable (``jax.stages.Compiled``, jax's C++ call path), which no
    call traces. A call it does not take (another tree, shape, dtype or
    sharding than it was built for: jax raises before it executes, so nothing
    was donated) goes to the jitted function, which the cache keeps for that
    key from then on. Everything else (``lower``) is the jitted function's."""

    __slots__ = ("_compiled", "_fn", "_cache", "_key", "__weakref__")

    def __init__(self, compiled, fn, cache: dict, key: tuple):
        self._compiled, self._fn, self._cache, self._key = compiled, fn, cache, key

    def __call__(self, *args, **kwargs):
        try:
            return self._compiled(*args, **kwargs)
        except (TypeError, ValueError) as e:
            return self._mismatched(e, args, kwargs)

    def _mismatched(self, err, args, kwargs):
        import jax

        if any(x.is_deleted() for x in jax.tree.leaves((args, kwargs)) if isinstance(x, jax.Array)):
            raise err  # an argument is gone (donated to an earlier call): nothing to call again with
        logger.warning(
            f"program {self._key}: a call does not match the executable of its first call "
            f"({str(err).splitlines()[0][:200]}); the jitted function takes this key from here on"
        )
        _count("refused")
        self._cache[self._key] = self._fn
        return self._fn(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._fn, name)


class FirstCall:
    """A jitted program (``cache[key]``, just built, its kind first in
    ``key``) as its builder hands it to the caller that saw the miss: the call
    through it is the program's first and runs inside the span
    ``areal.program.build``. It loads the program's executable from ``store``
    (under the name that ``described`` and the arguments give) or traces,
    lowers and compiles it, once and explicitly, and writes it there; the
    cache then keeps the loaded executable (``BuiltProgram``). Called with
    abstract arguments (``jax.ShapeDtypeStruct`` with the live arrays'
    shardings: ``precompile``) it does all of that but the call. Everything
    else (``lower``) is the jitted function's own."""

    __slots__ = ("_fn", "_cache", "_key", "_store", "_described", "__weakref__")  # jax keys its caches by weak references to callables

    def __init__(self, cache: dict, key: tuple, store: ProgramStore | None = None, described: str | None = None):
        self._fn, self._cache, self._key, self._described = cache[key], cache, key, described
        self._store = ProgramStore(None) if store is None else store

    def __call__(self, *args, **kwargs):
        import jax

        # no helper between here and ``trace``: jax lowers every op's location
        # from the Python stack, and one frame more between the caller and the
        # traced function cost a prefill program 0.2 s of lowering (PERF.md, PR 42)
        key, fn, store = self._key, self._fn, self._store
        program = str(key[0])
        with perf_tracer.trace_scope(
            "areal.program.build", perf_tracer.Category.INSTR, {"program": program, "key": repr(key[1:])}
        ) as span:
            leaves = jax.tree.leaves((args, kwargs))
            if any(isinstance(x, jax.core.Tracer) for x in leaves):  # inside a transformation: jit's own call
                span.set(served="jit")
                return fn(*args, **kwargs)
            on = store.directory is not None
            if on:
                _say_when_settled(False)
            entry = store.entry(self._described, args, kwargs) if on else None
            compiled = store.load(entry, program) if entry else None
            if compiled is not None:
                served = "store"
                _count("hits")
            else:
                traced = fn.trace(*args, **kwargs)
                compiled = traced.lower().compile()
                served = "jit"
                if on:
                    served = self._refused(entry, traced.jaxpr.consts) or served
            span.set(served=served)
            with _stats_lock:
                _STORE_STATS["built"] += 1
            built = self._cache[key] = BuiltProgram(compiled, fn, self._cache, key)
            out = None if any(isinstance(x, jax.ShapeDtypeStruct) for x in leaves) else built(*args, **kwargs)
            if served == "jit" and entry:
                store.save(entry, compiled, program)
            if on:
                _say_when_settled(True)
            return out

    def _refused(self, entry: str | None, consts) -> str | None:
        """``refused`` (counted, one WARNING) where the store may not hold the
        program just built; else None, and the miss is counted."""
        if entry is None:
            why = "what its builder closes over, or an argument, has no process-independent description"
        else:
            why = constants_not_in_the_key(consts)
        if why is None:
            _count("misses")
            return None
        _count("refused")
        logger.warning(f"program store: {self._key} is not stored: {why}")
        return "refused"

    def __getattr__(self, name):
        return getattr(self._fn, name)


def compile_stats() -> dict:
    """Process-lifetime compile counters (also mirrored in the metric
    registry): compiles, total compile seconds, persistent-cache hits."""
    with _stats_lock:
        return dict(_COMPILE_STATS)
