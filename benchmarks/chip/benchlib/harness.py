"""What every cell kind shares: the device check, the compile cache, the
window's tracing, percentiles and the result line."""

from __future__ import annotations

import os
import shutil
import sys
import time


def log(msg: str) -> None:
    """An earlier line of the run (never the last line of stdout)."""
    print(f"[bench] {msg}", flush=True)


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile of a non-empty list (q in 0..100)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    k = (len(xs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def setup_compile_cache(root: str) -> str | None:
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` or the fixed
    ``<checkout>/.jax_cache`` (the path is part of the key). TPU only: a CPU
    run must not write entries a chip run would then fail to read."""
    import jax

    if jax.default_backend() != "tpu":
        return None
    if jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir", os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


def devices_for(chips: int, rehearsal: bool):
    """The cell's devices, or SystemExit(2) with no result line where JAX
    finds no TPU or fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    if not rehearsal and devs[0].platform != "tpu":
        print(f"no accelerator: jax reports platform {devs[0].platform!r}", file=sys.stderr)
        raise SystemExit(2)
    if len(devs) < chips:
        print(f"cell needs {chips} chip(s), jax reports {len(devs)}", file=sys.stderr)
        raise SystemExit(2)
    return devs[:chips]


def device_report(devs) -> dict:
    peak = 0
    for d in devs:
        s = d.memory_stats() or {}
        peak = max(peak, int(s.get("peak_bytes_in_use", 0)))
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": peak,
    }


def model_config(cfg: dict, dtype: str):
    """The program's ModelConfig for a configuration file: its published
    keys plus the sizes listed under ``assumed``."""
    from areal_tpu.models import qwen

    notes = ("source", "reduced", "reduced_from", "assumed", "stands_for")
    hf = {k: v for k, v in cfg.items() if k not in notes}
    hf.update(cfg.get("assumed", {}))
    return qwen.ModelConfig(**{**qwen.ModelConfig.from_hf_dict(hf).__dict__, "dtype": dtype})


def attach_trace(out: dict, trace) -> dict:
    """busy_s, window_s and the breakdown of a traced run's result."""
    if trace is not None:
        from benchlib import trace_reduce

        summ = trace_reduce.summary(trace)
        out["device"].update(busy_s=summ["busy_s"], window_s=summ["window_s"])
        out["breakdown"] = summ["breakdown"]
    return out


def scratch_dir(root: str, workload: str) -> str:
    """A fixed directory inside the checkout for this cell's run files."""
    d = os.path.join(root, ".bench_tmp", workload)
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d, exist_ok=True)
    return d


class Tracer:
    """The JAX profiler over a part of the window, python tracing off (it
    slows the host and bloats the file); host TraceMe events stay on so that
    idle gaps can be named."""

    def __init__(self, out_dir: str):
        self.dir = os.path.join(out_dir, "trace")
        self.t_start = self.t_stop = None

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t_start = time.monotonic()

    def stop(self):
        import jax

        from benchlib import trace_reduce

        self.t_stop = time.monotonic()
        jax.profiler.stop_trace()
        return trace_reduce.load(trace_reduce.find_xplane(self.dir))


def compile_counts() -> dict:
    """Compilations and persistent-cache hits so far in this process."""
    from areal_tpu.utils.compile_cache import compile_stats, install_compile_counters

    install_compile_counters()
    return compile_stats()
