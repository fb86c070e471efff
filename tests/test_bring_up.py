"""What the bring-up on the installed jax changed, held on the CPU: where
the compile cache goes, which peaks a device resolves to, that a benchmark
or a smoke run without a chip fails instead of reporting CPU numbers."""

import os
import re
import subprocess
import sys
from types import SimpleNamespace

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- compile cache placement (utils/compile_cache.py) -----------------------


@pytest.fixture()
def cache_config():
    """Restore jax's cache settings: a test that leaves the persistent
    cache on would have every later CPU test write into it."""
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


@pytest.mark.parametrize(
    "backend,preset,want",
    [
        # where JAX_COMPILATION_CACHE_DIR is set jax already uses it: untouched
        ("tpu", "/somewhere/jax-cache", "/somewhere/jax-cache"),
        # unset: the fixed <checkout>/.jax_cache
        ("tpu", None, os.path.join(REPO, ".jax_cache")),
        # never on the CPU backend, whatever is configured
        ("cpu", None, None),
        ("cpu", "/somewhere/jax-cache", None),
    ],
)
def test_compile_cache_placement(cache_config, monkeypatch, backend, preset, want):
    from areal_tpu.utils import compile_cache

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    jax.config.update("jax_compilation_cache_dir", preset)
    assert compile_cache.enable_persistent_cache() == want
    # the CPU backend leaves jax's setting alone as well
    assert jax.config.jax_compilation_cache_dir == (want if backend == "tpu" else preset)


def test_compile_cache_has_no_private_knob():
    """No argument and no environment variable of the repo's own can move
    the cache: the path is part of the cache key."""
    import inspect

    from areal_tpu.utils import compile_cache

    assert not inspect.signature(compile_cache.enable_persistent_cache).parameters
    assert not inspect.signature(compile_cache.default_store).parameters  # nor the program store beside it
    # the environment is read for what a program is a function of (the store's key), and for nothing else
    src = inspect.getsource(compile_cache)
    assert re.findall(r"os\.environ[^ ]*", src) == ['os.environ.get("XLA_FLAGS"),', 'os.environ.get("LIBTPU_INIT_ARGS"),\n']


# -- chip peaks (observability/hw_accounting.py) -----------------------------


def test_v5e_device_kind_resolves_from_the_table():
    from areal_tpu.observability import hw_accounting as hw

    v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert hw.resolve_chip_peaks(v5e) == (197e12, 819e9, "spec")


def test_unknown_tpu_device_kind_is_an_error():
    from areal_tpu.observability import hw_accounting as hw

    with pytest.raises(ValueError, match="unknown TPU device_kind"):
        hw.resolve_chip_peaks(SimpleNamespace(platform="tpu", device_kind="TPU v9 mega"))


def test_cpu_backend_has_no_peak():
    """A CPU has no peak to take a share of: nothing is measured in its
    place, and the rehearsal's device phase prints nulls."""
    from areal_tpu.observability import hw_accounting as hw

    assert hw.resolve_chip_peaks(jax.devices()[0]) == (None, None, "none")
    assert hw.resolve_chip_peaks() == (None, None, "none")
    assert not hasattr(hw, "calibrate_" "host_peaks")  # in two parts: a search for the name finds records only


def test_hw_accounting_keeps_the_train_and_ledger_half():
    """What the train engine's MFU line and both engines' HBM ledgers use
    stays; the decode-side op/byte model and the host calibration went with
    the host-clock roofline they fed (PERF.md section 3 has the real one)."""
    from areal_tpu.observability import hw_accounting as hw

    for name in (
        "train_step_flops", "transformer_param_counts", "chip_peak_flops", "chip_peak_membw", "chip_hbm_bytes",
        "resolve_chip_peaks", "tree_bytes", "build_hbm_ledger", "step_transient_bytes",
    ):
        assert callable(getattr(hw, name)), name
    for name in ("decode_step_" "costs", "prefill_costs", "calibrate_" "host_peaks", "decode_device_attribution"):
        assert not hasattr(hw, name), name


def test_decode_programs_are_built_with_jax_jit_and_the_probe_knows_no_peak():
    """``decode_programs.py`` hands its program builders' functions straight to
    ``jax.jit``; ``kernel_probe.py`` times phases and imports no peak table."""
    import ast
    import inspect

    from areal_tpu.inference import decode_programs
    from areal_tpu.observability import kernel_probe

    imported = {
        alias.name
        for node in ast.walk(ast.parse(inspect.getsource(kernel_probe)))
        if isinstance(node, (ast.Import, ast.ImportFrom))
        for alias in node.names
    }
    assert "hw_accounting" not in imported and not hasattr(kernel_probe, "hw")
    assert sorted(n for n in vars(kernel_probe) if n[0].isupper() and n != "Any") == [
        "DECODE_PHASES", "DEFAULT_RECENT_STEPS", "DecodeStepTimeline", "Iterator", "KernelProbe",
    ]
    jitted = {}
    for node in ast.walk(ast.parse(inspect.getsource(decode_programs))):
        if isinstance(node, ast.FunctionDef) and node.name.endswith("_fn"):
            for call in ast.walk(node):
                if isinstance(call, ast.Call) and ast.unparse(call.func) == "jax.jit":
                    jitted[node.name] = ast.unparse(call.args[0])
    assert jitted == {
        "prefill_fn": "prefill", "prefill_paged_fn": "prefill", "chunk_fn": "chunk", "spec_fn": "spec",
        "update_fn": "apply", "clamp_fn": "clamp", "pagecopy_fn": "paged_kv.copy_pages",
        "vision_fn": "lambda vp, x, m, p: vis.vision_forward(vp, vcfg, x, m, p)",
    }
    assert inspect.signature(kernel_probe.KernelProbe.complete_step).parameters.keys() == {"self", "tl", "tokens"}


def _run_chip_smoke(*argv, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *argv],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )


def test_chip_smoke_fails_at_once_without_a_chip():
    p = _run_chip_smoke(timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""  # no result, not even a device line


def test_chip_smoke_device_phase_prints_no_peak_on_a_cpu():
    import json

    p = _run_chip_smoke("--size", "tiny", "--phases", "device", timeout=120)
    lines = [json.loads(ln) for ln in p.stdout.splitlines() if ln.startswith("{")]
    dev = lines[0]
    assert dev["phase"] == "device" and dev["platform"] == "cpu"
    assert (dev["peak_tflops"], dev["peak_membw_gbps"], dev["peaks_source"]) == (None, None, "none")
    assert p.returncode != 0 and lines[-1]["ok"] is False  # a rehearsal never says ok


@pytest.mark.slow  # ~1 min: every phase at toy widths on the CPU backend
def test_chip_smoke_tiny_walks_every_phase_and_never_says_ok():
    import json

    p = _run_chip_smoke("--size", "tiny", timeout=900)
    lines = [json.loads(ln) for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert [ln.get("phase") for ln in lines[:-1]] == [
        "device", "kernels", "serve", "train", "rl_loop",
    ], p.stderr[-2000:]
    assert p.returncode != 0
    assert lines[-1]["ok"] is False and '"ok": true' not in p.stdout
