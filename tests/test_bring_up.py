"""What the bring-up on the installed jax changed, held on the CPU: where
the compile cache goes, which peaks a device resolves to, that a benchmark
or a smoke run without a chip fails instead of reporting CPU numbers."""

import os
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
    assert "os.environ" not in inspect.getsource(compile_cache)


# -- chip peaks (observability/hw_accounting.py) -----------------------------


def test_v5e_device_kind_resolves_from_the_table():
    from areal_tpu.observability import hw_accounting as hw

    v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert hw.resolve_chip_peaks(v5e) == (197e12, 819e9, "spec")


def test_unknown_tpu_device_kind_is_an_error():
    from areal_tpu.observability import hw_accounting as hw

    with pytest.raises(ValueError, match="unknown TPU device_kind"):
        hw.resolve_chip_peaks(SimpleNamespace(platform="tpu", device_kind="TPU v9 mega"))


def test_cpu_backend_measures_its_host():
    from areal_tpu.observability import hw_accounting as hw

    flops, membw, source = hw.resolve_chip_peaks(jax.devices()[0])
    assert source == "calibrated" and flops > 0 and membw > 0


# -- no chip, no number ---------------------------------------------------------


@pytest.mark.parametrize("smoke", [False, True])
def test_bench_without_a_chip_reports_nothing(monkeypatch, capsys, tmp_path, smoke):
    import bench

    if smoke:
        monkeypatch.setenv("BENCH_SMOKE", "1")
    else:
        monkeypatch.delenv("BENCH_SMOKE", raising=False)
    monkeypatch.setattr(bench, "_PHASE_CACHE_DIR", str(tmp_path))
    spawned = []

    def fake_spawn(name, deadline=None):
        spawned.append(name)
        if name == "probe":
            return {"phase": "probe", "platform": "cpu", "n_devices": 1, "warm": True}
        return {"phase": name, "error": "not run in this test"}

    monkeypatch.setattr(bench, "_spawn_phase", fake_spawn)
    if smoke:
        bench.main()  # the CPU walk-through still runs its phases
        assert "decode" in spawned
        return
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code not in (0, None)
    assert spawned == ["probe"]  # no phase ran ...
    assert capsys.readouterr().out.strip() == ""  # ... and no number came out


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
