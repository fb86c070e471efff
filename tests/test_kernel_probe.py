"""Kernel observatory (observability/kernel_probe.py + tools/microbench.py):
the per-decode-step phase timeline must obey the exact-sum identity contract
(PRs 7/9: named phases + other_s == step wall) through the REAL engine —
including radix-hit admission and a hold-fence window — the AOT cost harvest
must fall back to the analytic model when a backend declines cost_analysis,
and the microbench compare gate must flag regressions without failing on
renames."""

import time

import numpy as np
import pytest

from areal_tpu.observability import kernel_probe
from areal_tpu.observability.kernel_probe import (
    DECODE_PHASES,
    DecodeStepTimeline,
    KernelProbe,
    ProbedFn,
    cost_from_analysis,
    roofline_fraction,
)


def _identity_residual(bd: dict) -> float:
    # generic over ad-hoc phases: every *_s key except the residual/total
    named = sum(
        v
        for k, v in bd.items()
        if k.endswith("_s") and k not in ("other_s", "total_s")
    )
    return abs(named + bd["other_s"] - bd["total_s"])


# ---------------------------------------------------------------------------
# timeline unit contract
# ---------------------------------------------------------------------------


def test_timeline_identity_exact():
    tl = DecodeStepTimeline()
    with tl.phase("admission"):
        time.sleep(0.002)
    with tl.phase("dispatch"):
        time.sleep(0.004)
    time.sleep(0.002)  # unattributed -> other_s
    bd = tl.breakdown()
    assert _identity_residual(bd) < 1e-12
    assert bd["admission_s"] >= 0.002
    assert bd["dispatch_s"] >= 0.004
    assert bd["other_s"] >= 0.002
    assert bd["total_s"] >= bd["admission_s"] + bd["dispatch_s"]


def test_timeline_exclusive_nesting():
    """Entering an inner phase PAUSES the outer one: each wall-clock moment
    is credited to exactly one phase, which is what makes the exact-sum
    identity possible (an inclusive outer span would double-count)."""
    # margins sized so single-core scheduler jitter (~ms per sleep return)
    # cannot push the exclusive outer span past the inclusive threshold
    tl = DecodeStepTimeline()
    with tl.phase("admission"):
        time.sleep(0.02)
        with tl.phase("radix_match"):
            time.sleep(0.06)
        time.sleep(0.02)
    bd = tl.breakdown()
    assert _identity_residual(bd) < 1e-12
    # inner time must NOT be credited to the outer phase
    assert bd["radix_match_s"] >= 0.06
    assert bd["admission_s"] >= 0.04
    assert bd["admission_s"] < 0.06  # would be >= 0.10 if inclusive


def test_timeline_adhoc_phase_carried():
    """An ad-hoc phase a caller adds is carried through breakdown() rather
    than silently dropped — dropping one would break the identity."""
    tl = DecodeStepTimeline()
    with tl.phase("weird_extra"):
        time.sleep(0.001)
    bd = tl.breakdown()
    assert bd["weird_extra_s"] >= 0.001
    assert _identity_residual(bd) < 1e-12


# ---------------------------------------------------------------------------
# cost extraction + roofline math
# ---------------------------------------------------------------------------


def test_cost_from_analysis_shapes():
    # plain dict (current jax)
    assert cost_from_analysis({"flops": 10.0, "bytes accessed": 20.0}) == (
        10.0,
        20.0,
    )
    # list-of-dicts (older jax): first computation wins
    assert cost_from_analysis([{"flops": 5.0}]) == (5.0, 0.0)
    # backend declined in every shape it has declined in
    assert cost_from_analysis(None) is None
    assert cost_from_analysis([]) is None
    assert cost_from_analysis("nope") is None
    assert cost_from_analysis({"flops": 0.0}) is None
    assert cost_from_analysis({"flops": "garbage"}) is None


def test_roofline_fraction_math():
    # compute-bound: intensity 100 F/B * 10 B/s membw > 100 F/s peak
    f = roofline_fraction(100.0, 1.0, 2.0, peak_flops=100.0, peak_membw=10.0)
    assert f == pytest.approx((100.0 / 2.0) / 100.0)
    # memory-bound: intensity 0.1 F/B caps attainable at 0.1*1000 = 100
    f = roofline_fraction(
        100.0, 1000.0, 1.0, peak_flops=1e6, peak_membw=1000.0
    )
    assert f == pytest.approx(100.0 / 100.0)
    # never fabricated
    assert roofline_fraction(0.0, 1.0, 1.0, 100.0, 100.0) is None
    assert roofline_fraction(100.0, 1.0, 0.0, 100.0, 100.0) is None
    assert roofline_fraction(100.0, 1.0, 1.0, None, 100.0) is None
    # capped at 1.0, and n_chips scales the ceiling
    assert roofline_fraction(1e9, 0.0, 1e-9, 100.0, None) == 1.0
    one = roofline_fraction(100.0, 0.0, 1.0, 100.0, None, n_chips=1)
    four = roofline_fraction(100.0, 0.0, 1.0, 100.0, None, n_chips=4)
    assert four == pytest.approx(one / 4.0)


# ---------------------------------------------------------------------------
# AOT cost harvest: backend-absent fallback
# ---------------------------------------------------------------------------


class _FakeCompiled:
    def __init__(self, ca, result):
        self._ca = ca
        self._result = result

    def cost_analysis(self):
        if isinstance(self._ca, Exception):
            raise self._ca
        return self._ca

    def __call__(self, *a, **k):
        return self._result


class _FakeLowered:
    def __init__(self, compiled):
        self._compiled = compiled

    def compile(self):
        return self._compiled


class _FakeJitted:
    """Mimics a jitted callable's AOT surface (lower().compile()) with a
    controllable cost_analysis — the CPU backend on this image actually
    RETURNS costs (source 'device'), so the backend-absent path can only
    be exercised with a fake."""

    def __init__(self, ca, result=42):
        self._compiled = _FakeCompiled(ca, result)

    def lower(self, *a, **k):
        return _FakeLowered(self._compiled)

    def __call__(self, *a, **k):
        return self._compiled(*a, **k)


def _probe():
    return KernelProbe(model_cfg=None, calibrate=False)


def test_probed_fn_backend_absent_falls_back_to_analytic():
    probe = _probe()
    pf = ProbedFn(
        _FakeJitted(ca=None), probe, ("chunk", 8), analytic=(123.0, 456.0)
    )
    assert pf(1) == 42
    cost = probe.cost_for(("chunk", 8))
    assert cost == {"flops": 123.0, "bytes": 456.0, "source": "analytic"}


def test_probed_fn_cost_analysis_raise_falls_back_to_analytic():
    probe = _probe()
    pf = ProbedFn(
        _FakeJitted(ca=NotImplementedError("no costs here")),
        probe,
        ("prefill", 1, 64),
        analytic=(7.0, 9.0),
    )
    assert pf() == 42
    assert probe.cost_for(("prefill", 1, 64))["source"] == "analytic"


def test_probed_fn_backend_costs_win_over_analytic():
    probe = _probe()
    pf = ProbedFn(
        _FakeJitted(ca={"flops": 1000.0, "bytes accessed": 2000.0}),
        probe,
        ("chunk", 4),
        analytic=(1.0, 2.0),
    )
    pf()
    cost = probe.cost_for(("chunk", 4))
    assert cost == {"flops": 1000.0, "bytes": 2000.0, "source": "device"}


def test_probe_complete_step_identity_and_stats():
    probe = _probe()
    probe.record_cost(("chunk", 8), 1e6, 2e6, "device")
    tl = probe.begin_step()
    with tl.phase("dispatch"):
        time.sleep(0.002)
    probe.complete_step(tl, tokens=8, cost_key=("chunk", 8))
    aband = probe.begin_step()
    probe.abandon_step(aband)
    st = probe.stats()
    assert st["steps"] == 1
    assert st["abandoned"] == 1
    rec = probe.recent()[0]
    assert _identity_residual(rec["breakdown"]) < 1e-12
    assert rec["flops"] == 1e6
    assert st["dominant_phase"] == "dispatch"
    assert st["tok_s"] > 0


# ---------------------------------------------------------------------------
# identity through the REAL engine (radix hit + hold fence)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_engine_phase_identity_radix_hit_and_hold_fence():
    """Serve through a live DecodeEngine with a small page size so a
    repeated prompt radix-hits at admission, and a hold-fence window in
    the middle: every RECORDED step must obey the exact-sum identity, the
    fence passes must be abandoned (a fence stall is not a decode step),
    and the steady-state roofline must be non-null on CPU (calibrated
    peak fallback)."""
    import jax

    from areal_tpu.api.config import MeshConfig, ServerConfig
    from areal_tpu.api.io_struct import GenerationHyperparameters, ModelRequest
    from areal_tpu.inference.decode_engine import DecodeEngine
    from areal_tpu.models import qwen
    from tpu_testing import TINY_QWEN2

    params = qwen.init_params(jax.random.PRNGKey(0), TINY_QWEN2)
    cfg = ServerConfig(
        max_batch_size=4,
        max_seq_len=256,
        decode_steps_per_call=4,
        page_size=16,  # a 40-token prompt spans 2 publishable pages
        seed=0,
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
    )
    eng = DecodeEngine(cfg, params=params, model_cfg=TINY_QWEN2)
    eng.initialize()
    eng.start()
    try:
        rng = np.random.default_rng(0)
        prompt = rng.integers(1, 200, 40).tolist()
        gc = GenerationHyperparameters(max_new_tokens=8, greedy=True)
        eng.generate_sync(ModelRequest(input_ids=prompt, gconfig=gc), timeout=120)
        # same prompt again: admission walks the radix tree and reuses the
        # two published pages (the page holding token plen-1 is never
        # matched by design — prompts must span > 1 full page to hit)
        eng.generate_sync(ModelRequest(input_ids=prompt, gconfig=gc), timeout=120)
        assert eng.stats["prefix_cache_hits"] >= 1, eng.stats

        # hold-fence window: loop passes during the fence are stalls, not
        # decode steps — they must be abandoned, never recorded
        abandoned_before = eng.kprobe.stats()["abandoned"]
        eng.pause_generation(mode="hold")
        assert eng.wait_fence_ack(10.0)
        time.sleep(0.2)
        eng.continue_generation()
        eng.generate_sync(ModelRequest(input_ids=prompt, gconfig=gc), timeout=120)
        assert eng.kprobe.stats()["abandoned"] > abandoned_before

        recs = eng.kprobe.recent()
        assert recs, "no decode steps recorded"
        for rec in recs:
            assert _identity_residual(rec["breakdown"]) < 1e-9
        st = eng.kprobe.stats()
        # radix_match was actually timed on the warm admissions
        assert "radix_match" in st["phase_means_s"]
        # roofline non-null on CPU via the calibrated-peak fallback
        assert st["roofline_fraction"] is not None
        assert 0.0 < st["roofline_fraction"] <= 1.0
        # chunk costs were harvested (device cost_analysis or analytic)
        assert any(k.startswith("chunk|") for k in st["costs"]), st["costs"]
        assert st["tok_s"] > 0
        # the engine surfaces the same stats through its public accessor
        # (what /statusz serves as the "kernels" section)
        ks = eng.kernel_stats()
        assert ks["steps"] == st["steps"]
        # the chunk's interior is measured from the device trace by scope
        # name (benchmarks/chip), no longer guessed from an analytic model
        assert "device_attribution" not in ks
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# microbench compare matrix
# ---------------------------------------------------------------------------


def _result(**benches):
    return {
        "schema": 1,
        "benches": {
            name: {"wall_s": wall, "noise_frac": noise}
            for name, (wall, noise) in benches.items()
        },
    }


def test_compare_matrix():
    from areal_tpu.tools import microbench as mb

    base = _result(a=(0.010, 0.02), b=(0.005, 0.02), c=(0.020, 0.02))

    # regression: 2x on one bench flags exactly that bench
    cur = _result(a=(0.020, 0.02), b=(0.005, 0.02), c=(0.020, 0.02))
    r = mb.compare(cur, base)
    assert [x["bench"] for x in r["regressions"]] == ["a"]
    assert sorted(r["ok"]) == ["b", "c"]

    # within-noise: +10% everywhere is silent
    cur = _result(a=(0.011, 0.02), b=(0.0055, 0.02), c=(0.022, 0.02))
    r = mb.compare(cur, base)
    assert not r["regressions"]

    # a jumpy bench widens its own margin: 80% slower but noise 0.5 on the
    # baseline run -> margin max(threshold, 2*0.5) = 100% -> silent
    jumpy_base = _result(a=(0.010, 0.5))
    r = mb.compare(_result(a=(0.018, 0.02)), jumpy_base)
    assert not r["regressions"]

    # new entry: warning, never a failure
    cur = _result(a=(0.010, 0.02), b=(0.005, 0.02), c=(0.020, 0.02), d=(0.001, 0.0))
    r = mb.compare(cur, base)
    assert r["new"] == ["d"] and not r["regressions"]

    # missing entry: warning, never a failure
    cur = _result(a=(0.010, 0.02))
    r = mb.compare(cur, base)
    assert sorted(r["missing"]) == ["b", "c"] and not r["regressions"]

    # self-compare is exactly silent
    r = mb.compare(base, base)
    assert not r["regressions"] and not r["new"] and not r["missing"]


def test_fast_benches_registered():
    """The committed CPU baseline's bench set is a stable contract: the
    hot-path benches from docs/perf.md must stay registered as the fast
    (non-heavy) set — including the suffix-attention kernel-path twins
    of suffix_prefill/spec_decode_step."""
    from areal_tpu.tools import microbench as mb

    assert set(mb.fast_names()) == {
        "paged_decode_step",
        "paged_attention_interpret",
        "suffix_prefill",
        "suffix_prefill_kernel",
        "int8_kv_dequant",
        "tree_verify_forward",
        "spec_decode_step",
        "spec_decode_step_kernel",
        "radix_match",
        "weight_stage_encode",
    }
    heavy = {n for n, s in mb.REGISTRY.items() if s["heavy"]}
    assert heavy == {
        "decode_engine_steady",
        "train_step",
        "tree_train",
        "weight_update",
    }
