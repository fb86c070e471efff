"""Decode-step observatory (observability/kernel_probe.py): the per-pass
phase timeline must obey the exact-sum identity contract (PRs 7/9: named
phases + other_s == step wall) through the REAL engine, including
radix-hit admission and a hold-fence window."""

import time

import numpy as np
import pytest

from areal_tpu.observability.kernel_probe import DecodeStepTimeline, KernelProbe


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


def test_probe_complete_step_identity_and_stats():
    probe = KernelProbe()
    tl = probe.begin_step()
    with tl.phase("dispatch"):
        time.sleep(0.002)
    probe.complete_step(tl, tokens=8)
    aband = probe.begin_step()
    probe.abandon_step(aband)
    st = probe.stats()
    assert st["steps"] == 1
    assert st["abandoned"] == 1
    rec = probe.recent()[0]
    assert _identity_residual(rec["breakdown"]) < 1e-12
    assert rec["tokens"] == 8
    assert st["dominant_phase"] == "dispatch"
    assert "tok_s" not in st  # a speed is not the probe's to guess: /statusz row_steps


# ---------------------------------------------------------------------------
# identity through the REAL engine (radix hit + hold fence)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_engine_phase_identity_radix_hit_and_hold_fence():
    """Serve through a live DecodeEngine with a small page size so a
    repeated prompt radix-hits at admission, and a hold-fence window in
    the middle: every RECORDED step must obey the exact-sum identity, the
    and the fence passes must be abandoned (a fence stall is not a decode
    step)."""
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
        # the engine surfaces the same stats through its public accessor
        # (what /statusz serves as the "kernels" section)
        ks = eng.kernel_stats()
        assert ks["steps"] == st["steps"]
        # the chunk's interior is measured from the device trace by scope
        # name (benchmarks/chip), no longer guessed from an analytic model
        assert "device_attribution" not in ks
    finally:
        eng.stop()
