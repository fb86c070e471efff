"""Goodput autopilot (areal_tpu/autopilot/, docs/autopilot.md).

Controller math in isolation — table-driven decide() coverage for
hysteresis bands, AIMD step sizes, cooldowns, min/max clamps, and the
stale-signal hold-position degradation (mirroring the PR 12 round-robin
fallback) — no fleet required. Plus the actuation surfaces: the
StalenessManager hook, the gateway headroom hook, the engine knob apply
(incl. live radix-cap shrink), the authenticated HTTP endpoint, and one
fake-fleet Autopilot.tick integration with the flight-ring audit.
"""

import json
import math
import time
import urllib.request

import pytest

from areal_tpu.api.config import (
    AdmissionControllerConfig,
    AutopilotConfig,
    CacheControllerConfig,
    FleetControllerConfig,
    InferenceEngineConfig,
    StalenessControllerConfig,
)
from areal_tpu.autopilot import (
    AdmissionController,
    Autopilot,
    CacheController,
    FleetController,
    StalenessController,
    autopilot_from_config,
)
from areal_tpu.autopilot import signals as sig_mod
from areal_tpu.autopilot.signals import RateTracker, ReplicaView, Signals
from areal_tpu.observability.timeline import FlightRecorder
from areal_tpu.routing.snapshot import ReplicaSnapshot


def _sig(now=100.0, **kw) -> Signals:
    return Signals(now=now, **kw)


# ---------------------------------------------------------------------------
# staleness controller
# ---------------------------------------------------------------------------


def _staleness(bound=2, **kw):
    cfg = StalenessControllerConfig(**kw)
    return StalenessController(cfg, initial=bound)


class TestStalenessController:
    @pytest.mark.parametrize(
        "bubble,span,bound,expect_new,reason",
        [
            # starved trainer grows the bound
            (0.40, None, 2, 3, "trainer_starved"),
            (0.25, None, 2, 3, "trainer_starved"),  # at-threshold grows
            # low bubble + wide span shrinks
            (0.02, 2.0, 2, 1, "low_bubble_wide_span"),
            (0.05, 1.0, 2, 1, "low_bubble_wide_span"),  # at both thresholds
            # hysteresis dead band: between thresholds nothing happens
            (0.15, 5.0, 2, None, None),
            # low bubble but NARROW span: the wide bound is harmless
            (0.01, 0.5, 2, None, None),
        ],
    )
    def test_decision_table(self, bubble, span, bound, expect_new, reason):
        ctrl = _staleness(bound=bound)
        acts = ctrl.decide(_sig(bubble_fraction=bubble, version_span_p99=span))
        if expect_new is None:
            assert acts == []
            assert ctrl.bound == bound
        else:
            assert len(acts) == 1
            assert acts[0].knob == "max_staleness"
            assert (acts[0].old, acts[0].new) == (bound, expect_new)
            assert acts[0].reason == reason

    def test_clamps_at_min_and_max(self):
        hi = _staleness(bound=3, max_staleness=3)
        assert hi.decide(_sig(bubble_fraction=0.9)) == []
        lo = _staleness(bound=0, min_staleness=0)
        assert lo.decide(_sig(bubble_fraction=0.0, version_span_p99=9.0)) == []

    def test_cooldown_blocks_consecutive_actions(self):
        ctrl = _staleness(bound=1, cooldown_s=30.0)
        assert len(ctrl.decide(_sig(now=100.0, bubble_fraction=0.9))) == 1
        assert ctrl.decide(_sig(now=110.0, bubble_fraction=0.9)) == []
        assert len(ctrl.decide(_sig(now=131.0, bubble_fraction=0.9))) == 1
        assert ctrl.bound == 3

    def test_missing_bubble_holds_position(self):
        ctrl = _staleness(bound=2)
        assert ctrl.decide(_sig(bubble_fraction=None)) == []
        assert ctrl.last_hold == "bubble_fraction"
        # shrink path additionally needs span evidence
        assert ctrl.decide(_sig(bubble_fraction=0.0, version_span_p99=None)) == []
        assert ctrl.last_hold == "version_span_p99"


# ---------------------------------------------------------------------------
# admission controller
# ---------------------------------------------------------------------------


def _admission(depth=32, pages=16, headroom=4, **kw):
    cfg = AdmissionControllerConfig(**kw)
    return AdmissionController(
        cfg, queue_depth=depth, min_free_pages=pages, headroom=headroom
    )


class TestAdmissionController:
    def test_multiplicative_decrease_on_high_queue_wait(self):
        ctrl = _admission(depth=32)
        acts = ctrl.decide(
            _sig(queue_wait_p99_s=8.0, shed_rate_per_s=0.0, reap_rate_per_s=0.0)
        )
        depth_acts = [a for a in acts if a.knob == "max_queue_depth"]
        assert len(depth_acts) == 1
        assert depth_acts[0].new == 16  # 32 * 0.5
        assert depth_acts[0].reason == "queue_wait_high"

    def test_additive_increase_on_shed_under_capacity(self):
        ctrl = _admission(depth=32)
        acts = ctrl.decide(
            _sig(queue_wait_p99_s=0.2, shed_rate_per_s=3.0, reap_rate_per_s=None)
        )
        depth_acts = [a for a in acts if a.knob == "max_queue_depth"]
        assert depth_acts[0].new == 36  # +queue_depth_step
        assert depth_acts[0].reason == "shed_under_capacity"

    def test_dead_band_holds(self):
        # queue wait between low and high thresholds: no depth action
        ctrl = _admission(depth=32)
        acts = ctrl.decide(
            _sig(queue_wait_p99_s=3.0, shed_rate_per_s=9.0, reap_rate_per_s=0.0)
        )
        assert not [a for a in acts if a.knob == "max_queue_depth"]

    def test_clamps(self):
        lo = _admission(depth=5, min_queue_depth=4)
        acts = lo.decide(
            _sig(queue_wait_p99_s=99.0, shed_rate_per_s=0.0, reap_rate_per_s=0.0)
        )
        assert [a.new for a in acts if a.knob == "max_queue_depth"] == [4]
        hi = _admission(depth=255, max_queue_depth=256)
        acts = hi.decide(
            _sig(queue_wait_p99_s=0.0, shed_rate_per_s=9.0, reap_rate_per_s=None)
        )
        assert [a.new for a in acts if a.knob == "max_queue_depth"] == [256]

    def test_min_free_pages_rises_on_reaps_and_relaxes_when_clean(self):
        ctrl = _admission(pages=16)
        acts = ctrl.decide(
            _sig(queue_wait_p99_s=3.0, shed_rate_per_s=0.0, reap_rate_per_s=2.0)
        )
        page_acts = [a for a in acts if a.knob == "min_free_pages"]
        assert page_acts[0].new == 24 and page_acts[0].reason == "deadline_reaps"
        ctrl2 = _admission(pages=16, cooldown_s=0.0)
        acts = ctrl2.decide(
            _sig(queue_wait_p99_s=3.0, shed_rate_per_s=5.0, reap_rate_per_s=0.0)
        )
        page_acts = [a for a in acts if a.knob == "min_free_pages"]
        assert page_acts[0].new == 8
        assert page_acts[0].reason == "shed_without_reaps"

    def test_headroom_widens_on_interactive_shed_and_narrows_after_quiet(self):
        ctrl = _admission(headroom=4, cooldown_s=0.0, narrow_after_quiet_rounds=3)
        acts = ctrl.decide(
            _sig(
                queue_wait_p99_s=3.0,
                shed_rate_per_s=1.0,
                interactive_shed_rate_per_s=0.5,
            )
        )
        hr = [a for a in acts if a.knob == "gateway_interactive_headroom"]
        assert hr[0].new == 6 and hr[0].reason == "interactive_shed"
        # three quiet rounds narrow it back by one step
        for i in range(2):
            acts = ctrl.decide(
                _sig(
                    now=200.0 + i,
                    queue_wait_p99_s=3.0,
                    shed_rate_per_s=0.0,
                    interactive_shed_rate_per_s=0.0,
                )
            )
            assert not [
                a for a in acts if a.knob == "gateway_interactive_headroom"
            ]
        acts = ctrl.decide(
            _sig(
                now=203.0,
                queue_wait_p99_s=3.0,
                shed_rate_per_s=0.0,
                interactive_shed_rate_per_s=0.0,
            )
        )
        hr = [a for a in acts if a.knob == "gateway_interactive_headroom"]
        assert hr[0].new == 4 and hr[0].reason == "sustained_quiet"

    def test_unmanaged_headroom_never_ratchets(self):
        """With no gateway hook wired the headroom branch is inert: no
        actions, no cooldown consumption, and the knob is absent from
        setpoints (no phantom fleet-wide value)."""
        ctrl = _admission(headroom=0, cooldown_s=0.0)
        ctrl.manage_headroom = False
        acts = ctrl.decide(
            _sig(
                queue_wait_p99_s=3.0,
                shed_rate_per_s=1.0,
                interactive_shed_rate_per_s=5.0,
            )
        )
        assert not [a for a in acts if a.knob == "gateway_interactive_headroom"]
        assert "gateway_interactive_headroom" not in ctrl.setpoints()

    def test_missing_signals_hold(self):
        ctrl = _admission()
        assert ctrl.decide(_sig(queue_wait_p99_s=None, shed_rate_per_s=1.0)) == []
        assert ctrl.last_hold == "queue_wait_p99_s"
        assert ctrl.decide(_sig(queue_wait_p99_s=1.0, shed_rate_per_s=None)) == []
        assert ctrl.last_hold == "shed_rate_per_s"

    def test_cooldown_covers_all_knobs(self):
        ctrl = _admission(depth=32, cooldown_s=10.0)
        assert ctrl.decide(
            _sig(now=100.0, queue_wait_p99_s=9.0, shed_rate_per_s=0.0)
        )
        assert (
            ctrl.decide(
                _sig(now=105.0, queue_wait_p99_s=9.0, shed_rate_per_s=0.0)
            )
            == []
        )


# ---------------------------------------------------------------------------
# cache controller
# ---------------------------------------------------------------------------


def _cache(fraction=0.5, **kw):
    return CacheController(CacheControllerConfig(**kw), initial_fraction=fraction)


class TestCacheController:
    @pytest.mark.parametrize(
        "hit,headroom,fraction,expect_new,reason",
        [
            (0.5, 0.5, 0.5, 0.55, "cache_earning"),
            (0.5, 0.03, 0.5, 0.45, "hbm_pressure"),  # pressure beats earning
            (0.0, 0.5, 0.5, 0.45, "cache_idle"),
            (0.5, 0.10, 0.5, None, None),  # headroom dead band: no grow
            (0.1, 0.5, 0.5, None, None),  # hit-rate dead band
        ],
    )
    def test_decision_table(self, hit, headroom, fraction, expect_new, reason):
        ctrl = _cache(fraction=fraction)
        acts = ctrl.decide(
            _sig(prefix_hit_rate=hit, hbm_headroom_fraction=headroom)
        )
        if expect_new is None:
            assert acts == []
        else:
            assert acts[0].new == pytest.approx(expect_new)
            assert acts[0].reason == reason

    def test_clamps(self):
        hi = _cache(fraction=0.8, max_fraction=0.8)
        assert hi.decide(
            _sig(prefix_hit_rate=0.9, hbm_headroom_fraction=0.9)
        ) == []
        lo = _cache(fraction=0.1, min_fraction=0.1)
        assert lo.decide(
            _sig(prefix_hit_rate=0.0, hbm_headroom_fraction=0.01)
        ) == []

    def test_missing_signal_holds(self):
        ctrl = _cache()
        assert ctrl.decide(_sig(prefix_hit_rate=None)) == []
        assert ctrl.last_hold == "prefix_hit_rate"
        assert (
            ctrl.decide(
                _sig(prefix_hit_rate=0.5, hbm_headroom_fraction=None)
            )
            == []
        )
        assert ctrl.last_hold == "hbm_headroom_fraction"

    def test_cooldown(self):
        ctrl = _cache(cooldown_s=20.0)
        assert ctrl.decide(
            _sig(now=50.0, prefix_hit_rate=0.9, hbm_headroom_fraction=0.9)
        )
        assert (
            ctrl.decide(
                _sig(now=60.0, prefix_hit_rate=0.9, hbm_headroom_fraction=0.9)
            )
            == []
        )


# ---------------------------------------------------------------------------
# fleet controller
# ---------------------------------------------------------------------------


def _fleet_sig(now, loads, queues, draining=(), terminal=(), **kw):
    reps = [
        ReplicaView(
            addr=f"r{i}",
            draining=(f"r{i}" in draining),
            drain_terminal=(f"r{i}" in terminal),
            load_fraction=loads[i],
            queue_depth=queues[i],
        )
        for i in range(len(loads))
    ]
    live = [r for r in reps if not r.draining]
    return _sig(
        now=now,
        replicas=reps,
        mean_load_fraction=(
            sum(r.load_fraction for r in live) / len(live) if live else None
        ),
        mean_queue_depth=(
            sum(r.queue_depth for r in live) / len(live) if live else None
        ),
        **kw,
    )


def _fleet(n=3, **kw):
    return FleetController(FleetControllerConfig(**kw), initial_replicas=n)


class TestFleetController:
    def test_drains_least_loaded_after_sustained_idle(self):
        ctrl = _fleet(sustain_rounds=3, cooldown_s=0.0)
        for i in range(2):
            assert ctrl.decide(_fleet_sig(100.0 + i, [0.1, 0.0, 0.2], [0, 0, 0])) == []
        acts = ctrl.decide(_fleet_sig(103.0, [0.1, 0.0, 0.2], [0, 0, 0]))
        assert len(acts) == 1
        assert acts[0].reason == "sustained_idle"
        assert acts[0].target == "r1"  # least loaded
        assert (acts[0].old, acts[0].new) == (3, 2)

    def test_transient_idle_does_not_drain(self):
        ctrl = _fleet(sustain_rounds=3, cooldown_s=0.0)
        ctrl.decide(_fleet_sig(100.0, [0.0, 0.0, 0.0], [0, 0, 0]))
        ctrl.decide(_fleet_sig(101.0, [0.9, 0.9, 0.9], [4, 4, 4]))  # busy blip
        assert ctrl._low_rounds == 0
        assert ctrl.decide(_fleet_sig(102.0, [0.0, 0.0, 0.0], [0, 0, 0])) == []

    def test_floor_respected(self):
        ctrl = _fleet(sustain_rounds=1, min_replicas=2, cooldown_s=0.0)
        acts = ctrl.decide(
            _fleet_sig(100.0, [0.0, 0.0, 0.0], [0, 0, 0], draining=("r2",))
        )
        # 2 live replicas already at the floor: no further drain
        assert acts == []

    def test_undrains_on_sustained_backlog(self):
        ctrl = _fleet(
            sustain_rounds=4, undrain_sustain_rounds=2, cooldown_s=0.0
        )
        sig1 = _fleet_sig(100.0, [0.9, 0.9, 0.0], [4, 5, 0], draining=("r2",))
        assert ctrl.decide(sig1) == []
        acts = ctrl.decide(
            _fleet_sig(101.0, [0.9, 0.9, 0.0], [4, 5, 0], draining=("r2",))
        )
        assert len(acts) == 1
        assert acts[0].reason == "sustained_backlog"
        assert acts[0].target == "r2"
        assert (acts[0].old, acts[0].new) == (2, 3)

    def test_undrain_skips_terminal_drains(self):
        """A preemption (terminal) drain belongs to an exiting process —
        scale-up must pick a cancellable drain or hold, never undrain a
        replica the platform is about to SIGKILL."""
        ctrl = _fleet(sustain_rounds=9, undrain_sustain_rounds=1, cooldown_s=0.0)
        sig = _fleet_sig(
            100.0,
            [0.9, 0.0, 0.0],
            [5, 0, 0],
            draining=("r1", "r2"),
            terminal=("r1",),
        )
        acts = ctrl.decide(sig)
        assert acts and acts[0].target == "r2"  # the cancellable one
        # only terminal drains available: hold, don't undrain the dying one
        ctrl2 = _fleet(sustain_rounds=9, undrain_sustain_rounds=1, cooldown_s=0.0)
        sig2 = _fleet_sig(
            100.0, [0.9, 0.0], [5, 0], draining=("r1",), terminal=("r1",)
        )
        assert ctrl2.decide(sig2) == []

    def test_undrain_bypasses_drain_cooldown(self):
        """Scale-up is the safety direction: a backlog right after a
        drain must not wait out the drain cooldown."""
        ctrl = _fleet(sustain_rounds=1, cooldown_s=60.0)
        acts = ctrl.decide(_fleet_sig(100.0, [0.0, 0.0, 0.0], [0, 0, 0]))
        assert acts and acts[0].reason == "sustained_idle"
        acts = ctrl.decide(
            _fleet_sig(101.0, [0.9, 0.9, 0.0], [5, 5, 0], draining=("r2",))
        )
        assert acts and acts[0].reason == "sustained_backlog"

    def test_ceiling_respected(self):
        ctrl = _fleet(n=2, sustain_rounds=1, cooldown_s=0.0)  # ceiling 2
        acts = ctrl.decide(
            _fleet_sig(100.0, [0.9, 0.9, 0.0], [5, 5, 0], draining=("r2",))
        )
        # 2 live already at the ceiling: the drained one stays drained
        assert acts == []

    def test_blind_round_resets_sustain_streak(self):
        ctrl = _fleet(sustain_rounds=2, cooldown_s=0.0)
        ctrl.decide(_fleet_sig(100.0, [0.0, 0.0, 0.0], [0, 0, 0]))
        assert ctrl._low_rounds == 1
        assert ctrl.decide(_sig(now=101.0)) == []  # no snapshots at all
        assert ctrl.last_hold == "fleet_snapshots"
        assert ctrl._low_rounds == 0

    def test_cooldown(self):
        ctrl = _fleet(sustain_rounds=1, cooldown_s=30.0)
        assert ctrl.decide(_fleet_sig(100.0, [0.0, 0.0, 0.0], [0, 0, 0]))
        ctrl.decide(_fleet_sig(101.0, [0.0, 0.0, 0.0], [0, 0, 0]))
        assert ctrl.decide(_fleet_sig(102.0, [0.0, 0.0, 0.0], [0, 0, 0])) == []


# ---------------------------------------------------------------------------
# signal plane
# ---------------------------------------------------------------------------


class TestSignals:
    def test_windowed_quantile_ignores_prior_lifetime(self):
        rates = RateTracker()

        def buckets(c1, cinf):
            return [
                ("areal_request_queue_wait_seconds_bucket", {"le": "1"}, c1),
                (
                    "areal_request_queue_wait_seconds_bucket",
                    {"le": "+Inf"},
                    cinf,
                ),
            ]

        s1 = sig_mod.assemble(buckets(100, 100), rates, now=1.0)
        assert s1.queue_wait_p99_s is None  # first round primes the window
        # 10 new observations, all slow (past the 1s bucket): the lifetime
        # distribution is 100 fast + 10 slow, the WINDOW is 10 slow
        s2 = sig_mod.assemble(buckets(100, 110), rates, now=2.0)
        assert s2.queue_wait_p99_s == pytest.approx(1.0)

    def test_counter_rates_and_reset_reprime(self):
        rates = RateTracker()
        shed = lambda v: [
            ("areal_gateway_shed_total", {"priority": "rollout"}, v)
        ]
        assert sig_mod.assemble(shed(5), rates, now=1.0).shed_rate_per_s is None
        assert sig_mod.assemble(
            shed(9), rates, now=3.0
        ).shed_rate_per_s == pytest.approx(2.0)
        # counter reset (restarted source) must not yield a negative rate
        assert sig_mod.assemble(shed(1), rates, now=4.0).shed_rate_per_s is None

    def test_bubble_needs_step_witness(self):
        rates = RateTracker()
        s = sig_mod.assemble(
            [("areal_train_bubble_fraction", {}, 0.4)], rates, now=1.0
        )
        assert s.bubble_fraction is None  # gauge alone: no step completed
        s = sig_mod.assemble(
            [
                ("areal_train_bubble_fraction", {}, 0.4),
                ("areal_train_step_seconds_count", {}, 3),
            ],
            rates,
            now=2.0,
        )
        assert s.bubble_fraction == pytest.approx(0.4)

    def test_headroom_derived_from_bytes_not_fraction_sum(self):
        """Headroom comes from summed BYTE gauges (meaningful on a
        fleet-merged endpoint) — never from the fraction gauge, whose
        per-replica sum inflates N-fold."""
        rates = RateTracker()
        s = sig_mod.assemble(
            [("areal_hbm_headroom_fraction", {}, 0.0)], rates, now=1.0
        )
        assert s.hbm_headroom_fraction is None  # no limit witness
        # two merged replicas: fractions would sum to 0.5 (wrong); bytes
        # give fleet in-use 1.5e9 over fleet limit 2e9 -> 0.25
        s = sig_mod.assemble(
            [
                ("areal_hbm_headroom_fraction", {}, 0.5),
                ("areal_hbm_bytes", {"component": "limit"}, 2e9),
                ("areal_hbm_bytes", {"component": "in_use"}, 1.5e9),
            ],
            rates,
            now=2.0,
        )
        assert s.hbm_headroom_fraction == pytest.approx(0.25)

    def test_empty_scrape_is_blind_not_zero(self):
        """A failed fetch must not reprime counter trackers at 0 — the
        next good scrape would fabricate the whole counter total as one
        interval's rate."""
        rates = RateTracker()
        shed = lambda v: [
            ("areal_gateway_shed_total", {"priority": "rollout"}, v)
        ]
        sig_mod.assemble(shed(5000), rates, now=1.0)
        blind = sig_mod.assemble([], rates, now=2.0)  # failed scrape
        assert blind.shed_rate_per_s is None
        after = sig_mod.assemble(shed(5002), rates, now=3.0)
        # 2 events over 2s, not 5002 events over 1s
        assert after.shed_rate_per_s == pytest.approx(1.0)

    def test_fleet_views_from_snapshots(self):
        snap = ReplicaSnapshot.from_statusz(
            "a:1",
            {
                "lifecycle": {
                    "queue_depth": 3,
                    "active_slots": 2,
                    "max_batch_size": 4,
                },
                "drain": {"draining": True},
                "stats": {"deadline_exceeded": 7, "generated_tokens": 123},
                "autopilot": {"knobs": {"max_queue_depth": 16.0}},
            },
        )
        assert snap.deadline_exceeded == 7
        assert snap.generated_tokens == 123
        assert snap.autopilot_knobs == {"max_queue_depth": 16.0}
        views = sig_mod.fleet_views({"a:1": snap})
        assert views[0].draining is True
        assert views[0].load_fraction == pytest.approx(0.5)
        assert views[0].queue_depth == 3


# ---------------------------------------------------------------------------
# actuation hooks
# ---------------------------------------------------------------------------


def test_staleness_manager_hook_retunes_capacity():
    from areal_tpu.infra.staleness_manager import StalenessManager

    class VP:
        def get_version(self):
            return 0

    sm = StalenessManager(
        VP(), max_concurrent_rollouts=64, consumer_batch_size=4, max_staleness=0
    )
    assert sm.get_capacity() == 4  # (0 + 0 + 1) * 4
    assert sm.set_max_staleness(2) == 2
    assert sm.get_capacity() == 12  # (2 + 0 + 1) * 4
    assert sm.set_max_staleness(-5) == 0  # clamped


def test_gateway_headroom_hook_clamps():
    from areal_tpu.openai.proxy.gateway import GatewayState

    gw = GatewayState(["http://b"], "k", max_inflight=8, interactive_headroom=2)
    assert gw.set_interactive_headroom(5) == 5
    assert gw.set_interactive_headroom(100) == 8  # capped at max_inflight
    assert gw.set_interactive_headroom(-3) == 0
    # shedding disabled: there is no cap to carve headroom out of
    gw_open = GatewayState(["http://b"], "k", max_inflight=0)
    assert gw_open.set_interactive_headroom(4) == 0


def test_autopilot_config_default_off_and_wiring_noop():
    assert AutopilotConfig().enabled is False
    assert InferenceEngineConfig().autopilot.enabled is False
    assert autopilot_from_config(AutopilotConfig(), lambda: []) is None
    assert autopilot_from_config(None, lambda: []) is None


# ---------------------------------------------------------------------------
# Autopilot facade integration (fake fleet; no threads)
# ---------------------------------------------------------------------------


class _FakeSource:
    def __init__(self):
        self.samples = []

    def fetch(self):
        return self.samples


def _qw(fast, slow):
    # slow observations land in (1, 10]: the windowed p99 interpolates
    # toward 10s, comfortably past the default 5s high threshold
    return [
        ("areal_request_queue_wait_seconds_bucket", {"le": "1"}, fast),
        ("areal_request_queue_wait_seconds_bucket", {"le": "10"}, fast + slow),
        ("areal_request_queue_wait_seconds_bucket", {"le": "+Inf"}, fast + slow),
    ]


def _mk_autopilot(posts, flight, addrs=("a:1", "b:2")):
    cfg = AutopilotConfig(
        enabled=True,
        interval_s=0.1,
        staleness=StalenessControllerConfig(enabled=False),
        cache=CacheControllerConfig(enabled=False),
        fleet=FleetControllerConfig(enabled=False),
        admission=AdmissionControllerConfig(cooldown_s=0.0),
    )
    src = _FakeSource()

    def post(addr, path, payload, timeout=None):
        posts.append((addr, path, dict(payload)))
        return {"status": "ok"}

    ap = Autopilot(
        cfg,
        lambda: list(addrs),
        metrics_source=src,
        post_fn=post,
        flight=flight,
    )
    ap.seed_setpoints(max_queue_depth=32)
    return ap, src


def test_autopilot_tick_applies_and_audits():
    posts, flight = [], FlightRecorder(capacity=64, role="test")
    ap, src = _mk_autopilot(posts, flight)
    src.samples = _qw(10, 0)
    assert ap.tick() == []  # priming round: windows empty -> hold
    src.samples = _qw(10, 8)  # 8 new slow waits: p99 >> high threshold
    acts = ap.tick()
    assert [a.knob for a in acts] == ["max_queue_depth"]
    assert acts[0].new == 16
    # the knob set was pushed to EVERY replica
    assert {a for a, _, _ in posts} == {"a:1", "b:2"}
    assert all(p == "/autopilot/knobs" for _, p, _ in posts)
    assert all(pl["max_queue_depth"] == 16.0 for _, _, pl in posts)
    # audited: flight ring carries the decision with signals attached
    evs = [
        e
        for e in flight.snapshot()["events"]
        if e["kind"] == "autopilot_decision"
    ]
    assert len(evs) == 1
    d = evs[0]["data"]
    assert d["controller"] == "admission" and d["knob"] == "max_queue_depth"
    assert d["old"] == 32 and d["new"] == 16
    assert d["reason"] == "queue_wait_high"
    assert d["queue_wait_p99_s"] is not None
    # status() view for bench detail.autopilot
    st = ap.status()
    assert st["decisions"] == 1
    assert st["decisions_by_reason"] == {"queue_wait_high": 1}
    assert st["setpoints"]["max_queue_depth"] == 16.0


def test_autopilot_repushes_to_failed_replica():
    posts, flight = [], FlightRecorder(capacity=64, role="test")
    ap, src = _mk_autopilot(posts, flight)
    fail = {"b:2"}
    orig_post = ap._post

    def flaky(addr, path, payload, timeout=None):
        if addr in fail:
            raise OSError("connection refused")
        return orig_post(addr, path, payload, timeout)

    ap._post = flaky
    src.samples = _qw(10, 0)
    ap.tick()
    src.samples = _qw(10, 8)
    ap.tick()
    assert {a for a, _, _ in posts} == {"a:1"}  # b failed
    # replica b recovers; the next actionable round converges it
    fail.clear()
    src.samples = _qw(10, 30)  # still slow: another decrease
    acts = ap.tick()
    assert acts and acts[0].new == 8
    assert ("b:2", "/autopilot/knobs", {"max_queue_depth": 8.0}) in [
        (a, p, {k: v for k, v in pl.items() if k == "max_queue_depth"})
        for a, p, pl in posts
    ]


def test_autopilot_signal_hold_counts():
    posts, flight = [], FlightRecorder(capacity=64, role="test")
    ap, src = _mk_autopilot(posts, flight)
    src.samples = []  # nothing measurable at all
    assert ap.tick() == []
    ctrl = ap.controllers[0]
    assert ctrl.last_hold is not None


# ---------------------------------------------------------------------------
# engine + HTTP surface (tiny real engine, one per module)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def knob_server():
    import jax

    from areal_tpu.api.config import MeshConfig, RequestLifecycleConfig, ServerConfig
    from areal_tpu.inference.decode_engine import DecodeEngine
    from areal_tpu.inference.server import ServerThread
    from areal_tpu.models import qwen

    from tpu_testing import TINY_QWEN2

    cfg = ServerConfig(
        max_batch_size=2,
        max_seq_len=128,
        page_size=16,
        decode_steps_per_call=4,
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
        autopilot_token="secret-token",
        lifecycle=RequestLifecycleConfig(max_queue_depth=32, min_free_pages=0),
    )
    params = qwen.init_params(jax.random.PRNGKey(0), TINY_QWEN2)
    eng = DecodeEngine(cfg, params=params, model_cfg=TINY_QWEN2)
    eng.initialize()
    st = ServerThread(cfg, eng)
    st.start()
    yield st
    st.stop()


def _post_knobs(addr, payload, token=None, expect=200):
    headers = {"Content-Type": "application/json"}
    if token:
        headers["x-areal-autopilot-token"] = token
    req = urllib.request.Request(
        f"http://{addr}/autopilot/knobs",
        data=json.dumps(payload).encode(),
        headers=headers,
        method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=10) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def test_knobs_endpoint_applies_and_reports(knob_server):
    st = knob_server
    status, body = _post_knobs(
        st.address,
        {"max_queue_depth": 8, "min_free_pages": 4, "radix_max_fraction": 0.25},
        token="secret-token",
    )
    assert status == 200
    assert body["knobs"]["max_queue_depth"] == 8.0
    assert body["knobs"]["min_free_pages"] == 4.0
    assert body["knobs"]["radix_max_fraction"] == 0.25
    eng = st.engine
    assert eng.config.lifecycle.max_queue_depth == 8
    assert eng.config.lifecycle.min_free_pages == 4
    assert eng.slots.radix.max_pages == int((eng.slots.pool.n_pages - 1) * 0.25)
    # the admission gate consumes the pushed value
    admit, reason, snap = eng.check_admission()
    assert admit
    # /statusz reports the applied setpoints back
    with urllib.request.urlopen(
        f"http://{st.address}/statusz", timeout=10
    ) as r:
        doc = json.loads(r.read())
    assert doc["autopilot"]["knobs"]["max_queue_depth"] == 8.0
    snap = ReplicaSnapshot.from_statusz(st.address, doc)
    assert snap.autopilot_knobs["max_queue_depth"] == 8.0


def test_knobs_endpoint_auth_and_validation(knob_server):
    st = knob_server
    status, body = _post_knobs(st.address, {"max_queue_depth": 4})
    assert status == 403  # token required when configured
    status, _ = _post_knobs(st.address, {"max_queue_depth": 4}, token="wrong")
    assert status == 403
    # unknown knobs are ignored (older server under a newer control plane)
    status, body = _post_knobs(
        st.address, {"not_a_knob": 1}, token="secret-token"
    )
    assert status == 200
    assert "not_a_knob" not in body["knobs"]


@pytest.mark.slow
def test_fleet_autopilot_acceptance():
    """ISSUE acceptance (fleet controller run): under the time-varying
    diurnal ``bench_gateway --load-profile`` on CPU, autopilot-on beats
    the static full fleet on goodput-per-replica (the trough's drained
    replicas return capacity), total goodput survives the scale-downs,
    every setpoint change is auditable in the flight ring, and the
    static arms — which ARE the ``autopilot.enabled=False`` twins — stay
    greedy byte-identical. Measured ~+20-45%% per-replica over 3 runs
    during development.

    This is a WALL-CLOCK bench (run it serially, not under a parallel
    suite): one retry absorbs a host-contention outlier — a real
    regression fails both attempts."""
    import asyncio

    from areal_tpu.tools.bench_gateway import run_autopilot_ab

    report = None
    for _attempt in range(2):
        report = asyncio.run(run_autopilot_ab(fleet_run=True))
        if report["comparison"]["autopilot_wins"]:
            break
    c = report["comparison"]
    assert c["metric"] == "goodput_per_replica_tok_s"
    assert c["autopilot_wins"], c
    assert c["autopilot_decisions"] > 0 and c["decisions_audited"], c
    assert c["greedy_identical"], "fleet control must never change output"
    auto_arm = report["arms"]["autopilot"]
    static_totals = [
        a["totals"]["goodput_tok_s"]
        for n, a in report["arms"].items()
        if n != "autopilot"
    ]
    # the win must come from the denominator (returned replica-seconds),
    # not from shedding the workload: total goodput stays comparable
    assert auto_arm["totals"]["goodput_tok_s"] >= 0.85 * max(static_totals)
    assert auto_arm["fleet"]["active_replicas_mean"] < 2.95
    # audit trail: drain/undrain decisions carry targets + reasons
    kinds = {d["reason"] for d in report["decisions"] if d}
    assert "sustained_idle" in kinds


def test_terminal_drain_refuses_undrain(knob_server):
    """A terminal (preemption) drain cannot be cancelled: end_drain
    refuses, POST /undrain returns 409, and /statusz marks it so the
    autoscaler's snapshot view can skip the replica."""
    st = knob_server
    eng = st.engine
    try:
        eng.begin_drain(terminal=True)
        assert eng.end_drain() is False
        assert eng.is_draining
        assert eng.drain_status()["terminal"] is True
        req = urllib.request.Request(
            f"http://{st.address}/undrain", data=b"{}", method="POST"
        )
        try:
            urllib.request.urlopen(req, timeout=10)
            raise AssertionError("expected 409")
        except urllib.error.HTTPError as e:
            assert e.code == 409
        with urllib.request.urlopen(
            f"http://{st.address}/statusz", timeout=10
        ) as r:
            doc = json.loads(r.read())
        snap = ReplicaSnapshot.from_statusz(st.address, doc)
        assert snap.draining and snap.drain_terminal
    finally:
        # restore the shared module fixture for later tests
        eng._drain_terminal = False
        eng.end_drain()
        eng.continue_generation()
    # an ops (non-terminal) drain still round-trips through /undrain
    eng.begin_drain()
    urllib.request.urlopen(
        urllib.request.Request(
            f"http://{st.address}/undrain", data=b"{}", method="POST"
        ),
        timeout=10,
    ).read()
    assert not eng.is_draining


def test_radix_cap_shrink_evicts_live(knob_server):
    """A shrunk cache cap converges on the live decode loop: pages over
    the new cap are LRU-evicted between chunks."""
    import numpy as np

    from areal_tpu.api.io_struct import GenerationHyperparameters, ModelRequest

    st = knob_server
    eng = st.engine
    _post_knobs(
        st.address, {"radix_max_fraction": 0.8}, token="secret-token"
    )
    # publish pages into the tree via completed generations
    g = GenerationHyperparameters(max_new_tokens=4, greedy=True, ignore_eos=True)
    for i in range(3):
        ids = [2 + i] + [3 + ((i * 5 + j) % 60) for j in range(40)]
        eng.generate_sync(ModelRequest(input_ids=ids, rid=f"cap-{i}", gconfig=g))
    held = eng.prefix_cache_stats()["pages_held"]
    assert held >= 2
    _post_knobs(
        st.address, {"radix_max_fraction": 0.0}, token="secret-token"
    )
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if eng.prefix_cache_stats()["pages_held"] == 0:
            break
        eng._wakeup.set()
        time.sleep(0.05)
    assert eng.prefix_cache_stats()["pages_held"] == 0
    assert eng.slots.radix.max_pages == 0
