"""Learning-health observatory (ISSUE 14): staleness-conditioned PPO loss
diagnostics, trajectory lineage, and the autopilot learning-health guard.

The load-bearing contract is the IDENTITY: bucketed clip/KL/token-share
stats must exactly recompose the batch-wide scalars (weighted by token
share) through the REAL engine path — packed grids, masked segment
reductions, the single step-fence device pull — on mixed synthetic version
tags including the zero-pause mid-commit split population (a sequence
whose tokens span a weight commit, test_weight_sync's versions contract).
"""

import json
import threading
import time

import jax
import numpy as np
import pytest

from areal_tpu.api.config import (
    InferenceEngineConfig,
    MeshConfig,
    MicroBatchSpec,
    OptimizerConfig,
    PPOActorConfig,
    StalenessControllerConfig,
    TrajectoryJournalConfig,
)
from areal_tpu.api.io_struct import FinetuneSpec
from areal_tpu.autopilot import StalenessController
from areal_tpu.autopilot.signals import RateTracker, Signals, assemble
from areal_tpu.engine.train_engine import JaxTrainEngine
from areal_tpu.infra.staleness_manager import (
    HIGH_LAG_BUCKET,
    LAG_BUCKET_LABELS,
    lag_bucket_index,
)
from areal_tpu.observability import lineage as lineage_mod
from areal_tpu.trainer.ppo import PPOActor

from tpu_testing import TINY_QWEN2


BUCKETS = LAG_BUCKET_LABELS


def _actor_cfg(**kw):
    base = dict(
        init_from_scratch=True,
        dtype="float32",
        param_dtype="float32",
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
        optimizer=OptimizerConfig(lr=5e-3, lr_scheduler_type="constant"),
        bucket_step=64,
        group_size=1,
        ppo_n_minibatches=1,
        adv_norm=None,
        kl_ctl=0.0,
        use_decoupled_loss=True,
        prox_logp_mode="recompute",
        # wild prox-vs-behave gaps in the synthetic batch: a tight cap
        # guarantees a non-empty cap-hit tail for the identity to cover
        behav_imp_weight_cap=1.5,
    )
    base.update(kw)
    return PPOActorConfig(**base)


def _mixed_version_batch(v_theta: int, n=4, L=24, seed=0):
    """Token-aligned rollout-style batch with per-sequence version tags:
    lag 0, lag 1, a zero-pause MID-COMMIT SPLIT (tokens span versions
    v_theta-3 -> v_theta-1 inside one sequence), and a deep lag-4+ tail."""
    rng = np.random.default_rng(seed)
    B = n
    ids = rng.integers(1, 250, (B, L)).astype(np.int32)
    attn = np.ones((B, L), bool)
    lm = np.zeros((B, L), np.float32)
    lm[:, 4:] = 1.0
    versions = np.zeros((B, L), np.int32)
    versions[0, :] = v_theta  # lag 0
    versions[1, :] = v_theta - 1  # lag 1
    # the split row: generation crossed a weight commit mid-sequence
    versions[2, : L // 2] = v_theta - 3
    versions[2, L // 2 :] = v_theta - 1
    versions[3, :] = v_theta - 5  # lag 5 -> bucket "4+"
    versions[:, :4] = -1  # prompt tokens are untagged
    return {
        "input_ids": ids,
        "attention_mask": attn,
        "loss_mask": lm,
        # behave logprobs straddle the recomputed prox distribution (tiny
        # model ~= -log V): exp(prox - old) then lands on BOTH sides of
        # the importance-weight cap, so the cap-hit tail is non-empty
        "logprobs": rng.normal(-6.5, 1.5, (B, L)).astype(np.float32),
        "versions": versions,
        "rewards": rng.normal(0.5, 1.0, B).astype(np.float32),
        "seq_no_eos_mask": np.zeros((B,), bool),
    }


@pytest.fixture(scope="module")
def actor():
    cfg = _actor_cfg()
    eng = JaxTrainEngine(cfg, model_config=TINY_QWEN2)
    eng.initialize(FinetuneSpec(1, 64, 4))
    eng.set_version(6)
    return PPOActor(cfg, eng)


# ---------------------------------------------------------------------------
# identity: bucketed stats recompose the batch-wide scalars exactly
# ---------------------------------------------------------------------------


def _run_update(actor, batch):
    batch = dict(batch)
    batch["prox_logp"] = actor.compute_logp(batch)
    adv = actor.compute_advantages(batch)
    stats = actor.ppo_update(adv)
    assert len(stats) == 1  # single minibatch: the identity is exact
    return stats[0]


def _assert_bucket_identity(s):
    share = {b: s[f"lag_{b}/token_share"] for b in BUCKETS}
    assert sum(share.values()) == pytest.approx(1.0, abs=1e-6)
    # clip fraction: token-share-weighted bucket sums == batch scalar
    assert sum(
        share[b] * s[f"lag_{b}/clip_ratio"] for b in BUCKETS
    ) == pytest.approx(s["clip_ratio"], abs=1e-5)
    # approx-KL likewise
    assert sum(
        share[b] * s[f"lag_{b}/approx_kl"] for b in BUCKETS
    ) == pytest.approx(s["approx_kl"], abs=1e-5)
    # behave stats recompose through the behave-token share
    bshare = {b: s[f"lag_{b}/behave_share"] for b in BUCKETS}
    assert sum(bshare.values()) == pytest.approx(1.0, abs=1e-6)
    assert sum(
        bshare[b] * s[f"lag_{b}/behave_approx_kl"] for b in BUCKETS
    ) == pytest.approx(s["behave_approx_kl"], abs=1e-5)
    # cap-hit tail mass recomposes the batch-wide uncapped ratio, and the
    # synthetic prox/behave gap guarantees the tail is non-empty
    cap_total = sum(share[b] * s[f"lag_{b}/cap_hit_share"] for b in BUCKETS)
    assert cap_total == pytest.approx(
        1.0 - s["unclipped_behave_ratio"], abs=1e-5
    )
    assert cap_total > 0
    return share


def test_lag_bucket_stats_recompose_batch_scalars(actor):
    s = _run_update(actor, _mixed_version_batch(v_theta=6))
    share = _assert_bucket_identity(s)
    # the four populations land where the vocabulary says: the split row
    # feeds BOTH the lag-3 (bucket "2") and lag-1 populations
    assert share["0"] > 0 and share["1"] > 0 and share["2"] > 0
    assert share["4+"] > 0


def test_identity_survives_microbatch_split():
    """The identity must hold through a ``max_tokens_per_mb`` split whose
    microbatches carry DIFFERENT bucket mixes (and uneven token weights):
    the jit emits bucket stats normalized by the engine's fold weight
    (total valid tokens) and `_finalize_lag_stats` derives the ratios
    AFTER the fold, so the weighted-mean recombination stays exact. With
    in-jit bucket-ratio normalization the fold weight disagreed with the
    ratio's own denominator and every bucket stat drifted whenever the
    mixes differed."""
    # dp=1 (one-device mesh): with the harness's 8 virtual devices, rows
    # round up to the DP degree and a 3-row grid can never split below it
    cfg = _actor_cfg(
        mb_spec=MicroBatchSpec(max_tokens_per_mb=64),
        mesh=MeshConfig(data=1, fsdp=1, seq=1, model=1),
    )
    eng = JaxTrainEngine(cfg, model_config=TINY_QWEN2)
    from areal_tpu.parallel import mesh as mesh_lib

    eng.initialize(
        FinetuneSpec(1, 64, 4),
        mesh=mesh_lib.make_mesh(cfg.mesh, devices=jax.devices()[:1]),
    )
    eng.set_version(6)
    actor = PPOActor(cfg, eng)
    # 5 sequences pack into 3 microbatches (2+2+1 rows of one 64-token
    # row each): uneven weights AND per-mb bucket mixes
    s = _run_update(actor, _mixed_version_batch(v_theta=6, n=5))
    assert s["n_microbatches"] > 1  # the split actually happened
    _assert_bucket_identity(s)


def test_mid_commit_split_row_spans_two_buckets(actor):
    """The zero-pause split population (versions v-3 -> v-1 inside one
    sequence) must distribute its tokens across BOTH its lag buckets —
    per-token bucketing, not per-trajectory head-version bucketing."""
    batch = _mixed_version_batch(v_theta=6)
    # isolate the split row: only sequence 2 carries loss
    batch["loss_mask"][0] = batch["loss_mask"][1] = batch["loss_mask"][3] = 0
    s = _run_update(actor, batch)
    assert s["lag_1/token_share"] > 0  # post-commit half (lag 1)
    assert s["lag_2/token_share"] > 0  # pre-commit half (lag 3)
    assert s["lag_0/token_share"] == pytest.approx(0.0, abs=1e-6)
    assert s["lag_4+/token_share"] == pytest.approx(0.0, abs=1e-6)
    assert s["lag_1/token_share"] + s["lag_2/token_share"] == pytest.approx(
        1.0, abs=1e-6
    )


def test_host_bucketing_matches_jit_edges():
    for lag, expect in ((0, 0), (1, 1), (2, 2), (3, 2), (4, 3), (32, 3)):
        assert lag_bucket_index(lag) == expect
    assert LAG_BUCKET_LABELS[lag_bucket_index(5)] == HIGH_LAG_BUCKET


def test_per_sequence_attribution_joins_lineage(actor):
    """seq__* grids map back through the packed-batch segment map onto the
    stamped lineage ids: per-trajectory token counts must equal each
    sequence's valid-token count, and the lineage ring must join."""
    ring = lineage_mod.get_lineage()
    batch = _mixed_version_batch(v_theta=6, seed=3)
    lids = [
        ring.register(task_id=f"t{i}", head_version=6, tail_version=6)
        for i in range(4)
    ]
    batch["lineage_id"] = np.asarray(lids, np.int64)
    _run_update(actor, batch)
    seq = actor.engine.last_seq_stats
    assert seq is not None
    lm = np.asarray(batch["loss_mask"])
    # label-aligned valid tokens per sequence == attributed token counts
    per_seq_valid = np.roll(lm, -1, axis=-1)[:, :-1].sum(-1)
    np.testing.assert_allclose(seq["seq__tokens"], per_seq_valid, atol=1e-5)
    for lid in lids:
        rec = ring.get(lid)
        assert rec.trained_version == 6
        assert rec.clip_fraction is not None and 0 <= rec.clip_fraction <= 1
        assert rec.behave_kl is not None


# ---------------------------------------------------------------------------
# lineage ring + executor wiring + journal payload
# ---------------------------------------------------------------------------


class _VersionedEngine:
    def __init__(self, version=0):
        self._v = version
        self.addresses = ["fake:1"]

    def get_version(self):
        return self._v


def _traj(version, n=16, B=1):
    return {
        "input_ids": np.ones((B, n), np.int32),
        "attention_mask": np.ones((B, n), bool),
        "loss_mask": np.ones((B, n), np.float32),
        "versions": np.full((B, n), version, np.int32),
        "rewards": np.full((B,), 2.0, np.float32),
    }


def _executor(tmp_path, version=0, eta=2):
    from areal_tpu.infra.trajectory_journal import TrajectoryJournal
    from areal_tpu.infra.workflow_executor import WorkflowExecutor

    ex = WorkflowExecutor(
        InferenceEngineConfig(
            max_concurrent_rollouts=4,
            consumer_batch_size=2,
            max_head_offpolicyness=eta,
        ),
        engine=_VersionedEngine(version),
    )
    ex.attach_journal(TrajectoryJournal(str(tmp_path / "journal"), fsync=False))
    return ex


def test_version_stats_helper(tmp_path):
    ex = _executor(tmp_path, version=5)
    t = _traj(3)
    t["versions"][0, :4] = -1
    t["versions"][0, -4:] = 4
    assert ex._version_stats(t) == (3, 4, 2, 1, True)
    # untagged trajectory: current version, zero lag/span, not tagged
    assert ex._version_stats({"input_ids": np.ones((1, 4))}) == (
        5,
        5,
        0,
        0,
        False,
    )


def test_executor_journals_lineage_and_replay_rejoins(tmp_path):
    ex = _executor(tmp_path, version=3)
    traj = _traj(3)
    head, tail, _lag, _span, _tagged = ex._version_stats(traj)
    meta = ex._register_lineage(traj, "task-a", head, tail, 16)
    assert meta["lineage_id"] >= 0 and meta["replica"] == "fake:1"
    assert np.asarray(traj["lineage_id"]).shape == (1,)
    ex._journal_append(traj, "task-a", 16, head, tail, meta)
    rec = lineage_mod.get_lineage().get(meta["lineage_id"])
    assert rec.journaled and rec.reward == 2.0
    # consumption stamps the ring with the consuming version
    ex._mark_consumed(["task-a"])
    assert lineage_mod.get_lineage().get(meta["lineage_id"]).consumed_version == 3
    ex.journal.close()

    # the journal frame carries the lineage payload; replay re-registers a
    # FRESH record (the old ring died with the old process) and rewrites
    # the stamped id so train-step attribution lands on the new record
    entries = ex.journal.scan()
    assert entries[0].lineage["task_id"] == "task-a"
    ex2 = _executor(tmp_path, version=3)
    replayed, dropped = ex2.replay_from_journal()
    assert (replayed, dropped) == (1, 0)
    tid, traj2, _ = ex2._results[0]
    new_lid = int(np.ravel(traj2["lineage_id"])[0])
    assert new_lid != meta["lineage_id"]
    rec2 = lineage_mod.get_lineage().get(new_lid)
    assert rec2.task_id == "task-a" and rec2.journaled
    assert rec2.reward == 2.0  # provenance restored from the frame payload


def test_replay_drop_leaves_flight_audit(tmp_path):
    from areal_tpu.observability.timeline import get_flight_recorder

    ex = _executor(tmp_path, version=0, eta=2)
    traj = _traj(0)
    ex._journal_append(traj, "doomed", 16, 0, 0, {"lineage_id": 1})
    ex.journal.close()
    ex2 = _executor(tmp_path, version=10, eta=2)
    before = [
        e
        for e in get_flight_recorder().snapshot()["events"]
        if e["kind"] == "journal_drop_stale"
    ]
    replayed, dropped = ex2.replay_from_journal()
    assert (replayed, dropped) == (0, 1)
    evs = [
        e
        for e in get_flight_recorder().snapshot()["events"]
        if e["kind"] == "journal_drop_stale"
    ]
    assert len(evs) == len(before) + 1
    ev = evs[-1]["data"]
    assert ev["task_id"] == "doomed"
    assert ev["lag"] == 10 and ev["bound"] == 2  # WHICH work, how far past


def test_lineage_ring_bounded_and_threadsafe():
    ring = lineage_mod.TrajectoryLineage(capacity=8)
    errs = []

    def writer(k):
        try:
            for i in range(50):
                lid = ring.register(task_id=f"w{k}-{i}")
                ring.mark_consumed([f"w{k}-{i}"], version=i)
                ring.record_train(lid, version=i, tokens=4, clip_fraction=0.1)
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=writer, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert len(ring.recent()) == 8  # bounded: FIFO eviction, no growth


def test_lineage_dump_merges_into_postmortem_trace(tmp_path):
    from areal_tpu.observability.timeline import FlightRecorder
    from areal_tpu.tools import postmortem

    ring = lineage_mod.TrajectoryLineage(capacity=16)
    lid = ring.register(
        task_id="abc123", replica="r:1", head_version=2, tail_version=3,
        n_tokens=32, reward=1.5, journaled=True,
    )
    ring.mark_consumed(["abc123"], version=4)
    ring.record_train(lid, version=4, tokens=30, clip_fraction=0.25, behave_kl=0.1)
    lpath = ring.dump(str(tmp_path / "lineage.json"), "test")

    flight = FlightRecorder(capacity=8, role="trainer")
    flight.record("journal_drop_stale", task_id="zzz", lag=9, bound=2)
    fpath = str(tmp_path / "flight.json")
    flight.dump(fpath, "test")

    out = tmp_path / "incident.json"
    rc = postmortem.main(["--files", lpath, fpath, "-o", str(out)])
    assert rc == 0
    trace = json.loads(out.read_text())
    names = [e.get("name") for e in trace["traceEvents"]]
    assert any(n == "traj abc123" for n in names)  # the lineage span
    assert any(n == "traj_update" for n in names)  # the loss-join instant
    assert any(n == "journal_drop_stale" for n in names)
    span = next(e for e in trace["traceEvents"] if e.get("name") == "traj abc123")
    assert span["args"]["task_id"] == "abc123"  # x-areal-trace join key
    assert span["args"]["consumed_version"] == 4


# ---------------------------------------------------------------------------
# autopilot learning-health guard
# ---------------------------------------------------------------------------


def _guard_ctrl(bound=2, **kw):
    cfg = StalenessControllerConfig(cooldown_s=0.0, **kw)
    return StalenessController(cfg, initial=bound)


class TestLearningHealthGuard:
    @pytest.mark.parametrize(
        "kw,sig_kw,expect_bound,expect_veto",
        [
            # starved + no learning-health signal: absence is NOT a veto
            ({}, {}, 3, None),
            # high-lag KL divergence blocks the raise
            (
                {},
                {"high_lag_behave_kl": 0.9, "high_lag_token_share": 0.3},
                2,
                "high_lag_kl_divergence",
            ),
            # high-lag tokens clipped dead block the raise
            (
                {},
                {"high_lag_clip_fraction": 0.95, "high_lag_token_share": 0.3},
                2,
                "high_lag_clipped_dead",
            ),
            # cap-hit dead weight blocks the raise too: capped tokens
            # contribute no gradient AND no KL, so a cap-dominated bucket
            # dilutes the KL mean toward zero exactly as it dies
            (
                {},
                {"high_lag_cap_fraction": 0.95, "high_lag_token_share": 0.3},
                2,
                "high_lag_capped_dead",
            ),
            # both present: the clip evidence wins the audit label
            (
                {},
                {
                    "high_lag_clip_fraction": 0.95,
                    "high_lag_behave_kl": 0.9,
                    "high_lag_token_share": 0.3,
                },
                2,
                "high_lag_clipped_dead",
            ),
            # healthy high-lag bucket: the raise proceeds
            (
                {},
                {
                    "high_lag_behave_kl": 0.05,
                    "high_lag_clip_fraction": 0.2,
                    "high_lag_token_share": 0.3,
                },
                3,
                None,
            ),
            # near-empty bucket (< guard_min_token_share): noise, no veto
            (
                {},
                {"high_lag_behave_kl": 0.9, "high_lag_token_share": 0.001},
                3,
                None,
            ),
            # guard off: byte-identical to the pre-guard controller
            (
                {"learning_guard": False},
                {"high_lag_behave_kl": 0.9, "high_lag_token_share": 0.3},
                3,
                None,
            ),
        ],
    )
    def test_grow_veto_table(self, kw, sig_kw, expect_bound, expect_veto):
        ctrl = _guard_ctrl(**kw)
        sig = Signals(now=100.0, bubble_fraction=0.4, **sig_kw)
        actions = ctrl.decide(sig)
        assert ctrl.bound == expect_bound
        if expect_veto is None:
            assert ctrl.last_veto is None
            assert [a.reason for a in actions] == ["trainer_starved"]
        else:
            assert actions == []
            assert ctrl.last_veto[0] == expect_veto
            # no cooldown consumed: the next healthy round may act at once
            healthy = Signals(now=100.5, bubble_fraction=0.4)
            assert ctrl.decide(healthy) != []

    def test_guard_never_blocks_shrink(self):
        ctrl = _guard_ctrl(bound=3)
        sig = Signals(
            now=100.0,
            bubble_fraction=0.0,
            version_span_p99=2.0,
            high_lag_behave_kl=5.0,
            high_lag_token_share=0.5,
        )
        acts = ctrl.decide(sig)
        assert [a.reason for a in acts] == ["low_bubble_wide_span"]
        assert ctrl.bound == 2 and ctrl.last_veto is None

    def test_facade_audits_veto(self):
        from areal_tpu.api.config import (
            AdmissionControllerConfig,
            AutopilotConfig,
            CacheControllerConfig,
            FleetControllerConfig,
        )
        from areal_tpu.autopilot import Autopilot
        from areal_tpu.infra.staleness_manager import StalenessManager
        from areal_tpu.observability.timeline import FlightRecorder

        cfg = AutopilotConfig(
            enabled=True,
            staleness=StalenessControllerConfig(cooldown_s=0.0),
            admission=AdmissionControllerConfig(enabled=False),
            cache=CacheControllerConfig(enabled=False),
            fleet=FleetControllerConfig(enabled=False),
        )
        sm = StalenessManager(
            _VersionedEngine(0), max_concurrent_rollouts=4,
            consumer_batch_size=2, max_staleness=2,
        )
        flight = FlightRecorder(capacity=16, role="test")

        class _Src:
            samples = []

            def fetch(self):
                return self.samples

        class _Poller:
            def live(self):
                return {}

            def start(self):
                pass

            def stop(self):
                pass

        ap = Autopilot(
            cfg,
            lambda: [],
            staleness_manager=sm,
            metrics_source=_Src(),
            poller=_Poller(),
            flight=flight,
        )
        ctrl = ap.controllers[0]
        sig = Signals(
            now=1.0,
            bubble_fraction=0.4,
            high_lag_behave_kl=0.9,
            high_lag_token_share=0.3,
        )
        ap.read_signals = lambda: sig  # inject the round's signals
        assert ap.tick() == []
        assert ctrl.bound == 2  # vetoed: the bound did not move
        evs = [
            e
            for e in flight.snapshot()["events"]
            if e["kind"] == "autopilot_guard_veto"
        ]
        assert len(evs) == 1
        assert evs[0]["data"]["reason"] == "high_lag_kl_divergence"
        assert sm.max_staleness == 2  # never actuated


# ---------------------------------------------------------------------------
# signal plane: windowed high-lag ratios from counter deltas
# ---------------------------------------------------------------------------


def _lag_samples(tokens, clipped, kl_sum, tot_extra=0.0, capped=0.0):
    hb = HIGH_LAG_BUCKET
    return [
        ("areal_train_lag_tokens_total", {"lag_bucket": hb}, tokens),
        ("areal_train_lag_tokens_total", {"lag_bucket": "0"}, tot_extra),
        ("areal_train_lag_clipped_total", {"lag_bucket": hb}, clipped),
        ("areal_train_lag_capped_total", {"lag_bucket": hb}, capped),
        ("areal_train_lag_behave_kl_sum_total", {"lag_bucket": hb}, kl_sum),
    ]


def test_assemble_high_lag_window():
    rates = RateTracker()
    s1 = assemble(_lag_samples(100, 10, 5.0, tot_extra=100), rates, now=10.0)
    # first observation: no window yet -> absent, guard cannot fire
    assert s1.high_lag_behave_kl is None
    assert s1.high_lag_clip_fraction is None
    s2 = assemble(
        _lag_samples(200, 100, 55.0, tot_extra=200, capped=80), rates, now=20.0
    )
    # window deltas: 100 tokens, 90 clipped, 80 capped, 50 KL high-lag
    assert s2.high_lag_clip_fraction == pytest.approx(0.9)
    assert s2.high_lag_cap_fraction == pytest.approx(0.8)
    assert s2.high_lag_behave_kl == pytest.approx(0.5)
    assert s2.high_lag_token_share == pytest.approx(0.5)
    # quiet window (no new trained tokens): absent again, never stale
    s3 = assemble(_lag_samples(200, 100, 55.0, tot_extra=200), rates, now=30.0)
    assert s3.high_lag_behave_kl is None


def test_assemble_without_lag_metrics_stays_absent():
    sig = assemble(
        [("areal_decode_generated_tokens_total", {}, 5.0)],
        RateTracker(),
        now=1.0,
    )
    assert sig.high_lag_behave_kl is None
    assert sig.high_lag_clip_fraction is None
    assert sig.high_lag_token_share is None
