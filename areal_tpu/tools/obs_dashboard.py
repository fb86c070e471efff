"""Live terminal dashboard over the areal_tpu telemetry fleet.

Scrapes one or more ``/metrics`` endpoints (inference servers directly, or
a rollout controller's aggregated endpoint) and renders the async-RL
vitals: queue depths, staleness admission state, tokens/s, pause state,
and weight-update latency.

Usage:
    python -m areal_tpu.tools.obs_dashboard --targets host:port,host:port
    python -m areal_tpu.tools.obs_dashboard --targets ... --once
    python -m areal_tpu.tools.obs_dashboard --self-test   # CI smoke mode

``--self-test`` starts a local fake scrape target serving canned
exposition text, runs one aggregation + render round against it, asserts
the pipeline end-to-end (scrape -> parse -> merge -> render), and exits
0/1 — the tier-1 smoke test invokes exactly this.
"""

from __future__ import annotations

import argparse
import sys
import time

from areal_tpu.observability.aggregator import FleetAggregator, FleetSnapshot

# (metric, label filter, display name) rows for the vitals table
_ROWS = (
    ("areal_rollout_capacity", "staleness capacity"),
    ("areal_rollout_running", "rollouts running"),
    ("areal_rollout_accepted_total", "accepted"),
    ("areal_rollout_rejected_total", "rejected"),
    ("areal_executor_input_queue_depth", "input queue"),
    ("areal_executor_eval_queue_depth", "eval queue"),
    ("areal_executor_inflight_tasks", "in flight"),
    ("areal_server_queue_depth", "server queue"),
    ("areal_request_queue_depth", "lifecycle queue"),
    ("areal_decode_batch_occupancy", "batch occupancy"),
    ("areal_server_paused", "paused servers"),
    ("areal_weight_update_total", "weight updates"),
    ("areal_prefix_cache_pages_held", "prefix-cache pages"),
)


def _merged_value(snap: FleetSnapshot, name: str) -> float | None:
    """Sum a metric across all its label children in the merged view."""
    total = None
    for (n, _labels), v in snap.merged.items():
        if n == name:
            total = (total or 0.0) + v
    return total


def _merged_value_labeled(
    snap: FleetSnapshot, name: str, **want: str
) -> float | None:
    """Sum a metric over the label children matching ``want`` (e.g. the
    mode="sync" slice of areal_ckpt_save_seconds_sum)."""
    total = None
    for (n, labels), v in snap.merged.items():
        if n != name:
            continue
        ld = dict(labels)
        if all(ld.get(k) == val for k, val in want.items()):
            total = (total or 0.0) + v
    return total


def _shed_total(snap: FleetSnapshot) -> float | None:
    """Fleet-wide count of requests turned away with a 429: gateway load
    shedding (by priority class) + engine admission rejections (by reason)."""
    gw = _merged_value(snap, "areal_gateway_shed_total")
    adm = _merged_value(snap, "areal_admission_rejected_total")
    if gw is None and adm is None:
        return None
    return (gw or 0.0) + (adm or 0.0)


def _histogram_quantile(
    snap: FleetSnapshot, name: str, q: float
) -> float | None:
    """Approximate quantile from merged histogram buckets (classic
    Prometheus-style linear interpolation inside the winning bucket).
    Label children (e.g. ttft's priority classes) are summed — per-``le``
    cumulative counts stay cumulative under addition."""
    buckets: dict[float, float] = {}
    target_name = name + "_bucket"
    for (n, labels), v in snap.merged.items():
        if n != target_name:
            continue
        le = dict(labels).get("le")
        if le is None:
            continue
        lef = float("inf") if le == "+Inf" else float(le)
        buckets[lef] = buckets.get(lef, 0.0) + v
    total = buckets.get(float("inf"))
    if not total:
        return None
    target = q * total
    prev_le, prev_c = 0.0, 0.0
    for le in sorted(buckets):
        c = buckets[le]
        if c >= target:
            if le == float("inf") or c == prev_c:
                return prev_le if le == float("inf") else le
            return prev_le + (le - prev_le) * (target - prev_c) / (c - prev_c)
        prev_le, prev_c = le, c
    return prev_le


def _flight_total(snap: FleetSnapshot) -> float | None:
    """Flight-recorder events across all kinds (rate needs two frames)."""
    return _merged_value(snap, "areal_flight_events_total")


def _mean_per_target(snap: FleetSnapshot, name: str) -> float | None:
    """Mean of a gauge across live targets: fractions (bubble, MFU,
    headroom) are per-process ratios — SUMMING them across a fleet would
    report 200% utilization from two healthy trainers."""
    per = snap.per_target(name)
    if not per:
        return None
    return sum(per.values()) / len(per)


def _min_per_target(snap: FleetSnapshot, name: str) -> float | None:
    """Worst-replica view of a gauge (the HBM headroom that matters is the
    replica closest to OOM, not the fleet average)."""
    per = snap.per_target(name)
    if not per:
        return None
    return min(per.values())


# learning-health lag-bucket vocabulary (infra/staleness_manager.py)
from areal_tpu.infra.staleness_manager import LAG_BUCKET_LABELS as _LAG_BUCKETS

# decode-step phase vocabulary (observability/kernel_probe.py) + the
# identity remainder bucket
_DECODE_PHASES = (
    "admission",
    "radix_match",
    "prefill",
    "draft",
    "dispatch",
    "device_wait",
    "verify",
    "bookkeeping",
    "other",
)

# trainer observatory phase vocabulary (observability/step_timeline.py)
_TRAIN_PHASES = (
    "rollout_wait",
    "host_prep",
    "forward_backward",
    "optimizer",
    "weight_publish",
    "ckpt_eval",
    "other",
)


def _labeled_values(
    snap: FleetSnapshot, name: str, label: str
) -> dict[str, list[float]]:
    """{label value: [per-target raw values]} for one labeled family —
    the un-summed view for gauges whose fleet semantics are not additive
    (autopilot setpoints, last-action ages)."""
    out: dict[str, list[float]] = {}
    for t in snap.targets:
        if not t.up:
            continue
        for n, labels, v in t.samples:
            if n == name:
                out.setdefault(dict(labels).get(label, "?"), []).append(v)
    return out


def _fmt(v: float | None) -> str:
    if v is None:
        return "-"
    if float(v).is_integer():
        return str(int(v))
    return f"{v:.2f}"


def render_frame(
    snap: FleetSnapshot, prev: FleetSnapshot | None = None
) -> str:
    """One dashboard frame as plain text (also the --once/--self-test
    output, so it stays pipe- and CI-friendly)."""
    lines = []
    up, total = snap.n_up, len(snap.targets)
    lines.append(
        f"areal_tpu fleet  |  targets {up}/{total} up  |  "
        + time.strftime("%H:%M:%S", time.localtime(snap.scraped_at))
    )
    lines.append("-" * 64)
    # tokens/s needs two frames: rate = d(generated)/dt
    toks = _merged_value(snap, "areal_decode_generated_tokens_total")
    if prev is not None and toks is not None:
        prev_toks = _merged_value(
            prev, "areal_decode_generated_tokens_total"
        )
        dt = snap.scraped_at - prev.scraped_at
        if prev_toks is not None and dt > 0:
            lines.append(f"{'tokens/s':<24} {(toks - prev_toks) / dt:>12.1f}")
    elif toks is not None:
        lines.append(f"{'tokens (total)':<24} {_fmt(toks):>12}")
    for name, label in _ROWS:
        v = _merged_value(snap, name)
        if v is not None:
            lines.append(f"{label:<24} {_fmt(v):>12}")
    # fleet-level prefix reuse: tokens served from radix-cached KV over all
    # prompt tokens admitted (cached + actually prefilled)
    hit_tok = _merged_value(snap, "areal_prefix_cache_hit_tokens_total")
    pf_tok = _merged_value(snap, "areal_decode_prefill_tokens_total")
    if hit_tok is not None and pf_tok is not None and (hit_tok + pf_tok) > 0:
        lines.append(
            f"{'prefix hit rate':<24} {hit_tok / (hit_tok + pf_tok):>11.1%}"
        )
    # routing brain (docs/serving.md "Cache-aware routing"): decision
    # totals by reason plus the predicted-vs-actual prefix-hit audit —
    # divergence means the shadow index drifted from the fleet's caches
    decisions = _merged_value(snap, "areal_router_decisions_total")
    if decisions is not None:
        lines.append(f"{'router decisions':<24} {_fmt(decisions):>12}")
        reasons = {}
        for (n, labels), v in snap.merged.items():
            if n == "areal_router_decisions_total":
                key = dict(labels).get("reason", "?")
                reasons[key] = reasons.get(key, 0.0) + v
        top = sorted(reasons.items(), key=lambda kv: -kv[1])[:4]
        for reason, v in top:
            lines.append(f"{'  ' + reason:<24} {_fmt(v):>12}")
        pred = _merged_value(snap, "areal_router_predicted_hit_total")
        act = _merged_value(snap, "areal_router_actual_hit_total")
        if pred is not None or act is not None:
            lines.append(
                f"{'router hit pred/actual':<24} "
                f"{_fmt(pred or 0):>6} / {_fmt(act or 0)}"
            )
    # goodput autopilot (docs/autopilot.md): current setpoints, decision
    # totals by reason, and each controller's last-action age — the
    # at-a-glance answer to "what is the control plane doing right now".
    # Setpoints and ages are per-control-plane FACTS, not additive: they
    # bypass the fleet merge-sum (two scrapes of one plane must not
    # double a setpoint; a mixed acted/never fleet must not average the
    # -1 sentinel into a bogus age).
    ap_decisions = _merged_value(snap, "areal_autopilot_decisions_total")
    if ap_decisions is not None:
        lines.append("-" * 64)
        lines.append(f"{'autopilot decisions':<24} {_fmt(ap_decisions):>12}")
        reasons: dict[str, float] = {}
        for (n, labels), v in snap.merged.items():
            if n == "areal_autopilot_decisions_total":
                key = dict(labels).get("reason", "?")
                reasons[key] = reasons.get(key, 0.0) + v
        for reason, v in sorted(reasons.items(), key=lambda kv: -kv[1])[:4]:
            lines.append(f"{'  ' + reason:<24} {_fmt(v):>12}")
        for knob, vs in sorted(
            _labeled_values(snap, "areal_autopilot_setpoint", "knob").items()
        ):
            lines.append(f"{'  set ' + knob:<28} {_fmt(max(vs)):>8}")
        for ctrl, vs in sorted(
            _labeled_values(
                snap, "areal_autopilot_last_action_age_seconds", "controller"
            ).items()
        ):
            nonneg = [v for v in vs if v >= 0]
            age = f"{min(nonneg):.0f}s ago" if nonneg else "never"
            lines.append(f"{'  ' + ctrl + ' acted':<24} {age:>12}")
    # gateway tier (docs/serving.md "Gateway tier"): ring fan-out plus the
    # shard-death story — degraded membership refreshes, affinity repairs
    # on survivors, misroutes, and per-shard session balance. The shard
    # count and per-shard session gauges are per-membership-view FACTS
    # (co-located shards share one registry, so every scrape of the tier
    # process reports the whole tier): take the max, never the merge-sum.
    shard_counts = [
        v
        for t in snap.targets
        if t.up
        for n, _labels, v in t.samples
        if n == "areal_gateway_shard_count"
    ]
    if shard_counts:
        lines.append("-" * 64)
        lines.append(f"{'gateway shards':<24} {_fmt(max(shard_counts)):>12}")
        for metric, label in (
            ("areal_gateway_shard_membership_stale_total", "  stale membership"),
            ("areal_gateway_shard_route_recoveries_total", "  route recoveries"),
            ("areal_gateway_shard_misroute_total", "  misroutes"),
            ("areal_gateway_shard_drain_total", "  drain transitions"),
        ):
            v = _merged_value(snap, metric)
            if v is not None:
                lines.append(f"{label:<24} {_fmt(v):>12}")
        for shard, vs in sorted(
            _labeled_values(
                snap, "areal_gateway_shard_sessions", "shard"
            ).items()
        ):
            lines.append(f"{'  sessions ' + shard:<24} {_fmt(max(vs)):>12}")
    # overload view (docs/request_lifecycle.md): everything turned away with
    # a 429 — gateway load shedding + engine admission rejections — as a
    # fleet total, and as a rate once two frames exist
    shed = _shed_total(snap)
    if shed is not None:
        lines.append(f"{'shed/rejected (429)':<24} {_fmt(shed):>12}")
        if prev is not None:
            prev_shed = _shed_total(prev)
            dt = snap.scraped_at - prev.scraped_at
            if prev_shed is not None and dt > 0:
                lines.append(
                    f"{'shed rate (429/s)':<24} {(shed - prev_shed) / dt:>12.1f}"
                )
    # request-timeline stage view (observability/timeline.py): TTFT/TPOT
    # tails from the catalogued stage histograms, fence-stall cost, and the
    # flight-recorder event cadence
    for metric, label in (
        ("areal_request_ttft_seconds", "ttft"),
        ("areal_request_tpot_seconds", "tpot"),
    ):
        p50 = _histogram_quantile(snap, metric, 0.50)
        p99 = _histogram_quantile(snap, metric, 0.99)
        if p50 is not None and p99 is not None:
            lines.append(
                f"{label + ' p50/p99 (s)':<24} {p50:>6.3f} / {p99:.3f}"
            )
    fence_sum = _merged_value(snap, "areal_request_fence_stall_seconds_sum")
    fence_cnt = _merged_value(snap, "areal_request_fence_stall_seconds_count")
    if fence_sum is not None and fence_cnt:
        lines.append(
            f"{'fence stall (mean s)':<24} {fence_sum / fence_cnt:>12.3f}"
        )
    flight = _flight_total(snap)
    if flight is not None:
        lines.append(f"{'flight events':<24} {_fmt(flight):>12}")
        if prev is not None:
            prev_flight = _flight_total(prev)
            dt = snap.scraped_at - prev.scraped_at
            if prev_flight is not None and dt > 0:
                lines.append(
                    f"{'flight events/s':<24} {(flight - prev_flight) / dt:>12.1f}"
                )
    pause_sum = _merged_value(snap, "areal_weight_update_pause_seconds_sum")
    pause_cnt = _merged_value(snap, "areal_weight_update_pause_seconds_count")
    if pause_sum is not None and pause_cnt:
        lines.append(
            f"{'update pause (mean s)':<24} {pause_sum / pause_cnt:>12.3f}"
        )
    # preemption tolerance (docs/fault_tolerance.md): drains survived,
    # drain cost, step-loop checkpoint pause by mode, and how much rollout
    # work the trajectory journal saved from re-generation
    preempts = _merged_value(snap, "areal_preemption_total")
    if preempts is not None:
        lines.append(f"{'preemptions':<24} {_fmt(preempts):>12}")
    drain_sum = _merged_value(snap, "areal_drain_seconds_sum")
    drain_cnt = _merged_value(snap, "areal_drain_seconds_count")
    if drain_sum is not None and drain_cnt:
        lines.append(
            f"{'drain (mean s)':<24} {drain_sum / drain_cnt:>12.2f}"
        )
    for mode in ("sync", "async"):
        s = _merged_value_labeled(
            snap, "areal_ckpt_save_seconds_sum", mode=mode
        )
        c = _merged_value_labeled(
            snap, "areal_ckpt_save_seconds_count", mode=mode
        )
        if s is not None and c:
            lines.append(
                f"{'ckpt pause ' + mode + ' (s)':<24} {s / c:>12.3f}"
            )
    replayed = _merged_value(snap, "areal_journal_replayed_total")
    dropped = _merged_value(snap, "areal_journal_dropped_stale_total")
    if replayed is not None or dropped is not None:
        lines.append(
            f"{'journal replay/stale':<24} "
            f"{_fmt(replayed or 0):>6} / {_fmt(dropped or 0)}"
        )
    # decode-step phases (docs/observability.md "Decode-step phases"):
    # phase means with the dominant phase highlighted
    dphase_rows = []
    for ph in _DECODE_PHASES:
        s = _merged_value_labeled(
            snap, "areal_decode_phase_seconds_sum", phase=ph
        )
        c = _merged_value_labeled(
            snap, "areal_decode_phase_seconds_count", phase=ph
        )
        if s is not None and c:
            dphase_rows.append((ph, s / c))
    if dphase_rows:
        lines.append("-" * 64)
        lines.append("decode step phases (mean s)")
        dominant = max(dphase_rows, key=lambda kv: kv[1])[0]
        for ph, v in dphase_rows:
            label = "  " + ph + (" (dominant)" if ph == dominant else "")
            lines.append(f"{label:<24} {v:>12.6f}")
    # speculative decoding (docs/serving.md "Speculative decoding"):
    # acceptance economics — drafted vs accepted tokens, the per-round
    # accepted-length mean, and allocator-level rollback churn
    spec_rounds = _merged_value(snap, "areal_spec_rounds_total")
    if spec_rounds is not None:
        lines.append("-" * 64)
        lines.append(f"{'spec rounds':<24} {_fmt(spec_rounds):>12}")
        drafted = _merged_value(snap, "areal_spec_draft_tokens_total")
        accepted = _merged_value(snap, "areal_spec_accepted_tokens_total")
        if drafted is not None:
            lines.append(f"{'spec drafted tokens':<24} {_fmt(drafted):>12}")
            for src, vs in sorted(
                _labeled_values(
                    snap, "areal_spec_draft_tokens_total", "source"
                ).items()
            ):
                lines.append(f"{'  draft ' + src:<24} {_fmt(sum(vs)):>12}")
        if accepted is not None:
            lines.append(f"{'spec accepted tokens':<24} {_fmt(accepted):>12}")
        if drafted and accepted is not None:
            lines.append(
                f"{'spec acceptance rate':<24} {accepted / drafted:>11.1%}"
            )
        al_sum = _merged_value(snap, "areal_spec_accepted_length_sum")
        al_cnt = _merged_value(snap, "areal_spec_accepted_length_count")
        if al_sum is not None and al_cnt:
            lines.append(
                f"{'spec accepted len mean':<24} {al_sum / al_cnt:>12.2f}"
            )
        rb = _merged_value(snap, "areal_spec_rollback_pages_total")
        if rb is not None:
            lines.append(f"{'spec rollback pages':<24} {_fmt(rb):>12}")
    # trainer observatory (docs/observability.md "Trainer observatory"):
    # step-phase means with the async bubble highlighted, utilization,
    # worst-replica HBM headroom, and the recompile-storm counters
    phase_rows = []
    for ph in _TRAIN_PHASES:
        s = _merged_value_labeled(
            snap, "areal_train_phase_seconds_sum", phase=ph
        )
        c = _merged_value_labeled(
            snap, "areal_train_phase_seconds_count", phase=ph
        )
        if s is not None and c:
            phase_rows.append((ph, s / c))
    if phase_rows:
        lines.append("-" * 64)
        lines.append("trainer step phases (mean s)")
        for ph, v in phase_rows:
            label = "  " + ph + (" (bubble)" if ph == "rollout_wait" else "")
            lines.append(f"{label:<24} {v:>12.3f}")
    bub = _mean_per_target(snap, "areal_train_bubble_fraction")
    if bub is not None:
        lines.append(f"{'bubble fraction':<24} {bub:>11.1%}")
    mfu = _mean_per_target(snap, "areal_train_mfu")
    if mfu is not None:
        lines.append(f"{'mfu':<24} {mfu:>11.1%}")
    tok_chip = _mean_per_target(snap, "areal_train_tokens_per_sec_per_chip")
    if tok_chip is not None:
        lines.append(f"{'train tok/s/chip':<24} {tok_chip:>12.1f}")
    head = _min_per_target(snap, "areal_hbm_headroom_fraction")
    if head is not None:
        lines.append(f"{'hbm headroom (worst)':<24} {head:>11.1%}")
    compiles = _merged_value(snap, "areal_xla_compiles_total")
    if compiles is not None:
        lines.append(f"{'xla compiles':<24} {_fmt(compiles):>12}")
        cs = _merged_value(snap, "areal_xla_compile_seconds_sum")
        if cs is not None:
            lines.append(f"{'xla compile time (s)':<24} {cs:>12.1f}")
    # learning-health observatory (docs/observability.md): decoupled-PPO
    # loss diagnostics by version-lag bucket — clip fraction, behave |KL|,
    # cap-hit tail mass, token share — plus the lineage join counters.
    # Per-bucket gauges are per-trainer facts (mean across targets, like
    # bubble/MFU), never fleet-summed.
    share_by = _labeled_values(snap, "areal_train_lag_token_share", "lag_bucket")
    if share_by:
        clip_by = _labeled_values(snap, "areal_train_lag_clip_ratio", "lag_bucket")
        kl_by = _labeled_values(snap, "areal_train_lag_behave_kl", "lag_bucket")
        cap_by = _labeled_values(
            snap, "areal_train_lag_cap_hit_share", "lag_bucket"
        )

        def _bucket_mean(d: dict[str, list[float]], label: str) -> float:
            vs = d.get(label)
            return sum(vs) / len(vs) if vs else 0.0

        lines.append("-" * 64)
        lines.append("learning health by lag bucket (clip/|KL|/cap-hit/tok)")
        for label in _LAG_BUCKETS:
            if label not in share_by:
                continue
            lines.append(
                f"{'  lag ' + label:<10}"
                f" clip {_bucket_mean(clip_by, label):>6.1%}"
                f"  |KL| {_bucket_mean(kl_by, label):>8.4f}"
                f"  cap {_bucket_mean(cap_by, label):>6.1%}"
                f"  tok {_bucket_mean(share_by, label):>6.1%}"
            )
        regd = _merged_value(snap, "areal_lineage_records_total")
        joined = _merged_value(snap, "areal_lineage_joined_total")
        if regd is not None:
            lines.append(
                f"{'lineage joined/records':<24} "
                f"{_fmt(joined or 0):>6} / {_fmt(regd)}"
            )
    # straggler view: per-target token counters expose a lagging server
    # that the fleet-merged sums hide
    per = snap.per_target("areal_decode_generated_tokens_total")
    if len(per) > 1:
        lines.append("-" * 64)
        for target, v in sorted(per.items(), key=lambda kv: kv[1]):
            lines.append(f"  {target:<22} {_fmt(v):>12} tok")
    down = [t.target for t in snap.targets if not t.up]
    if down:
        lines.append("-" * 64)
        for t in down:
            lines.append(f"DOWN  {t}")
    return "\n".join(lines)


def run_dashboard(
    targets: list[str],
    refresh: float = 2.0,
    once: bool = False,
    timeout: float = 2.0,
) -> int:
    agg = FleetAggregator(targets, timeout=timeout)
    prev = None
    while True:
        snap = agg.scrape_once()
        frame = render_frame(snap, prev)
        if once:
            print(frame)
            return 0 if snap.n_up == len(targets) else 1
        # clear + home, then the frame (plain ANSI, no curses dependency)
        sys.stdout.write("\x1b[2J\x1b[H" + frame + "\n")
        sys.stdout.flush()
        prev = snap
        time.sleep(refresh)


# ---------------------------------------------------------------------------
# --self-test: CI smoke over a fake scrape target
# ---------------------------------------------------------------------------

_FAKE_EXPOSITION = """\
# HELP areal_rollout_capacity Remaining rollout admission capacity.
# TYPE areal_rollout_capacity gauge
areal_rollout_capacity 7
# HELP areal_executor_input_queue_depth Queued train rollout tasks.
# TYPE areal_executor_input_queue_depth gauge
areal_executor_input_queue_depth 3
# HELP areal_decode_generated_tokens_total Tokens emitted by the decode loop.
# TYPE areal_decode_generated_tokens_total counter
areal_decode_generated_tokens_total 1234
# HELP areal_server_paused 1 while generation is paused.
# TYPE areal_server_paused gauge
areal_server_paused 0
# HELP areal_prefix_cache_hit_tokens_total Tokens served from cached KV.
# TYPE areal_prefix_cache_hit_tokens_total counter
areal_prefix_cache_hit_tokens_total 800
# HELP areal_decode_prefill_tokens_total Prompt tokens prefilled.
# TYPE areal_decode_prefill_tokens_total counter
areal_decode_prefill_tokens_total 200
# HELP areal_request_queue_depth Engine admission queue + backlog depth.
# TYPE areal_request_queue_depth gauge
areal_request_queue_depth 2
# HELP areal_gateway_shed_total Requests load-shed at the gateway.
# TYPE areal_gateway_shed_total counter
areal_gateway_shed_total{priority="rollout"} 5
areal_gateway_shed_total{priority="interactive"} 1
# HELP areal_router_decisions_total Replica-selection decisions by reason.
# TYPE areal_router_decisions_total counter
areal_router_decisions_total{reason="prefix_overlap"} 6
areal_router_decisions_total{reason="least_loaded"} 3
areal_router_decisions_total{reason="stale_snapshots"} 1
# HELP areal_router_predicted_hit_total Decisions predicting a warm prefix.
# TYPE areal_router_predicted_hit_total counter
areal_router_predicted_hit_total 6
# HELP areal_router_actual_hit_total Routed requests with a real radix hit.
# TYPE areal_router_actual_hit_total counter
areal_router_actual_hit_total 5
# HELP areal_admission_rejected_total Requests rejected at engine admission.
# TYPE areal_admission_rejected_total counter
areal_admission_rejected_total{reason="queue_depth"} 4
# HELP areal_gateway_shard_count Live gateway shards in the membership view.
# TYPE areal_gateway_shard_count gauge
areal_gateway_shard_count 3
# HELP areal_gateway_shard_membership_stale_total Failed membership refreshes served on the last-known view.
# TYPE areal_gateway_shard_membership_stale_total counter
areal_gateway_shard_membership_stale_total 2
# HELP areal_gateway_shard_route_recoveries_total Sessions adopted by a surviving shard.
# TYPE areal_gateway_shard_route_recoveries_total counter
areal_gateway_shard_route_recoveries_total 4
# HELP areal_gateway_shard_misroute_total Requests landing on an unexpected shard.
# TYPE areal_gateway_shard_misroute_total counter
areal_gateway_shard_misroute_total 1
# HELP areal_gateway_shard_sessions Active session routes per gateway shard.
# TYPE areal_gateway_shard_sessions gauge
areal_gateway_shard_sessions{shard="gw0"} 5
areal_gateway_shard_sessions{shard="gw1"} 3
# HELP areal_autopilot_decisions_total Autopilot setpoint changes applied.
# TYPE areal_autopilot_decisions_total counter
areal_autopilot_decisions_total{controller="admission",reason="queue_wait_high"} 3
areal_autopilot_decisions_total{controller="fleet",reason="sustained_idle"} 1
# HELP areal_autopilot_setpoint Current autopilot-managed setpoint by knob.
# TYPE areal_autopilot_setpoint gauge
areal_autopilot_setpoint{knob="max_queue_depth"} 16
# HELP areal_autopilot_last_action_age_seconds Seconds since each controller acted.
# TYPE areal_autopilot_last_action_age_seconds gauge
areal_autopilot_last_action_age_seconds{controller="admission"} 12
areal_autopilot_last_action_age_seconds{controller="cache"} -1
# HELP areal_weight_update_pause_seconds Availability gap per update.
# TYPE areal_weight_update_pause_seconds histogram
areal_weight_update_pause_seconds_bucket{le="1"} 2
areal_weight_update_pause_seconds_bucket{le="+Inf"} 2
areal_weight_update_pause_seconds_sum 1.5
areal_weight_update_pause_seconds_count 2
# HELP areal_request_ttft_seconds Engine-side time to first token.
# TYPE areal_request_ttft_seconds histogram
areal_request_ttft_seconds_bucket{priority="interactive",le="0.05"} 8
areal_request_ttft_seconds_bucket{priority="interactive",le="0.1"} 10
areal_request_ttft_seconds_bucket{priority="interactive",le="+Inf"} 10
areal_request_ttft_seconds_sum{priority="interactive"} 0.5
areal_request_ttft_seconds_count{priority="interactive"} 10
# HELP areal_request_tpot_seconds Time per output token after the first.
# TYPE areal_request_tpot_seconds histogram
areal_request_tpot_seconds_bucket{le="0.005"} 90
areal_request_tpot_seconds_bucket{le="0.01"} 100
areal_request_tpot_seconds_bucket{le="+Inf"} 100
areal_request_tpot_seconds_sum 0.4
areal_request_tpot_seconds_count 100
# HELP areal_request_fence_stall_seconds Fence stall per request.
# TYPE areal_request_fence_stall_seconds histogram
areal_request_fence_stall_seconds_bucket{le="0.1"} 4
areal_request_fence_stall_seconds_bucket{le="+Inf"} 4
areal_request_fence_stall_seconds_sum 0.2
areal_request_fence_stall_seconds_count 4
# HELP areal_flight_events_total Flight-recorder events by kind.
# TYPE areal_flight_events_total counter
areal_flight_events_total{kind="admission_reject"} 3
areal_flight_events_total{kind="weight_commit"} 2
# HELP areal_preemption_total Preemption signals honored, by role.
# TYPE areal_preemption_total counter
areal_preemption_total{role="trainer"} 1
areal_preemption_total{role="inference_server"} 2
# HELP areal_drain_seconds Graceful-drain duration.
# TYPE areal_drain_seconds histogram
areal_drain_seconds_bucket{le="5"} 3
areal_drain_seconds_bucket{le="+Inf"} 3
areal_drain_seconds_sum 6.0
areal_drain_seconds_count 3
# HELP areal_ckpt_save_seconds Step-loop pause per checkpoint save, by mode.
# TYPE areal_ckpt_save_seconds histogram
areal_ckpt_save_seconds_bucket{mode="sync",le="+Inf"} 2
areal_ckpt_save_seconds_sum{mode="sync"} 5.0
areal_ckpt_save_seconds_count{mode="sync"} 2
areal_ckpt_save_seconds_bucket{mode="async",le="+Inf"} 4
areal_ckpt_save_seconds_sum{mode="async"} 0.4
areal_ckpt_save_seconds_count{mode="async"} 4
# HELP areal_journal_replayed_total Journaled trajectories replayed on recovery.
# TYPE areal_journal_replayed_total counter
areal_journal_replayed_total 7
# HELP areal_journal_dropped_stale_total Journaled trajectories dropped over-stale.
# TYPE areal_journal_dropped_stale_total counter
areal_journal_dropped_stale_total 1
# HELP areal_decode_phase_seconds Wall-clock seconds per decode-step phase.
# TYPE areal_decode_phase_seconds histogram
areal_decode_phase_seconds_bucket{phase="dispatch",le="+Inf"} 10
areal_decode_phase_seconds_sum{phase="dispatch"} 0.5
areal_decode_phase_seconds_count{phase="dispatch"} 10
areal_decode_phase_seconds_bucket{phase="device_wait",le="+Inf"} 10
areal_decode_phase_seconds_sum{phase="device_wait"} 0.2
areal_decode_phase_seconds_count{phase="device_wait"} 10
# HELP areal_spec_rounds_total Speculative draft/verify rounds executed.
# TYPE areal_spec_rounds_total counter
areal_spec_rounds_total 50
# HELP areal_spec_draft_tokens_total Draft tokens proposed, by source.
# TYPE areal_spec_draft_tokens_total counter
areal_spec_draft_tokens_total{source="ngram"} 150
areal_spec_draft_tokens_total{source="radix"} 50
# HELP areal_spec_accepted_tokens_total Draft tokens accepted by the verifier.
# TYPE areal_spec_accepted_tokens_total counter
areal_spec_accepted_tokens_total 120
# HELP areal_spec_accepted_length Accepted draft-prefix length per slot-round.
# TYPE areal_spec_accepted_length histogram
areal_spec_accepted_length_bucket{le="+Inf"} 60
areal_spec_accepted_length_sum 120
areal_spec_accepted_length_count 60
# HELP areal_spec_rollback_pages_total KV pages rolled back after rejection.
# TYPE areal_spec_rollback_pages_total counter
areal_spec_rollback_pages_total 9
# HELP areal_train_phase_seconds Wall-clock seconds per training-step phase.
# TYPE areal_train_phase_seconds histogram
areal_train_phase_seconds_bucket{phase="rollout_wait",le="+Inf"} 4
areal_train_phase_seconds_sum{phase="rollout_wait"} 6.0
areal_train_phase_seconds_count{phase="rollout_wait"} 4
areal_train_phase_seconds_bucket{phase="forward_backward",le="+Inf"} 4
areal_train_phase_seconds_sum{phase="forward_backward"} 2.0
areal_train_phase_seconds_count{phase="forward_backward"} 4
# HELP areal_train_bubble_fraction rollout_wait / step wall time.
# TYPE areal_train_bubble_fraction gauge
areal_train_bubble_fraction 0.6
# HELP areal_train_mfu Model FLOPs utilization over the compute window.
# TYPE areal_train_mfu gauge
areal_train_mfu 0.35
# HELP areal_train_tokens_per_sec_per_chip Trained tokens/s per chip.
# TYPE areal_train_tokens_per_sec_per_chip gauge
areal_train_tokens_per_sec_per_chip 5200
# HELP areal_hbm_headroom_fraction Free fraction of device memory.
# TYPE areal_hbm_headroom_fraction gauge
areal_hbm_headroom_fraction 0.25
# HELP areal_xla_compiles_total XLA backend compilations.
# TYPE areal_xla_compiles_total counter
areal_xla_compiles_total 12
# HELP areal_xla_compile_seconds Per-compilation backend compile time.
# TYPE areal_xla_compile_seconds histogram
areal_xla_compile_seconds_bucket{le="+Inf"} 12
areal_xla_compile_seconds_sum 30.0
areal_xla_compile_seconds_count 12
# HELP areal_train_lag_token_share Bucket share of last update's tokens.
# TYPE areal_train_lag_token_share gauge
areal_train_lag_token_share{lag_bucket="0"} 0.5
areal_train_lag_token_share{lag_bucket="4+"} 0.25
# HELP areal_train_lag_clip_ratio Clip fraction by version-lag bucket.
# TYPE areal_train_lag_clip_ratio gauge
areal_train_lag_clip_ratio{lag_bucket="0"} 0.05
areal_train_lag_clip_ratio{lag_bucket="4+"} 0.85
# HELP areal_train_lag_behave_kl Mean behave |KL| by version-lag bucket.
# TYPE areal_train_lag_behave_kl gauge
areal_train_lag_behave_kl{lag_bucket="0"} 0.01
areal_train_lag_behave_kl{lag_bucket="4+"} 0.62
# HELP areal_train_lag_cap_hit_share Cap-hit tail mass by lag bucket.
# TYPE areal_train_lag_cap_hit_share gauge
areal_train_lag_cap_hit_share{lag_bucket="0"} 0.0
areal_train_lag_cap_hit_share{lag_bucket="4+"} 0.2
# HELP areal_lineage_records_total Trajectory lineage records registered.
# TYPE areal_lineage_records_total counter
areal_lineage_records_total 9
# HELP areal_lineage_joined_total Lineage records joined to step stats.
# TYPE areal_lineage_joined_total counter
areal_lineage_joined_total 6
"""


def self_test() -> int:
    """End-to-end smoke: fake target -> scrape -> merge -> render."""
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class Handler(BaseHTTPRequestHandler):
        def do_GET(self):  # noqa: N802 — http.server API
            body = _FAKE_EXPOSITION.encode()
            self.send_response(200 if self.path == "/metrics" else 404)
            self.send_header("Content-Type", "text/plain; charset=utf-8")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            if self.path == "/metrics":
                self.wfile.write(body)

        def log_message(self, *a):
            pass

    srv = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    target = f"127.0.0.1:{srv.server_address[1]}"
    try:
        # two live targets sharing one backend: merge must sum them, and a
        # third dead target must not stall or fail the round
        agg = FleetAggregator(
            [target, target, "127.0.0.1:1"], timeout=2.0, retries=0
        )
        t0 = time.monotonic()
        snap = agg.scrape_once()
        elapsed = time.monotonic() - t0
        frame = render_frame(snap)
        checks = [
            (snap.n_up == 2, f"expected 2 targets up, got {snap.n_up}"),
            (
                _merged_value(snap, "areal_rollout_capacity") == 14,
                "gauge merge: capacity should sum to 14",
            ),
            (
                _merged_value(snap, "areal_decode_generated_tokens_total")
                == 2468,
                "counter merge: tokens should sum to 2468",
            ),
            (
                elapsed < 10.0,
                f"dead target stalled the round ({elapsed:.1f}s)",
            ),
            ("staleness capacity" in frame, "frame missing capacity row"),
            (
                "prefix hit rate" in frame and "80.0%" in frame,
                "frame missing prefix hit-rate row (800/(800+200) per "
                "target merges to the same 80% ratio)",
            ),
            ("update pause (mean s)" in frame, "frame missing pause row"),
            (
                "decode step phases (mean s)" in frame,
                "frame missing decode phase panel",
            ),
            (
                "dispatch (dominant)" in frame,
                "dispatch (0.05 mean) should be highlighted as the "
                "dominant decode phase over device_wait (0.02)",
            ),
            (
                "ttft p50/p99 (s)" in frame,
                "frame missing timeline ttft quantile row",
            ),
            (
                "tpot p50/p99 (s)" in frame,
                "frame missing timeline tpot quantile row",
            ),
            (
                abs(
                    (
                        _histogram_quantile(
                            snap, "areal_request_ttft_seconds", 0.5
                        )
                        or 0.0
                    )
                    - 0.03125
                )
                < 1e-9,
                "ttft p50 should interpolate to 0.03125 (target 10 of 16 "
                "in the 0.05 bucket)",
            ),
            (
                "fence stall (mean s)" in frame and "0.050" in frame,
                "frame missing fence-stall row (0.2/4 = 0.050)",
            ),
            (
                "flight events" in frame
                and _flight_total(snap) == 10,
                "flight events should sum kinds across targets (2x(3+2))",
            ),
            (
                "lifecycle queue" in frame,
                "frame missing lifecycle queue-depth row",
            ),
            (
                "router decisions" in frame
                and _merged_value(snap, "areal_router_decisions_total")
                == 20,
                "router decisions should sum reasons across targets "
                "(2x(6+3+1))",
            ),
            (
                "prefix_overlap" in frame,
                "frame missing top decision-reason rows",
            ),
            (
                "router hit pred/actual" in frame and "12 / 10" in frame,
                "frame missing predicted-vs-actual router hit row "
                "(2x6 / 2x5)",
            ),
            (
                _shed_total(snap) == 20,
                "shed total: gateway (5+1) + admission (4) per target "
                "should merge to 20",
            ),
            (
                "autopilot decisions" in frame
                and _merged_value(snap, "areal_autopilot_decisions_total")
                == 8,
                "autopilot decisions should sum controller/reason children "
                "across targets (2x(3+1))",
            ),
            (
                "queue_wait_high" in frame,
                "frame missing autopilot decision-reason rows",
            ),
            (
                "set max_queue_depth" in frame and "16" in frame,
                "frame missing autopilot setpoint row (a per-plane fact: "
                "16, never the 32 a fleet merge-sum would claim)",
            ),
            (
                "admission acted" in frame and "12s ago" in frame,
                "frame missing per-controller last-action age row (12s "
                "per target must stay 12s, not merge-sum to 24)",
            ),
            (
                "cache acted" in frame and "never" in frame,
                "a controller that never acted must read 'never', not a "
                "negative age",
            ),
            (
                "shed/rejected (429)" in frame and "20" in frame,
                "frame missing shed/rejected row",
            ),
            (
                "gateway shards" in frame and "3" in frame,
                "frame missing gateway-tier panel (shard count is a "
                "membership FACT: 3 per scrape must stay 3, never the 6 "
                "a fleet merge-sum would claim)",
            ),
            (
                "route recoveries" in frame
                and _merged_value(
                    snap, "areal_gateway_shard_route_recoveries_total"
                )
                == 8,
                "frame missing affinity-repair row (counters are "
                "additive: 2x4 = 8)",
            ),
            (
                "stale membership" in frame,
                "frame missing degraded-discovery row",
            ),
            (
                "sessions gw0" in frame and "sessions gw1" in frame,
                "frame missing per-shard session balance rows (gauge "
                "children keyed by shard, max across scrapes)",
            ),
            (
                "preemptions" in frame
                and _merged_value(snap, "areal_preemption_total") == 6,
                "preemption total should sum roles across targets (2x(1+2))",
            ),
            (
                "drain (mean s)" in frame and "2.00" in frame,
                "frame missing drain row (6.0/3 = 2.00 mean)",
            ),
            (
                "ckpt pause sync (s)" in frame
                and "ckpt pause async (s)" in frame
                and "2.500" in frame
                and "0.100" in frame,
                "frame missing per-mode ckpt pause rows (sync 5.0/2, "
                "async 0.4/4)",
            ),
            (
                "journal replay/stale" in frame and "14 / 2" in frame,
                "frame missing journal replay row (2x7 / 2x1)",
            ),
            (
                "trainer step phases (mean s)" in frame
                and "rollout_wait (bubble)" in frame
                and "1.500" in frame,
                "frame missing trainer phase rows (rollout_wait mean "
                "6.0/4 = 1.500, merged across targets)",
            ),
            (
                "bubble fraction" in frame and "60.0%" in frame,
                "frame missing bubble-fraction row (per-target MEAN of "
                "0.6, not the 1.2 a fleet sum would claim)",
            ),
            (
                "mfu" in frame and "35.0%" in frame,
                "frame missing mfu row (per-target mean of 0.35)",
            ),
            (
                "train tok/s/chip" in frame and "5200.0" in frame,
                "frame missing train tok/s/chip row",
            ),
            (
                "hbm headroom (worst)" in frame and "25.0%" in frame,
                "frame missing hbm-headroom row (worst replica, 0.25)",
            ),
            (
                "xla compiles" in frame
                and _merged_value(snap, "areal_xla_compiles_total") == 24,
                "frame missing compile-count row (12 per target sums to 24)",
            ),
            (
                "xla compile time (s)" in frame and "60.0" in frame,
                "frame missing compile-time row (30.0s per target sums "
                "to 60.0)",
            ),
            (
                "spec rounds" in frame
                and _merged_value(snap, "areal_spec_rounds_total") == 100,
                "frame missing speculation panel (50 rounds per target "
                "sums to 100)",
            ),
            (
                "draft ngram" in frame and "draft radix" in frame,
                "frame missing per-source draft-token rows",
            ),
            (
                "spec acceptance rate" in frame and "60.0%" in frame,
                "frame missing acceptance-rate row (120 accepted / 200 "
                "drafted = 60.0%, ratio survives the fleet merge)",
            ),
            (
                "spec accepted len mean" in frame and "2.00" in frame,
                "frame missing accepted-length row (120/60 = 2.00)",
            ),
            (
                "spec rollback pages" in frame
                and _merged_value(snap, "areal_spec_rollback_pages_total")
                == 18,
                "frame missing rollback-pages row (counters sum: 2x9)",
            ),
            (
                "learning health by lag bucket" in frame
                and "lag 4+" in frame
                and "0.6200" in frame,
                "frame missing learning-health panel (per-target mean "
                "behave |KL| 0.62 in the 4+ bucket)",
            ),
            (
                "lineage joined/records" in frame and "12 / 18" in frame,
                "frame missing lineage join row (counters sum: 2x6 / 2x9)",
            ),
            ("DOWN  127.0.0.1:1" in frame, "frame missing down-target row"),
        ]
        failed = [msg for ok, msg in checks if not ok]
        print(frame)
        print("-" * 64)
        for ok, msg in checks:
            print(f"{'PASS' if ok else 'FAIL'}  {msg}")
        if failed:
            return 1
        print("self-test OK")
        return 0
    finally:
        srv.shutdown()
        srv.server_close()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument(
        "--targets",
        default="",
        help="comma-separated host:port /metrics endpoints",
    )
    p.add_argument(
        "--refresh", type=float, default=2.0, help="redraw period (s)"
    )
    p.add_argument(
        "--timeout", type=float, default=2.0, help="per-target scrape timeout"
    )
    p.add_argument(
        "--once", action="store_true", help="render one frame and exit"
    )
    p.add_argument(
        "--self-test",
        action="store_true",
        help="run against a built-in fake target (CI smoke)",
    )
    args = p.parse_args(argv)
    if args.self_test:
        return self_test()
    targets = [t for t in args.targets.split(",") if t]
    if not targets:
        p.error("--targets required (or --self-test)")
    return run_dashboard(
        targets, refresh=args.refresh, once=args.once, timeout=args.timeout
    )


if __name__ == "__main__":
    raise SystemExit(main())
