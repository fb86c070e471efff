"""Many-client open-loop gateway goodput benchmark.

The standing scoreboard for ROADMAP item 3 (disaggregated, cache-aware
serving fleet): drive the OpenAI-compatible gateway with mixed
interactive/rollout priority traffic on per-request deadlines, at an
OPEN-LOOP arrival schedule (clients arrive on a clock, not when the
previous one finishes — so overload shows up as queueing/shedding, not as
a slower client), and report per class:

- p50/p99 TTFT (from the ``areal_timing`` extension the proxy stamps onto
  completions — the engine-side request-timeline breakdown)
- p50/p99 end-to-end latency
- goodput: tokens completed WITHIN deadline per second
- shed/429, deadline-reap, and error counts

as a JSON artifact (``--output``), so router changes (prefix-locality
routing, prefill/decode disaggregation) have a fixed number to move.

Usage:
    # self-contained local fleet (tiny model, CPU-safe) under chaos stalls:
    python -m areal_tpu.tools.bench_gateway --local --replicas 2 \
        --interactive 8 --rollout 8 --duration 20 -o report.json
    # against an existing gateway:
    python -m areal_tpu.tools.bench_gateway --gateway http://host:port \
        --admin-key KEY --interactive 64 --rollout 64 --duration 60
    # routing A/B (ROADMAP item 3): round_robin vs cache_aware on an
    # 80%-shared-prefix multi-turn-style workload, one report:
    python -m areal_tpu.tools.bench_gateway --ab --replicas 3 \
        --workload shared_prefix --duration 15 -o ab.json
    # gateway tier (ROADMAP item 8): 3 consistent-hash shards, one
    # hard-killed 2s into the measured window:
    python -m areal_tpu.tools.bench_gateway --local --gateways 3 \
        --kill-shard-at 2 -o tier.json
    # the tier acceptance A/B (1 vs 3 shards + kill twin, one report):
    python -m areal_tpu.tools.bench_gateway --tier-ab -o tier_ab.json
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import time
from dataclasses import dataclass, field
from typing import Any

# the self-contained local fleet serves the toy char tokenizer — the bench
# measures serving latency, not tokenization; real deployments pass
# --gateway at a fleet whose proxies run the production tokenizer
from areal_tpu.api import wire
from areal_tpu.infra.rpc.echo_engine import CharTokenizer  # noqa: F401
from areal_tpu.utils import logging as alog

logger = alog.getLogger("bench_gateway")

PRIORITIES = ("interactive", "rollout")

# time-varying open-loop arrival profiles: (fraction_of_duration,
# relative_rate) segments. "step" doubles down mid-run, "diurnal" ramps
# up and back (the traffic shape the fleet autoscaler tracks), "burst"
# is a calm fleet hit by a 6x spike — the shape a static admission
# config must lose on somewhere (shed the calm or drown in the spike).
LOAD_PROFILES: dict[str, list[tuple[float, float]]] = {
    "step": [(0.5, 1.0), (0.5, 3.0)],
    # a real night: the trough runs at ~5% of the peak rate, so a
    # load-following fleet has genuine idle capacity to return
    "diurnal": [(0.3, 0.25), (0.25, 2.0), (0.25, 5.0), (0.2, 1.0)],
    "burst": [(0.4, 1.0), (0.2, 6.0), (0.4, 1.0)],
}


def profile_arrivals(
    n: int, duration_s: float, segments: list[tuple[float, float]]
) -> list[float]:
    """Client arrival offsets in [0, duration_s) following the piecewise-
    constant relative rate (inverse CDF of the integrated rate, midpoint
    rule — n clients land exactly where the profile says the traffic
    is). A uniform profile reproduces the legacy even spread."""
    total = sum(f * w for f, w in segments) or 1.0
    out: list[float] = []
    for i in range(n):
        u = (i + 0.5) / max(1, n) * total
        t, start, cum = 1.0, 0.0, 0.0
        for f, w in segments:
            seg = f * w
            if seg > 0 and cum + seg >= u:
                t = start + (u - cum) / w
                break
            start += f
            cum += seg
        out.append(min(duration_s, t * duration_s))
    return out


def resolve_load_profile(
    profile: str | list | None,
) -> list[tuple[float, float]] | None:
    if profile is None:
        return None
    if isinstance(profile, str):
        if profile in ("", "uniform"):
            return None
        return LOAD_PROFILES[profile]
    return [(float(f), float(w)) for f, w in profile]


def make_shared_prefix_prompts(
    n: int,
    shared_frac: float = 0.8,
    total_chars: int = 400,
    seed: int = 11,
) -> list[str]:
    """The router scoreboard's workload: ``n`` prompts sharing the first
    ``shared_frac`` of their characters (the CharTokenizer maps one char
    to one token, so this IS an 80%-shared token prefix) with unique
    suffixes — the multi-turn-agent shape where prefix-locality routing
    pays: replicas that already hold the shared prefix's KV pages prefill
    only the suffix."""
    import random as _random
    import string

    rng = _random.Random(seed)
    alphabet = string.ascii_lowercase + " "
    shared_len = max(0, min(total_chars, int(total_chars * shared_frac)))
    shared = "".join(rng.choice(alphabet) for _ in range(shared_len))
    out = []
    for _ in range(n):
        sfx = "".join(
            rng.choice(alphabet) for _ in range(total_chars - shared_len)
        )
        out.append(shared + sfx)
    return out


def _percentile(values: list[float], q: float) -> float | None:
    if not values:
        return None
    xs = sorted(values)
    idx = min(len(xs) - 1, max(0, math.ceil(q * len(xs)) - 1))
    return xs[idx]


@dataclass
class _ClassStats:
    sent: int = 0
    completed: int = 0
    # shed_429 counts 429 RESPONSES (a retrying client can collect several
    # before admission and shed_429 may exceed sent); shed_requests counts
    # requests that were shed at least once — the router-comparison ratio
    shed_429: int = 0
    shed_requests: int = 0
    deadline_reaped: int = 0
    errors: int = 0
    ttft_s: list[float] = field(default_factory=list)
    e2e_s: list[float] = field(default_factory=list)
    tokens: int = 0
    tokens_within_deadline: int = 0

    def report(self, duration_s: float) -> dict[str, Any]:
        return {
            "sent": self.sent,
            "completed": self.completed,
            "shed_429": self.shed_429,
            "shed_requests": self.shed_requests,
            "deadline_reaped": self.deadline_reaped,
            "errors": self.errors,
            "ttft_p50_s": _percentile(self.ttft_s, 0.50),
            "ttft_p99_s": _percentile(self.ttft_s, 0.99),
            "e2e_p50_s": _percentile(self.e2e_s, 0.50),
            "e2e_p99_s": _percentile(self.e2e_s, 0.99),
            "tokens": self.tokens,
            "tokens_within_deadline": self.tokens_within_deadline,
            "goodput_tok_s": (
                self.tokens_within_deadline / duration_s if duration_s > 0 else 0.0
            ),
        }


class _TierResolver:
    """Session-key -> gateway-shard placement for the tier bench.

    Wraps :class:`~areal_tpu.openai.proxy.tier.TierClient` (the ring +
    circuit machinery every tier client threads through) and keeps the
    per-shard goodput scoreboard: each client attributes its
    within-deadline tokens to the shard that served them (the
    ``x-areal-gateway-shard`` response header), so the artifact shows
    load re-hashing onto survivors after a kill."""

    def __init__(self, tier):
        self.tier = tier
        self._client = tier.client()
        self.shard_tokens: dict[str, int] = {}
        self.failovers = 0

    def pick(self, session_key: str, exclude: tuple[str, ...] = ()):
        return self._client.pick(session_key, exclude)

    def note_failure(self, addr: str) -> None:
        self.failovers += 1
        self._client.note_failure(addr)

    def note_success(self, addr: str) -> None:
        self._client.note_success(addr)

    def note_tokens(self, shard_id: str, n: int) -> None:
        if shard_id:
            self.shard_tokens[shard_id] = self.shard_tokens.get(shard_id, 0) + n

    def report(self, duration_s: float) -> dict[str, Any]:
        return {
            "per_shard_goodput_tok_s": {
                sid: (tok / duration_s if duration_s > 0 else 0.0)
                for sid, tok in sorted(self.shard_tokens.items())
            },
            "failovers": self.failovers,
        }


async def _one_client(
    http,
    gateway_url: str,
    admin_key: str,
    priority: str,
    deadline_s: float,
    max_completion_tokens: int,
    prompt: str,
    stats: _ClassStats,
    turns: int = 1,
    greedy: bool = False,
    resolver: _TierResolver | None = None,
    client_id: int = 0,
) -> None:
    """One open-loop client: session -> ``turns`` sequential prioritized
    chat completions -> end session, honoring 429 Retry-After inside the
    deadline budget. With ``turns > 1`` this is a multi-turn episode: each
    turn appends the assistant's reply plus a follow-up message, so turn
    t's prompt extends turn t-1's — the conversation-history locality
    that prefix-aware routing exploits (and round-robin re-prefills on a
    cold replica ~(N-1)/N of the time).
    With a ``resolver`` (the tier bench) the session hashes to ONE gateway
    shard for its whole lifetime; a connection-refused shard (killed
    mid-run) is reported into the circuit machinery and the request
    re-hashes to the ring successor, where route adoption resumes the
    session — the request must never end responseless.
    The session ends on EVERY exit path: an abandoned session burns one of
    the proxy's capacity units forever, and a bench that leaks capacity
    under sustained overload corrupts its own scoreboard (start_session
    eventually 429s and every later client counts as an error)."""
    import aiohttp

    stats.sent += 1
    t0 = time.monotonic()
    budget_end = t0 + deadline_s
    key = None
    session_key = f"bench-{priority}-{client_id}"
    pick = resolver.pick(session_key) if resolver is not None else None
    shard_tokens: dict[str, int] = {}

    async def post(path: str, body: dict, headers: dict):
        """POST returning (status, headers, json-or-None). Without a
        resolver this is a single attempt against ``gateway_url`` — the
        pre-tier behavior, byte for byte. With one, a refused connection
        re-picks past the dead shard and retries (bounded)."""
        nonlocal pick
        tried: list[str] = []
        for _ in range(4):
            if pick is not None:
                base = pick.url
                headers = dict(headers)
                headers[wire.GATEWAY_EXPECT_SHARD_HEADER] = pick.shard_id
            else:
                base = gateway_url
            try:
                async with http.post(
                    f"{base}{path}", json=body, headers=headers
                ) as r:
                    payload = (
                        await r.json(content_type=None)
                        if r.status == 200
                        else None
                    )
                    if pick is not None:
                        resolver.note_success(pick.addr)
                    return r.status, r.headers, payload
            except (aiohttp.ClientConnectionError, OSError):
                if pick is None:
                    raise
                resolver.note_failure(pick.addr)
                tried.append(pick.addr)
                pick = resolver.pick(session_key, tuple(tried))
                if pick is None:
                    break
        raise ConnectionError("no reachable gateway shard")

    try:
        status, _hd, sess = await post(
            "/rl/start_session",
            {"task_id": f"bench-{priority}"},
            {"Authorization": f"Bearer {admin_key}"},
        )
        if status != 200:
            stats.errors += 1
            return
        key = sess["api_key"]
        headers = {
            "Authorization": f"Bearer {key}",
            wire.PRIORITY_HEADER: priority,
            wire.DEADLINE_HEADER: f"{time.time() + (budget_end - time.monotonic()):.6f}",
        }
        messages = [{"role": "user", "content": prompt}]
        was_shed = False
        session_tokens = 0
        reaped = False
        for turn in range(max(1, turns)):
            body = {
                "messages": messages,
                "max_completion_tokens": max_completion_tokens,
                "model": "bench",
            }
            if greedy:
                # deterministic decode lengths: an A/B comparing CONTROL
                # policies must not let sampling-dependent EOS timing
                # masquerade as a goodput difference between arms
                body["temperature"] = 0
            comp = None
            served_by = ""
            while True:
                status, hd, comp = await post(
                    "/v1/chat/completions", body, headers
                )
                if status == 429:
                    stats.shed_429 += 1
                    if not was_shed:
                        was_shed = True
                        stats.shed_requests += 1
                    # floor: a foreign gateway's "Retry-After: 0" must
                    # not hot-spin the bench into amplifying the
                    # overload; the RFC 7231 HTTP-date form falls back
                    # to the default rather than misclassifying the
                    # shed as an error
                    try:
                        ra = float(hd.get("Retry-After", "0.5") or 0.5)
                    except ValueError:
                        ra = 0.5
                    ra = max(0.05, ra)
                    if time.monotonic() + ra >= budget_end:
                        return  # budget exhausted while shed
                    await asyncio.sleep(ra)
                    continue
                if status != 200:
                    stats.errors += 1
                    return
                served_by = hd.get(wire.GATEWAY_SHARD_HEADER, "")
                break
            timing = comp.get("areal_timing") or {}
            usage = comp.get("usage") or {}
            n_tok = int(usage.get("completion_tokens") or 0)
            session_tokens += n_tok
            stats.tokens += n_tok
            if resolver is not None and served_by:
                shard_tokens[served_by] = (
                    shard_tokens.get(served_by, 0) + n_tok
                )
            if n_tok > 0 and timing.get("ttft_s"):
                # EVERY turn's TTFT enters the distribution — turns 2+
                # are exactly where prefix routing shows up (warm
                # suffix-only prefill vs a cold re-prefill of the whole
                # history). Zero-token completions (queued-expiry reaps)
                # never emitted a first token: their fallback ttft is the
                # full wall latency and would saturate p99 at the
                # deadline — counted by deadline_reaped, not the TTFT dist
                stats.ttft_s.append(float(timing["ttft_s"]))
            if (
                timing.get("truncated_by") == "deadline"
                or timing.get("stop_reason") == "deadline"
            ):
                reaped = True
                break
            messages = messages + [
                {
                    "role": "assistant",
                    "content": comp["choices"][0]["message"]["content"] or "",
                },
                {"role": "user", "content": f"go deeper on part {turn + 2}"},
            ]
        e2e = time.monotonic() - t0
        stats.completed += 1
        stats.e2e_s.append(e2e)
        if reaped:
            stats.deadline_reaped += 1
        elif e2e <= deadline_s:
            stats.tokens_within_deadline += session_tokens
            if resolver is not None:
                # per-shard goodput uses the same within-deadline rule as
                # the class totals, attributed to the serving shard
                for sid, tok in shard_tokens.items():
                    resolver.note_tokens(sid, tok)
    except Exception as e:  # noqa: BLE001 — one client's failure is a data
        # point (errors count), not a bench abort
        logger.debug(f"bench client failed: {e!r}")
        stats.errors += 1
    finally:
        if key is not None:
            try:
                await post(
                    "/rl/end_session",
                    {},
                    {"Authorization": f"Bearer {key}"},
                )
            except Exception as e:  # noqa: BLE001 — best-effort release
                logger.debug(f"end_session failed: {e!r}")


async def drive_gateway(
    gateway_url: str,
    admin_key: str,
    n_interactive: int,
    n_rollout: int,
    duration_s: float,
    interactive_deadline_s: float = 20.0,
    rollout_deadline_s: float = 30.0,
    interactive_tokens: int = 16,
    rollout_tokens: int = 128,
    interactive_prompts: list[str] | None = None,
    rollout_prompts: list[str] | None = None,
    turns: int = 1,
    rounds: int = 1,
    load_profile: str | list | None = None,
    greedy: bool = False,
    resolver: _TierResolver | None = None,
) -> dict[str, Any]:
    """Open-loop drive: each class's clients start on a fixed arrival
    schedule spread over ``duration_s``. ``*_prompts`` override the default
    single prompt per class (client i takes prompts[i % len]) — the
    shared-prefix router workload rides through here; ``turns`` makes each
    client a multi-turn episode. ``rounds`` repeats the whole schedule
    back-to-back into ONE aggregated report (the A/B uses it to average
    out scheduling transients). ``load_profile`` (a LOAD_PROFILES name or
    explicit (time_fraction, relative_rate) segments) makes the arrival
    rate time-varying — the overload-study / autopilot-acceptance shape;
    None keeps the legacy even spread. A ``resolver`` (gateway tier mode)
    hashes each session to a shard and survives shard death; without one
    every request hits ``gateway_url``. Returns the report dict."""
    import aiohttp

    stats = {p: _ClassStats() for p in PRIORITIES}
    segments = resolve_load_profile(load_profile)
    t_start = time.monotonic()

    async def schedule(priority, n, deadline_s, max_tokens, prompts, t0, rnd):
        offsets = (
            profile_arrivals(n, duration_s, segments)
            if segments is not None
            else [i * duration_s / max(1, n) for i in range(n)]
        )
        async with aiohttp.ClientSession() as http:
            tasks = []
            for i in range(n):
                target = t0 + offsets[i]
                delay = max(0.0, target - time.monotonic())
                if delay:
                    await asyncio.sleep(delay)
                tasks.append(
                    asyncio.ensure_future(
                        _one_client(
                            http,
                            gateway_url,
                            admin_key,
                            priority,
                            deadline_s,
                            max_tokens,
                            # rounds walk forward through the prompt list so
                            # a replayed schedule still sees fresh suffixes
                            prompts[(rnd * n + i) % len(prompts)],
                            stats[priority],
                            turns=turns,
                            greedy=greedy,
                            resolver=resolver,
                            client_id=rnd * n + i,
                        )
                    )
                )
            await asyncio.gather(*tasks)

    for rnd in range(max(1, rounds)):
        t0 = time.monotonic()
        await asyncio.gather(
            schedule(
                "interactive",
                n_interactive,
                interactive_deadline_s,
                interactive_tokens,
                interactive_prompts or ["ping?"],
                t0,
                rnd,
            ),
            schedule(
                "rollout",
                n_rollout,
                rollout_deadline_s,
                rollout_tokens,
                rollout_prompts or ["solve this problem step by step please"],
                t0,
                rnd,
            ),
        )
    wall = time.monotonic() - t_start
    report = {
        "bench": "gateway_goodput",
        "gateway": gateway_url,
        "duration_s": round(wall, 3),
        "classes": {p: stats[p].report(wall) for p in PRIORITIES},
    }
    if segments is not None:
        # the piecewise schedule rides the artifact so a report is
        # self-describing (which seconds were the spike)
        report["load_profile"] = {
            "name": load_profile if isinstance(load_profile, str) else "custom",
            "segments": [[f, w] for f, w in segments],
        }
    tot = _ClassStats()
    for s in stats.values():
        tot.sent += s.sent
        tot.completed += s.completed
        tot.shed_429 += s.shed_429
        tot.shed_requests += s.shed_requests
        tot.deadline_reaped += s.deadline_reaped
        tot.errors += s.errors
        tot.ttft_s += s.ttft_s
        tot.e2e_s += s.e2e_s
        tot.tokens += s.tokens
        tot.tokens_within_deadline += s.tokens_within_deadline
    report["totals"] = tot.report(wall)
    return report


# ---------------------------------------------------------------------------
# self-contained local fleet (tiny model; CPU-safe) under chaos stalls
# ---------------------------------------------------------------------------


class LocalFleet:
    """N engine replicas + rollout client + OpenAI proxy + gateway, all
    in-process — the 2-replica-under-chaos configuration the ISSUE's
    acceptance scenario names. ``start`` returns (gateway_url, admin_key)."""

    def __init__(
        self,
        n_replicas: int = 2,
        max_batch_size: int = 4,
        chaos_stall_prob: float = 0.3,
        chaos_stall_s: float = 0.1,
        max_queue_depth: int = 32,
        retry_after_s: float = 0.1,
        gateway_max_inflight: int = 0,
        gateway_interactive_headroom: int = 0,
        seed: int = 7,
        route_policy: str = "round_robin",
        max_seq_len: int = 512,
        routing_kw: dict | None = None,
        model: str = "tiny",
        autopilot_cfg: Any = None,
        n_gateways: int = 1,
    ):
        self.n_replicas = n_replicas
        self.n_gateways = n_gateways
        self.tier = None
        self.max_batch_size = max_batch_size
        self.chaos_stall_prob = chaos_stall_prob
        self.chaos_stall_s = chaos_stall_s
        self.max_queue_depth = max_queue_depth
        self.retry_after_s = retry_after_s
        self.gateway_max_inflight = gateway_max_inflight
        self.gateway_interactive_headroom = gateway_interactive_headroom
        self.seed = seed
        self.route_policy = route_policy
        self.max_seq_len = max_seq_len
        self.routing_kw = dict(routing_kw or {})
        self.model = model
        self.autopilot_cfg = autopilot_cfg
        self.autopilot = None
        self.gw_state = None
        self.servers: list[Any] = []
        self.client = None
        self._proxy_runner = None
        self._gateway_runner = None
        self.admin_key = "bench-admin"
        self.gateway_url = ""
        self.proxy_url = ""
        self._act_stop: Any = None
        self._act_samples: list[int] = []

    async def astart(self) -> tuple[str, str]:
        import jax
        from aiohttp import web

        from areal_tpu.api.config import (
            ChaosConfig,
            InferenceEngineConfig,
            MeshConfig,
            RequestLifecycleConfig,
            RoutingConfig,
            ServerConfig,
        )
        from areal_tpu.inference.client import RemoteJaxEngine
        from areal_tpu.inference.decode_engine import DecodeEngine
        from areal_tpu.inference.server import ServerThread
        from areal_tpu.models import qwen
        from areal_tpu.openai.proxy.gateway import (
            GatewayState,
            create_gateway_app,
        )
        from areal_tpu.openai.proxy.rollout_server import (
            ProxyState,
            create_proxy_app,
        )
        from areal_tpu.robustness import FaultInjector
        from areal_tpu.utils.network import find_free_port

        from areal_tpu.tools.validate_installation import tiny_model_config

        if self.model == "small":
            # prefill-costly bench model (the routing A/B): on the toy
            # 32-dim model a 700-token prefill costs single-digit ms, so
            # there is nothing for prefix routing to save — this one makes
            # prompt prefill the dominant per-request cost, like real
            # serving, while still CPU-feasible
            tiny = qwen.ModelConfig(
                vocab_size=128,
                hidden_size=128,
                intermediate_size=512,
                num_layers=4,
                num_heads=4,
                num_kv_heads=2,
                dtype="float32",
                tie_word_embeddings=True,
                rope_theta=10000.0,
            )
        else:
            tiny = tiny_model_config()
        params = qwen.init_params(jax.random.PRNGKey(0), tiny)
        for i in range(self.n_replicas):
            cfg = ServerConfig(
                max_batch_size=self.max_batch_size,
                max_seq_len=self.max_seq_len,
                decode_steps_per_call=4,
                # a real (shared-pool) page budget instead of the dense-
                # equivalent default: the radix cache may hold up to half
                # of it, so cross-request prefix reuse isn't evicted by a
                # handful of concurrent sessions (the router workload's
                # whole premise). The bigger bench model carries a bigger
                # per-page cost, so its budget scales to keep a few dozen
                # session prefixes resident.
                kv_hbm_gb=0.1 if self.model == "small" else 0.005,
                seed=self.seed + i,
                mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
                lifecycle=RequestLifecycleConfig(
                    max_queue_depth=self.max_queue_depth,
                    retry_after_s=self.retry_after_s,
                    watchdog_s=60.0,
                ),
            )
            eng = DecodeEngine(cfg, params=params, model_cfg=tiny)
            eng.initialize()
            st = ServerThread(cfg, eng)
            st.start()
            self.servers.append(st)
        self.client = RemoteJaxEngine(
            InferenceEngineConfig(
                max_concurrent_rollouts=64,
                consumer_batch_size=8,
                max_head_offpolicyness=1000,
                request_timeout=120,
                request_retries=3,
                routing_policy=self.route_policy,
                # short bench: snapshots must refresh well inside the run
                routing=RoutingConfig(
                    poll_interval_s=0.5, **self.routing_kw
                ),
            ),
            addresses=[s.address for s in self.servers],
        )
        self.client.initialize()
        if self.chaos_stall_prob > 0:
            self.client.install_fault_injector(
                FaultInjector(
                    ChaosConfig(
                        enabled=True,
                        seed=self.seed,
                        stall_prob=self.chaos_stall_prob,
                        stall_s=self.chaos_stall_s,
                        path_prefix="/generate",
                    )
                )
            )
        proxy_state = ProxyState(
            self.client,
            CharTokenizer(),
            admin_api_key=self.admin_key,
            capacity=4096,
        )
        self._proxy_runner = web.AppRunner(create_proxy_app(proxy_state))
        await self._proxy_runner.setup()
        pport = find_free_port()
        await web.TCPSite(self._proxy_runner, "127.0.0.1", pport).start()
        self.proxy_url = f"http://127.0.0.1:{pport}"
        if self.n_gateways > 1:
            # the horizontally-sharded tier: N gateway shards over this
            # one proxy, membership in a PRIVATE memory repo (concurrent
            # benches must not cross-pollinate the process-wide default)
            from areal_tpu.api.config import GatewayTierConfig
            from areal_tpu.openai.proxy.tier import GatewayTier
            from areal_tpu.utils import name_resolve

            self.tier = GatewayTier(
                [self.proxy_url],
                self.admin_key,
                cfg=GatewayTierConfig(
                    enabled=True,
                    n_shards=self.n_gateways,
                    membership_ttl_s=2.0,
                    membership_poll_s=0.25,
                ),
                max_inflight=self.gateway_max_inflight,
                interactive_headroom=self.gateway_interactive_headroom,
                retry_after_s=0.2,
                repo=name_resolve.MemoryNameResolveRepo(),
            )
            await self.tier.astart()
            # the plain-URL consumers (greedy probes) pin shard 0
            self.gateway_url = f"http://{self.tier.addresses()[0]}"
            self.gw_state = next(iter(self.tier.shards.values())).state
        else:
            gw_state = GatewayState(
                [self.proxy_url],
                admin_api_key=self.admin_key,
                max_inflight=self.gateway_max_inflight,
                interactive_headroom=self.gateway_interactive_headroom,
                retry_after_s=0.2,
            )
            self._gateway_runner = web.AppRunner(create_gateway_app(gw_state))
            await self._gateway_runner.setup()
            gport = find_free_port()
            await web.TCPSite(self._gateway_runner, "127.0.0.1", gport).start()
            self.gateway_url = f"http://127.0.0.1:{gport}"
            self.gw_state = gw_state
        if self.autopilot_cfg is not None and self.autopilot_cfg.enabled:
            # the goodput autopilot over this fleet: knob pushes over HTTP
            # like production, the gateway headroom via the in-process
            # hook (the gateway lives in the controller process there too)
            from areal_tpu.autopilot import Autopilot

            self.autopilot = Autopilot(
                self.autopilot_cfg,
                lambda: [s.address for s in self.servers],
                gateway=self.gw_state,
                gateway_tier=self.tier,
            )
            self.autopilot.seed_setpoints(
                max_queue_depth=self.max_queue_depth,
                gateway_interactive_headroom=self.gateway_interactive_headroom,
            )
            self.autopilot.start()
        return self.gateway_url, self.admin_key

    async def astop(self) -> None:
        from areal_tpu.inference.client import close_loop_sessions

        if self.autopilot is not None:
            self.autopilot.stop()
        if self.tier is not None:
            await self.tier.astop()
        if self._gateway_runner is not None:
            await self._gateway_runner.cleanup()
        if self._proxy_runner is not None:
            await self._proxy_runner.cleanup()
        if self.client is not None:
            self.client.destroy()
        # the proxy drove agenerate on THIS loop: close its cached session
        # (destroy only reaches the client's executor-loop cache)
        await close_loop_sessions()
        for st in self.servers:
            st.stop()

    # -- fleet-activity accounting (the autoscaler scoreboard) -------------
    def start_activity_sampler(self, period_s: float = 0.25) -> None:
        """Sample the count of non-draining replicas on a wall clock so
        the report can price goodput per replica-second — the number the
        fleet controller must move (drained capacity is returned
        capacity)."""
        import threading

        stop = threading.Event()
        self._act_stop = stop
        self._act_samples = []

        def run():
            while not stop.wait(period_s):
                self._act_samples.append(
                    sum(1 for st in self.servers if not st.engine.is_draining)
                )

        threading.Thread(target=run, daemon=True).start()

    def stop_activity_sampler(self) -> float | None:
        if self._act_stop is not None:
            self._act_stop.set()
            self._act_stop = None
        if not self._act_samples:
            return None
        return sum(self._act_samples) / len(self._act_samples)

    def mark_baseline(self) -> None:
        """Snapshot the cumulative engine counters so ``engine_stats``
        reports deltas from here — the A/B measures its timed window, not
        the warm-up traffic before it."""
        self._baseline = {
            st.address: {
                k: st.engine.stats[k]
                for k in (
                    "generated_tokens",
                    "prefix_cache_hits",
                    "prefix_hit_tokens",
                    "prefill_tokens",
                )
            }
            for st in self.servers
        }

    def engine_stats(self) -> dict[str, Any]:
        """Fleet-level engine counters folded into the report (deadline
        reaps, timeline health, and the prefix-reuse numbers the routing
        A/B compares come from the engines themselves). Counters are
        deltas from ``mark_baseline`` when one was taken."""
        base = getattr(self, "_baseline", {})
        out: dict[str, Any] = {"replicas": []}
        hit_tokens = prefill_tokens = 0
        for st in self.servers:
            eng = st.engine
            b = base.get(st.address, {})

            def d(key: str) -> int:
                return eng.stats[key] - b.get(key, 0)

            hit_tokens += d("prefix_hit_tokens")
            prefill_tokens += d("prefill_tokens")
            out["replicas"].append(
                {
                    "address": st.address,
                    "generated_tokens": d("generated_tokens"),
                    "deadline_exceeded": eng.stats["deadline_exceeded"],
                    "prefix_cache_hits": d("prefix_cache_hits"),
                    "prefix_hit_tokens": d("prefix_hit_tokens"),
                    "prefill_tokens": d("prefill_tokens"),
                    "timelines": eng.timeline.stats(),
                }
            )
        # suffix-only prefill economics: warm tokens over all prompt
        # tokens admitted (cached + actually prefilled) — the number the
        # cache-aware arm must raise
        out["prefix_hit_tokens"] = hit_tokens
        out["prefill_tokens"] = prefill_tokens
        out["prefix_hit_rate"] = (
            hit_tokens / (hit_tokens + prefill_tokens)
            if (hit_tokens + prefill_tokens) > 0
            else None
        )
        return out


async def _greedy_probes(
    gateway_url: str,
    admin_key: str,
    prompts: list[str],
    max_tokens: int = 8,
) -> list[str]:
    """Sequential greedy (temperature=0) completions through the gateway.

    Dual duty in the A/B: the returned texts are the byte-identity
    evidence (routing is placement-only — greedy output must not depend
    on the policy), and running them BEFORE the timed drive warms both
    arms' compile caches (incl. the suffix-only prefill variant) so the
    measured window compares steady-state serving, not XLA compiles."""
    import aiohttp

    texts: list[str] = []
    async with aiohttp.ClientSession() as http:
        for i, prompt in enumerate(prompts):
            admin = {"Authorization": f"Bearer {admin_key}"}
            async with http.post(
                f"{gateway_url}/rl/start_session",
                json={"task_id": f"probe-{i}"},
                headers=admin,
            ) as r:
                sess = await r.json(content_type=None)
            key = sess["api_key"]
            headers = {"Authorization": f"Bearer {key}"}
            try:
                async with http.post(
                    f"{gateway_url}/v1/chat/completions",
                    json={
                        "messages": [{"role": "user", "content": prompt}],
                        "max_completion_tokens": max_tokens,
                        "temperature": 0,
                        "model": "bench",
                    },
                    headers=headers,
                ) as r:
                    # a failed probe is evidence, not an abort: a marker
                    # text keeps the byte-identity comparison meaningful
                    # (both arms see the same fleet, so a persistent error
                    # reproduces; a transient one shows as a mismatch)
                    if r.status != 200:
                        texts.append(f"<probe-error:{r.status}>")
                        continue
                    comp = await r.json(content_type=None)
                choices = comp.get("choices") or []
                msg = (choices[0].get("message") or {}) if choices else {}
                texts.append(
                    msg.get("content") or ("" if choices else "<probe-malformed>")
                )
            finally:
                async with http.post(
                    f"{gateway_url}/rl/end_session",
                    json={},
                    headers=headers,
                ):
                    pass
    return texts


def _workload_prompts(
    workload: str,
    n_interactive: int,
    n_rollout: int,
    shared_frac: float,
    prompt_chars: int,
    generation: int = 0,
    generations: int = 1,
) -> tuple[list[str] | None, list[str] | None]:
    if workload != "shared_prefix":
        return None, None
    # one shared family across BOTH classes (the agent-fleet shape: many
    # concurrent episodes over one system prompt/task template).
    # ``generation`` skips past earlier windows' suffix sets over the SAME
    # shared prefix — the warm-up and measured windows (and each measured
    # round, via ``generations``) must not replay identical prompts (a
    # full-prompt radix match would measure memoization, not prefix
    # routing). Suffixes are split per class so round r's interactive set
    # never collides with round r-1's rollout set.
    n = n_interactive + n_rollout
    prompts = make_shared_prefix_prompts(
        n * (generation + generations),
        shared_frac=shared_frac,
        total_chars=prompt_chars,
    )[n * generation :]
    ni_all = n_interactive * generations
    return prompts[:ni_all] or None, prompts[ni_all:] or None


async def run_local_bench(
    n_replicas: int = 2,
    n_interactive: int = 8,
    n_rollout: int = 8,
    duration_s: float = 15.0,
    workload: str = "mixed",
    shared_frac: float = 0.8,
    prompt_chars: int = 400,
    interactive_tokens: int = 16,
    rollout_tokens: int = 128,
    interactive_deadline_s: float = 20.0,
    rollout_deadline_s: float = 30.0,
    turns: int = 1,
    rounds: int = 1,
    probe_prompts: list[str] | None = None,
    warmup_s: float = 0.0,
    load_profile: str | list | None = None,
    greedy: bool = False,
    kill_shard_at: float | None = None,
    post_probe_prompts: list[str] | None = None,
    **fleet_kw: Any,
) -> dict[str, Any]:
    fleet = LocalFleet(n_replicas=n_replicas, **fleet_kw)
    try:
        gateway_url, admin_key = await fleet.astart()
        resolver = _TierResolver(fleet.tier) if fleet.tier is not None else None
        probe_texts = None
        if probe_prompts:
            probe_texts = await _greedy_probes(
                gateway_url, admin_key, probe_prompts
            )
        if warmup_s > 0:
            # uncounted steady-state warm-up: first-use XLA compiles (incl.
            # the suffix-only prefill variant at its batched shapes) and
            # the radix/shadow warm-up must not land inside the measured
            # window of either A/B arm. Its prompts share the prefix but
            # none of the suffixes of the measured set (generation 0 vs 1).
            warm_ip, warm_rp = _workload_prompts(
                workload,
                n_interactive,
                n_rollout,
                shared_frac,
                prompt_chars,
                generation=0,
            )
            # FULL client count: the warm-up must reach the same batched
            # admission shapes (prefill A_pad x bucket x page-table width)
            # as the measured window, or first-use compiles land in it
            await drive_gateway(
                gateway_url,
                admin_key,
                n_interactive=n_interactive,
                n_rollout=n_rollout,
                duration_s=warmup_s,
                interactive_tokens=interactive_tokens,
                rollout_tokens=rollout_tokens,
                interactive_deadline_s=interactive_deadline_s,
                rollout_deadline_s=rollout_deadline_s,
                interactive_prompts=warm_ip,
                rollout_prompts=warm_rp,
                turns=turns,
                greedy=greedy,
                resolver=resolver,
            )
        ip, rp = _workload_prompts(
            workload,
            n_interactive,
            n_rollout,
            shared_frac,
            prompt_chars,
            generation=1 if warmup_s > 0 else 0,
            generations=max(1, rounds),
        )
        fleet.mark_baseline()
        if resolver is not None:
            # the measured window's scoreboard starts clean (warm-up
            # traffic attributed tokens too)
            resolver.shard_tokens = {}
            resolver.failovers = 0
        killed_shard = None
        kill_handle = None
        if kill_shard_at is not None and fleet.tier is not None:
            # the deterministic chaos point: hard-kill one shard T seconds
            # into the measured window (highest shard id — stable across
            # runs, so the kill and no-kill twins differ ONLY in the kill)
            killed_shard = sorted(fleet.tier.shards)[-1]
            kill_handle = asyncio.get_running_loop().call_later(
                max(0.0, kill_shard_at), fleet.tier.kill_shard, killed_shard
            )
        fleet.start_activity_sampler()
        report = await drive_gateway(
            gateway_url,
            admin_key,
            n_interactive=n_interactive,
            n_rollout=n_rollout,
            duration_s=duration_s,
            interactive_tokens=interactive_tokens,
            rollout_tokens=rollout_tokens,
            interactive_deadline_s=interactive_deadline_s,
            rollout_deadline_s=rollout_deadline_s,
            interactive_prompts=ip,
            rollout_prompts=rp,
            turns=turns,
            rounds=rounds,
            load_profile=load_profile,
            greedy=greedy,
            resolver=resolver,
        )
        if kill_handle is not None:
            kill_handle.cancel()  # no-op if it already fired
        active_mean = fleet.stop_activity_sampler()
        report["workload"] = workload
        report["turns"] = turns
        report["route_policy"] = fleet.route_policy
        report["fleet"] = fleet.engine_stats()
        report["fleet"]["active_replicas_mean"] = active_mean
        goodput = report["totals"]["goodput_tok_s"]
        report["goodput_per_replica_tok_s"] = (
            goodput / active_mean if active_mean else None
        )
        report["router"] = fleet.client.router.stats()
        report["router_hit_rate"] = report["fleet"]["prefix_hit_rate"]
        # the control plane's scoreboard entry: active setpoints + the
        # decision ledger
        report["autopilot"] = (
            fleet.autopilot.status() if fleet.autopilot is not None else None
        )
        report["gateway_shards"] = fleet.n_gateways
        if resolver is not None:
            tier_report = resolver.report(report["duration_s"])
            tier_report["killed_shard"] = killed_shard
            tier_report["shard_stats"] = fleet.tier.shard_stats()
            report["gateway_tier"] = tier_report
        if probe_texts is not None:
            report["probe_texts"] = probe_texts
        if post_probe_prompts:
            # POST-drive identity evidence: in a kill run these greedy
            # completions ride a tier that already lost a shard — output
            # must still match the no-kill twin byte for byte (membership
            # moves placement, never sampling). Served from a live shard.
            url = (
                f"http://{fleet.tier.addresses()[0]}"
                if fleet.tier is not None
                else gateway_url
            )
            report["post_probe_texts"] = await _greedy_probes(
                url, admin_key, post_probe_prompts
            )
        return report
    finally:
        await fleet.astop()


async def run_ab(
    n_replicas: int = 3,
    n_interactive: int = 18,
    n_rollout: int = 18,
    duration_s: float = 4.0,
    workload: str = "shared_prefix",
    shared_frac: float = 0.1,
    # long unique base prompts (the A/B fleet runs a 1024-token context
    # and a prefill-costly bench model) with short completions: the
    # workload where prefix routing pays is prefill-dominated — the
    # multi-turn agent / RL-scoring shape. Short prompts + long decodes
    # are load-balancing's domain (the score's queue/inflight terms), not
    # a prefix-locality scoreboard.
    prompt_chars: int = 680,
    interactive_tokens: int = 4,
    rollout_tokens: int = 8,
    turns: int = 3,
    rounds: int = 2,
    **fleet_kw: Any,
) -> dict[str, Any]:
    """The routing scoreboard: one fresh fleet per arm (identical seeds,
    params, chaos schedule), round_robin then cache_aware, same
    80%-shared-prefix multi-turn workload, each arm warmed (probes + an
    uncounted drive) before its measured window.

    Workload shape: each session's base prompt is unique (plus a small
    fleet-global task preamble, ``shared_frac``); the ~80%+ prefix
    sharing is per-request CONVERSATION HISTORY — turn t's prompt extends
    turn t-1's sequence, so every turn past the first shares >85% of its
    tokens with state some replica already holds. That is the sharing a
    router can actually exploit: a fleet-global prefix replicates onto
    every replica within one warm-up pass and round-robin gets it for
    free, while session history lives on exactly ONE replica — blind
    rotation re-prefills it ~(N-1)/N of the time and prefix routing never
    does. Arrivals outpace service (open-loop saturation) so the saved
    prefill converts into wall-clock/goodput, not idle slots.

    The comparison block is what the driver reads: goodput, warm
    suffix-only prefill economics, and greedy byte-identity across arms
    (placement only, never output)."""
    # probes repeat 2 prompts x3 so every replica sees the shared prefix
    # at least once under round-robin too — compile + radix warm-up in
    # both arms, and 6 texts of identity evidence
    probe_prompts = make_shared_prefix_prompts(
        2, shared_frac=shared_frac, total_chars=prompt_chars, seed=97
    ) * 3
    arms: dict[str, dict[str, Any]] = {}
    for policy in ("round_robin", "cache_aware"):
        arms[policy] = await run_local_bench(
            n_replicas=n_replicas,
            n_interactive=n_interactive,
            n_rollout=n_rollout,
            duration_s=duration_s,
            workload=workload,
            shared_frac=shared_frac,
            prompt_chars=prompt_chars,
            interactive_tokens=interactive_tokens,
            rollout_tokens=rollout_tokens,
            turns=turns,
            rounds=rounds,
            probe_prompts=probe_prompts,
            warmup_s=max(2.0, duration_s / 2),
            route_policy=policy,
            max_seq_len=1024,
            model="small",
            **fleet_kw,
        )
    rr, ca = arms["round_robin"], arms["cache_aware"]
    comparison = {
        "goodput_tok_s": {
            "round_robin": rr["totals"]["goodput_tok_s"],
            "cache_aware": ca["totals"]["goodput_tok_s"],
        },
        "prefix_hit_rate": {
            "round_robin": rr["fleet"]["prefix_hit_rate"],
            "cache_aware": ca["fleet"]["prefix_hit_rate"],
        },
        "suffix_prefill_tokens": {
            "round_robin": rr["fleet"]["prefill_tokens"],
            "cache_aware": ca["fleet"]["prefill_tokens"],
        },
        "cache_aware_wins_goodput": (
            ca["totals"]["goodput_tok_s"] > rr["totals"]["goodput_tok_s"]
        ),
        "cache_aware_wins_prefill": (
            (ca["fleet"]["prefix_hit_rate"] or 0.0)
            > (rr["fleet"]["prefix_hit_rate"] or 0.0)
        ),
        "greedy_identical": rr.get("probe_texts") == ca.get("probe_texts"),
    }
    return {
        "bench": "gateway_route_ab",
        "workload": workload,
        "shared_frac": shared_frac,
        "prompt_chars": prompt_chars,
        "arms": arms,
        "comparison": comparison,
    }


async def run_tier_ab(
    n_replicas: int = 2,
    n_interactive: int = 90,
    n_rollout: int = 90,
    duration_s: float = 3.0,
    deadline_s: float = 20.0,
    shard_inflight: int = 2,
    kill_at_frac: float = 0.4,
    **fleet_kw: Any,
) -> dict[str, Any]:
    """The gateway-tier scoreboard (ISSUE 18 acceptance): the SAME fleet
    shape behind 1 gateway shard, 3 shards, and 3 shards with one killed
    mid-run.

    The workload is gateway-ADMISSION-bound by construction: each shard
    admits only ``shard_inflight`` concurrent completions (the per-process
    ceiling the tier exists to multiply), and per-request service time is
    dominated by a deterministic chaos stall on every engine call (wait,
    not compute — in-process shards share one CPU budget, so only
    latency-bound work can scale with admission slots, exactly like a
    production fleet whose gateway ceiling is connection/IO concurrency,
    not cycles). Demand is several times what ``shard_inflight`` slots
    can clear inside ``deadline_s``: the single-shard arm sheds clients
    out of their entire deadline budget while three shards clear the same
    demand in time. Scored on within-deadline goodput, the metric the
    whole gateway exists to protect; sub-linear scaling means the tier
    added contention on the request path (exactly what the shared-nothing
    design forbids).

    The kill twin asserts the robustness headline: zero responseless
    requests (every client completes, sheds, or reaps — never errors) and
    post-kill greedy outputs byte-identical to the no-kill twin's
    (membership moves placement, never sampling)."""
    probe_prompts = make_shared_prefix_prompts(
        2, shared_frac=0.5, total_chars=120, seed=53
    )
    common = dict(
        n_replicas=n_replicas,
        n_interactive=n_interactive,
        n_rollout=n_rollout,
        duration_s=duration_s,
        interactive_tokens=8,
        rollout_tokens=16,
        interactive_deadline_s=deadline_s,
        rollout_deadline_s=deadline_s,
        greedy=True,
        post_probe_prompts=probe_prompts,
        # every engine call stalls 0.4s: service time is wait-dominated
        # and identical across arms (same seed, same schedule), so the
        # admission ceiling is the only thing the arms disagree on
        chaos_stall_prob=1.0,
        chaos_stall_s=0.4,
        gateway_max_inflight=shard_inflight,
        **fleet_kw,
    )
    arms: dict[str, dict[str, Any]] = {}
    arms["shards_1"] = await run_local_bench(n_gateways=1, **common)
    arms["shards_3"] = await run_local_bench(n_gateways=3, **common)
    arms["shards_3_kill"] = await run_local_bench(
        n_gateways=3, kill_shard_at=duration_s * kill_at_frac, **common
    )
    g1 = arms["shards_1"]["totals"]["goodput_tok_s"]
    g3 = arms["shards_3"]["totals"]["goodput_tok_s"]
    kill = arms["shards_3_kill"]
    kill_errors = sum(
        kill["classes"][p]["errors"] for p in PRIORITIES
    )
    survivors = {
        sid: tok
        for sid, tok in kill["gateway_tier"]["per_shard_goodput_tok_s"].items()
        if sid != kill["gateway_tier"]["killed_shard"]
    }
    comparison = {
        "goodput_tok_s": {"shards_1": g1, "shards_3": g3},
        "scaling_x": (g3 / g1) if g1 > 0 else None,
        "near_linear": g1 > 0 and g3 / g1 >= 2.2,
        "killed_shard": kill["gateway_tier"]["killed_shard"],
        "kill_failovers": kill["gateway_tier"]["failovers"],
        "kill_errors": kill_errors,
        "kill_zero_responseless": kill_errors == 0,
        # the dead shard's keyspace must land on survivors, not vanish
        "survivors_absorbed": any(v > 0 for v in survivors.values()),
        "kill_greedy_identical": (
            kill.get("post_probe_texts")
            == arms["shards_3"].get("post_probe_texts")
        ),
    }
    return {
        "bench": "gateway_tier_ab",
        "shard_inflight": shard_inflight,
        "arms": arms,
        "comparison": comparison,
    }


def bench_autopilot_config(
    interval_s: float = 1.0,
    min_queue_depth: int = 2,
    max_queue_depth: int = 128,
    high_queue_wait_s: float = 2.0,
    low_queue_wait_s: float = 0.8,
    fleet: bool = False,
    fleet_floor: int = 1,
):
    """A fast-cadence AutopilotConfig tuned for short CPU benches and
    self-tests (sub-second control rounds, 1-2s cooldowns). Production
    deployments should keep the config defaults — 5s rounds and 10-30s
    cooldowns — and let hysteresis do its job over minutes, not seconds."""
    from areal_tpu.api.config import (
        AdmissionControllerConfig,
        AutopilotConfig,
        CacheControllerConfig,
        FleetControllerConfig,
        StalenessControllerConfig,
    )

    return AutopilotConfig(
        enabled=True,
        interval_s=interval_s,
        signal_ttl_s=10.0,
        staleness=StalenessControllerConfig(enabled=False),
        cache=CacheControllerConfig(enabled=False),
        admission=AdmissionControllerConfig(
            enabled=not fleet,
            cooldown_s=interval_s * 2,
            min_queue_depth=min_queue_depth,
            max_queue_depth=max_queue_depth,
            queue_depth_step=8,
            high_queue_wait_s=high_queue_wait_s,
            low_queue_wait_s=low_queue_wait_s,
            high_shed_rate_per_s=0.5,
            # the page-headroom subcontroller is the self-test's subject
            # (it needs a page-tight fleet to matter); on the short A/B it
            # would only add decision churn
            high_reap_rate_per_s=1e9,
            headroom_step=2,
            max_headroom=16,
            narrow_after_quiet_rounds=8,
        ),
        fleet=FleetControllerConfig(
            enabled=fleet,
            min_replicas=fleet_floor,
            drain_below_load=0.4,
            undrain_above_queue=0.3,
            sustain_rounds=3,
            undrain_sustain_rounds=1,
            cooldown_s=interval_s * 3,
        ),
    )


async def run_autopilot_ab(
    n_replicas: int = 1,
    n_interactive: int = 10,
    n_rollout: int = 80,
    duration_s: float = 16.0,
    load_profile: str = "burst",
    static_queue_depths: tuple[int, ...] = (24, 96),
    autopilot_start_depth: int = 24,
    deadline_s: float = 3.0,
    fleet_run: bool = False,
    **fleet_kw: Any,
) -> dict[str, Any]:
    """The autopilot acceptance scoreboard (ROADMAP item 6): one fresh
    fleet per arm, identical seeds/params/chaos schedule and the SAME
    time-varying ``load_profile``, comparing a small static-config sweep
    against autopilot-on.

    The admission run (default): static ``max_queue_depth`` arms must
    lose somewhere on a bursty profile — a small queue sheds the calm
    phase, a big one converts the spike into deadline-missed tail latency
    — while the autopilot's AIMD tracks the phase it is in. Scored on
    within-deadline goodput. The greedy probes double as the byte-identity
    evidence: the control plane moves ADMISSION, never sampling.

    ``fleet_run=True`` instead scores the fleet controller on
    goodput-per-replica-second over a diurnal profile: draining idle
    replicas during the trough returns capacity (the denominator) that a
    static fleet keeps burning.

    Every autopilot arm also reports its decision ledger, and the driver
    can join each setpoint change against the flight ring
    (``kind=autopilot_decision``) for the audit trail."""
    from areal_tpu.observability import timeline as tl_mod

    if fleet_run:
        n_replicas = max(3, n_replicas)
        load_profile = "diurnal"
        # mean demand ~60% of fleet capacity: the autoscaler's win is the
        # trough's returned replica-seconds, not overload admission
        n_rollout = min(n_rollout, 50)
        # bounded per-replica queues in BOTH arms: after a scale-down, a
        # rising wave must spill to siblings (429 -> failover) instead of
        # piling deadline-doomed work onto the survivor
        fleet_kw.setdefault("max_queue_depth", 8)
    probe_prompts = make_shared_prefix_prompts(
        2, shared_frac=0.5, total_chars=120, seed=31
    ) * 2
    common = dict(
        n_replicas=n_replicas,
        n_interactive=n_interactive,
        n_rollout=n_rollout,
        duration_s=duration_s,
        interactive_tokens=8,
        # rollout decodes are the capacity sink: on the decode-costly
        # "small" bench model, 256-token greedy decodes make per-request
        # service time a real fraction of the deadline, so the burst
        # overcommits the engine ~3x while the calm phases stay under
        # capacity — the regime where a static queue depth must pick its
        # poison: a deep queue decodes doomed work past its deadline
        # (measured: depth 96 loses ~10% goodput here), a shallow one
        # idles the engine between Retry-After waves
        rollout_tokens=256,
        interactive_deadline_s=deadline_s,
        rollout_deadline_s=deadline_s,
        load_profile=load_profile,
        probe_prompts=probe_prompts,
        warmup_s=3.0,
        model="small",
        max_batch_size=2,
        retry_after_s=0.4,
        greedy=True,
        **fleet_kw,
    )
    arms: dict[str, dict[str, Any]] = {}
    if fleet_run:
        # the static fleet-size sweep: the full fleet, always on
        static_arms = {f"static_{n_replicas}_replicas": dict(common)}
    else:
        static_arms = {
            f"static_depth_{d}": dict(common, max_queue_depth=d)
            for d in static_queue_depths
        }
    for name, kw in static_arms.items():
        arms[name] = await run_local_bench(**kw)
    # autopilot arm: count only ITS decisions (the ring is process-global)
    ring_seq0 = max(
        (e.get("seq", 0) for e in tl_mod.get_flight_recorder().snapshot()["events"]),
        default=0,
    )
    # floor 2 of 3: the trough returns one replica's worth of capacity
    # while two survivors keep every deadline coverable (a floor of 1
    # measured ~20% deadline reaps when the rising wave lands before the
    # undrain — scale-down depth is a safety knob, not a free lunch)
    ap_cfg = bench_autopilot_config(fleet=fleet_run, fleet_floor=2)
    auto_kw = dict(common, autopilot_cfg=ap_cfg)
    if not fleet_run:
        auto_kw["max_queue_depth"] = autopilot_start_depth
    arms["autopilot"] = await run_local_bench(**auto_kw)
    decisions = [
        e
        for e in tl_mod.get_flight_recorder().snapshot()["events"]
        if e.get("kind") == "autopilot_decision" and e.get("seq", 0) > ring_seq0
    ]
    metric = "goodput_per_replica_tok_s" if fleet_run else None

    def score(arm: dict[str, Any]) -> float:
        if metric:
            return float(arm.get(metric) or 0.0)
        return float(arm["totals"]["goodput_tok_s"])

    static_scores = {n: score(arms[n]) for n in static_arms}
    auto_score = score(arms["autopilot"])
    probe_sets = {n: arms[n].get("probe_texts") for n in arms}
    comparison = {
        "metric": metric or "goodput_tok_s",
        "load_profile": load_profile,
        "static": static_scores,
        "autopilot": auto_score,
        "autopilot_wins": bool(
            static_scores and auto_score > max(static_scores.values())
        ),
        "autopilot_decisions": len(decisions),
        "decisions_audited": all(
            (e.get("data") or {}).get("reason")
            and (e.get("data") or {}).get("knob")
            for e in decisions
        )
        and len(decisions) > 0,
        # placement/admission only, never output: greedy probes must be
        # byte-identical across every arm
        "greedy_identical": len({tuple(v or ()) for v in probe_sets.values()})
        == 1,
    }
    return {
        "bench": "gateway_autopilot_ab",
        "fleet_run": fleet_run,
        "arms": arms,
        "decisions": [e.get("data") for e in decisions[-32:]],
        "comparison": comparison,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--gateway", default="", help="existing gateway base url")
    p.add_argument("--admin-key", default="", help="gateway admin API key")
    p.add_argument(
        "--local",
        action="store_true",
        help="spin a self-contained local fleet (tiny model) to bench",
    )
    p.add_argument("--replicas", type=int, default=None)
    p.add_argument("--interactive", type=int, default=None)
    p.add_argument("--rollout", type=int, default=None)
    p.add_argument("--duration", type=float, default=None)
    p.add_argument("--stall-prob", type=float, default=0.3)
    p.add_argument("--stall-s", type=float, default=0.1)
    p.add_argument("--max-inflight", type=int, default=0)
    p.add_argument("--headroom", type=int, default=0)
    p.add_argument(
        "--route-policy",
        choices=("round_robin", "cache_aware"),
        default="round_robin",
        help="replica-selection policy for the local fleet's client",
    )
    p.add_argument(
        "--workload",
        choices=("mixed", "shared_prefix"),
        default=None,
        help="shared_prefix = 80%%-shared multi-turn-style prompts (the "
        "prefix-locality routing scoreboard). Default: mixed, or "
        "shared_prefix under --ab",
    )
    p.add_argument(
        "--shared-frac",
        type=float,
        default=None,
        help="fleet-global shared task-preamble fraction of each base "
        "prompt. Default: 0.8, or 0.1 under --ab (there the ~80%% "
        "per-request sharing comes from multi-turn conversation history "
        "— the prefix structure a router can actually exploit)",
    )
    p.add_argument("--prompt-chars", type=int, default=None)
    p.add_argument(
        "--turns",
        type=int,
        default=None,
        help="chat turns per client session (default: 3 under --ab, else 1)",
    )
    p.add_argument(
        "--ab",
        action="store_true",
        help="run BOTH policies on fresh identical local fleets and emit "
        "one comparison report (goodput, suffix-prefill tokens, greedy "
        "byte-identity)",
    )
    p.add_argument(
        "--gateways",
        type=int,
        default=1,
        help="gateway shards for the local fleet (N>1 runs the "
        "consistent-hash tier; 1 keeps the pre-tier single gateway)",
    )
    p.add_argument(
        "--kill-shard-at",
        type=float,
        default=None,
        metavar="T",
        help="with --gateways N>1: hard-kill one shard T seconds into "
        "the measured window (the chaos point — clients must re-hash to "
        "survivors with zero responseless requests)",
    )
    p.add_argument(
        "--tier-ab",
        action="store_true",
        help="gateway-tier acceptance A/B: 1 vs 3 shards on the same "
        "fleet plus a mid-run-kill twin, one comparison report (scaling, "
        "zero responseless, greedy byte-identity)",
    )
    p.add_argument(
        "--load-profile",
        choices=("uniform", *sorted(LOAD_PROFILES)),
        default="uniform",
        help="time-varying open-loop arrival-rate profile (piecewise "
        "schedule recorded in the JSON artifact); uniform keeps the "
        "legacy even spread",
    )
    p.add_argument(
        "--autopilot-ab",
        action="store_true",
        help="autopilot acceptance A/B: a static max_queue_depth sweep vs "
        "autopilot-on under the chosen --load-profile (default: burst), "
        "scored on within-deadline goodput with the decision audit "
        "attached",
    )
    p.add_argument(
        "--fleet-run",
        action="store_true",
        help="with --autopilot-ab: score the FLEET controller instead "
        "(3 replicas, diurnal profile, goodput per replica-second)",
    )
    p.add_argument("-o", "--output", default="", help="JSON report path")
    args = p.parse_args(argv)
    # mode-dependent defaults: the A/B needs a saturated shared-prefix
    # multi-turn fleet; the plain bench keeps its standing configuration
    if args.workload is None:
        args.workload = "shared_prefix" if args.ab else "mixed"
    if args.turns is None:
        args.turns = 3 if args.ab else 1
    if args.replicas is None:
        args.replicas = 3 if args.ab else 2
    if args.interactive is None:
        args.interactive = 18 if args.ab else 8
    if args.rollout is None:
        args.rollout = 18 if args.ab else 8
    if args.duration is None:
        args.duration = 4.0 if args.ab else 15.0
    if args.shared_frac is None:
        args.shared_frac = 0.1 if args.ab else 0.8

    if args.tier_ab:
        report = asyncio.run(
            run_tier_ab(
                duration_s=args.duration if args.duration != 15.0 else 6.0,
            )
        )
    elif args.autopilot_ab:
        report = asyncio.run(
            run_autopilot_ab(
                load_profile=(
                    "burst"
                    if args.load_profile == "uniform" and not args.fleet_run
                    else args.load_profile
                ),
                fleet_run=args.fleet_run,
                chaos_stall_prob=args.stall_prob,
                chaos_stall_s=args.stall_s,
            )
        )
    elif args.ab:
        kw = {}
        if args.prompt_chars is not None:
            kw["prompt_chars"] = args.prompt_chars
        report = asyncio.run(
            run_ab(
                n_replicas=args.replicas,
                n_interactive=args.interactive,
                n_rollout=args.rollout,
                duration_s=args.duration,
                workload=args.workload,
                shared_frac=args.shared_frac,
                turns=args.turns,
                chaos_stall_prob=args.stall_prob,
                chaos_stall_s=args.stall_s,
                gateway_max_inflight=args.max_inflight,
                gateway_interactive_headroom=args.headroom,
                **kw,
            )
        )
    elif args.local or not args.gateway:
        report = asyncio.run(
            run_local_bench(
                n_replicas=args.replicas,
                n_interactive=args.interactive,
                n_rollout=args.rollout,
                duration_s=args.duration,
                workload=args.workload,
                shared_frac=args.shared_frac,
                prompt_chars=args.prompt_chars or 400,
                turns=args.turns,
                load_profile=args.load_profile,
                chaos_stall_prob=args.stall_prob,
                chaos_stall_s=args.stall_s,
                gateway_max_inflight=args.max_inflight,
                gateway_interactive_headroom=args.headroom,
                route_policy=args.route_policy,
                n_gateways=args.gateways,
                kill_shard_at=args.kill_shard_at,
            )
        )
    else:
        report = asyncio.run(
            drive_gateway(
                args.gateway,
                args.admin_key,
                n_interactive=args.interactive,
                n_rollout=args.rollout,
                duration_s=args.duration,
                load_profile=args.load_profile,
            )
        )
    text = json.dumps(report, indent=1)
    print(text)
    if args.output:
        from areal_tpu.utils import atomic_io

        atomic_io.atomic_write_text(args.output, text)
        print(f"wrote {args.output}")
    # non-null scoreboard or the run proved nothing
    if args.tier_ab:
        cmp_ = report["comparison"]
        ok = (
            cmp_["near_linear"]
            and cmp_["kill_zero_responseless"]
            and cmp_["survivors_absorbed"]
            and cmp_["kill_greedy_identical"]
        )
    elif args.autopilot_ab:
        cmp_ = report["comparison"]
        ok = (
            cmp_["autopilot_wins"]
            and cmp_["decisions_audited"]
            and cmp_["greedy_identical"]
        )
    elif args.ab:
        cmp_ = report["comparison"]
        ok = (
            cmp_["greedy_identical"]
            and cmp_["cache_aware_wins_prefill"]
            and all(
                arm["classes"][p]["ttft_p50_s"] is not None
                for arm in report["arms"].values()
                for p in PRIORITIES
            )
        )
    else:
        ok = all(
            report["classes"][p]["ttft_p50_s"] is not None for p in PRIORITIES
        )
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
