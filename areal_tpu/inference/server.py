"""HTTP generation server wrapping DecodeEngine.

Speaks the same small protocol the reference's client layer needs from
SGLang/vLLM (SURVEY §7.1; reference engine/sglang_remote.py:34-436 builds
these requests): /generate, /pause_generation, /continue_generation,
/update_weights_from_disk, /update_weights_from_distributed (mem path),
/health, /release_memory_occupation, /resume_memory_occupation. aiohttp
replaces fastapi/uvicorn (not in the image).
"""

from __future__ import annotations

import asyncio
import json
import random as _random
import threading
import time

import numpy as np
from aiohttp import web

from areal_tpu.api.config import ServerConfig
from areal_tpu.api import io_struct, wire
from areal_tpu.api.io_struct import GenerationHyperparameters, ModelRequest
from areal_tpu.inference.decode_engine import DecodeEngine
from areal_tpu.observability import catalog, tracecontext
from areal_tpu.observability import timeline as tl_mod
from areal_tpu.observability.metrics import get_registry
from areal_tpu.utils import logging as alog, network
from areal_tpu.utils import name_resolve, perf_tracer

logger = alog.getLogger("inference_server")


def _req_from_json(d: dict) -> ModelRequest:
    g = d.get("sampling_params", {})
    gconfig = GenerationHyperparameters(
        max_new_tokens=g.get("max_new_tokens", 128),
        greedy=bool(g.get("greedy", False)),
        temperature=g.get("temperature", 1.0),
        top_p=g.get("top_p", 1.0),
        top_k=g.get("top_k", -1),
        stop_token_ids=g.get("stop_token_ids", []),
        max_tokens=g.get("max_tokens"),
        ignore_eos=bool(g.get("ignore_eos", False)),
        frequency_penalty=float(g.get("frequency_penalty", 0.0)),
        min_new_tokens=int(g.get("min_new_tokens", 0)),
        denoising_steps=g.get("denoising_steps"),
        remasking_strategy=g.get("remasking_strategy"),
        confidence_threshold=g.get("confidence_threshold"),
    )
    image_data = None
    if d.get("image_data"):
        # base64 fp32 patch array [P, patch_dim] (VLM serving; the reference
        # ships base64 images to SGLang — here the processor runs client-side
        # and the wire carries extracted patches)
        import base64 as b64
        import io

        image_data = np.load(io.BytesIO(b64.b64decode(d["image_data"])))
    deadline = d.get("deadline")
    return ModelRequest(
        input_ids=d["input_ids"],
        gconfig=gconfig,
        rid=d.get("rid", ""),
        metadata=d.get("metadata", {}),
        image_data=image_data,
        image_grid_thw=d.get("image_grid_thw"),
        deadline=float(deadline) if deadline is not None else None,
    )


class InferenceServer:
    """One HTTP endpoint over one DecodeEngine replica."""

    def __init__(self, config: ServerConfig, engine: DecodeEngine | None = None):
        self.config = config
        self.engine = engine or DecodeEngine(config)
        self._runner: web.AppRunner | None = None
        self.port = config.port or network.find_free_port()
        self.host = config.host
        self._metrics = catalog.server_metrics()
        self._engine_obs = catalog.engine_metrics()
        self._pc_obs = catalog.prefix_cache_metrics()
        self._lc_obs = catalog.lifecycle_metrics()
        self._hw_obs = catalog.train_obs_metrics()  # HBM ledger gauges
        self._started_at = time.time()
        self._update_begin_ts: float | None = None
        # flight recorder: the engine's ring when it has one (DecodeEngine),
        # else the process default — /debug/flight serves it either way
        self._flight = getattr(
            self.engine, "flight", None
        ) or tl_mod.get_flight_recorder()
        # role travels INSIDE the ring, not just the HTTP snapshot: the
        # wedge/SIGTERM disk dumps serialize the recorder directly, and
        # postmortem keys its merged process rows on this field.
        # First claimant wins (mirror of the controller's guard): a
        # colocated controller's earlier claim must not be clobbered
        if self._flight.role == "proc":
            self._flight.role = "inference_server"

    @property
    def address(self) -> str:
        ip = "127.0.0.1" if self.host in ("0.0.0.0", "") else self.host
        return f"{ip}:{self.port}"

    def build_app(self) -> web.Application:
        app = web.Application(client_max_size=1 << 30)
        app.add_routes(
            [
                web.get("/health", self.h_health),
                web.get("/healthz", self.h_health),
                web.get("/statusz", self.h_statusz),
                web.get("/metrics", self.h_metrics),
                web.post("/generate", self.h_generate),
                web.post("/pause_generation", self.h_pause),
                web.post("/continue_generation", self.h_continue),
                web.post("/update_weights_from_disk", self.h_update_disk),
                web.post("/update_weights_from_tensors", self.h_update_tensors),
                web.post("/update_weights_begin", self.h_update_begin),
                web.post("/update_weights_bucket", self.h_update_bucket),
                web.post("/update_weights_commit", self.h_update_commit),
                web.post("/update_weights_abort", self.h_update_abort),
                web.post("/update_weights_lora", self.h_update_lora),
                web.post("/set_version", self.h_set_version),
                web.post("/release_memory_occupation", self.h_release_memory),
                web.post("/resume_memory_occupation", self.h_resume_memory),
                web.post("/flush_prefix_cache", self.h_flush_prefix_cache),
                web.post("/abort_request", self.h_abort_request),
                web.post("/drain", self.h_drain),
                web.post("/undrain", self.h_undrain),
                web.post("/autopilot/knobs", self.h_autopilot_knobs),
                web.get("/debug/flight", self.h_debug_flight),
                web.post("/debug/profile", self.h_debug_profile),
            ]
        )
        return app

    # -- handlers ---------------------------------------------------------
    async def h_health(self, request: web.Request) -> web.Response:
        # preemption drain (docs/fault_tolerance.md): a draining replica is
        # leaving the fleet — 503 makes the client fleet probe / PR 3
        # supervision stop routing to it immediately, while in-flight
        # decodes finish-or-park inside the drain budget
        draining = getattr(self.engine, "is_draining", False)
        if draining:
            return web.json_response(
                {"status": "draining", "version": self.engine.get_version()},
                status=503,
            )
        # wedge escalation (docs/request_lifecycle.md): a decode loop that
        # stopped making passes while work is pending can't run its own
        # watchdog — report 503 so the client fleet probe / PR 3
        # supervision evicts and respawns this replica
        wedged = getattr(self.engine, "is_wedged", None)
        if wedged is not None and wedged():
            return web.json_response(
                {"status": "wedged", "version": self.engine.get_version()},
                status=503,
            )
        return web.json_response(
            {"status": "ok", "version": self.engine.get_version()}
        )

    def _refresh_gauges(self) -> None:
        """Point-in-time engine state -> registry gauges (scrape-driven;
        the hot decode loop never touches these)."""
        m = self._metrics
        m.paused.set(1.0 if self.engine.is_paused else 0.0)
        q = getattr(self.engine, "_queue", None)
        backlog = getattr(self.engine, "_backlog", ())
        depth = (q.qsize() if q is not None else 0) + len(backlog)
        m.queue_depth.set(depth)
        # lifecycle twin: the depth the admission gate compares against
        self._lc_obs.queue_depth.set(depth)
        slots = getattr(self.engine, "_slot_task", None)
        if slots is not None:
            self._engine_obs.batch_occupancy.set(
                sum(1 for t in slots if t is not None)
            )
        pc = getattr(self.engine, "prefix_cache_stats", None)
        if pc is not None:
            self._pc_obs.pages_held.set(float(pc().get("pages_held", 0)))
        hb = getattr(self.engine, "hbm_ledger", None)
        if hb is not None:
            try:
                from areal_tpu.observability import hw_accounting

                hw_accounting.observe_hbm_ledger(hb(), obs=self._hw_obs)
            except Exception:  # noqa: BLE001 — scrape must not 500 on an
                # accounting edge (mid-initialize engine, missing pool)
                pass

    async def h_metrics(self, request: web.Request) -> web.Response:
        """Content-negotiated metrics.

        Default (and ``Accept: application/json``) keeps the legacy JSON
        shape for existing callers (client._await_unpaused and older
        scrapers); ``Accept: text/plain`` serves the Prometheus text
        exposition of the process registry.
        """
        self._refresh_gauges()
        accept = request.headers.get("Accept", "")
        if "text/plain" in accept:
            return web.Response(
                text=get_registry().render_prometheus(),
                content_type="text/plain",
                charset="utf-8",
            )
        # the server's pause state gets its OWN key (server_paused) so an
        # engine-provided "paused" stat is never clobbered; "paused" keeps
        # the legacy boolean shape unless the engine claims the name (the
        # pause-wait client polls server_paused first — client.py)
        out = dict(self.engine.stats)
        out["server_paused"] = self.engine.is_paused
        out.setdefault("paused", self.engine.is_paused)
        return web.json_response(out)

    async def h_statusz(self, request: web.Request) -> web.Response:
        """Human/ops summary: identity, uptime, version, live state. The
        ``stats`` section carries every decode-loop counter (prefills,
        prefill_batches, chunks, prefix-cache hit/miss, ...); the
        ``prefix_cache`` section is the radix tree's own live state."""
        self._refresh_gauges()
        out = {
            "role": "inference_server",
            "address": self.address,
            "uptime_secs": time.time() - self._started_at,
            "version": self.engine.get_version(),
            "paused": self.engine.is_paused,
            "stats": dict(self.engine.stats),
        }
        pc = getattr(self.engine, "prefix_cache_stats", None)
        if pc is not None:
            out["prefix_cache"] = pc()
        snap = getattr(self.engine, "admission_snapshot", None)
        if snap is not None:
            out["lifecycle"] = snap()
        ds = getattr(self.engine, "drain_status", None)
        if ds is not None:
            # preemption drain view (docs/fault_tolerance.md): live flag
            # plus the last drain's summary (finish-or-park outcome, leak
            # audit) — what an operator checks after a spot reclaim
            out["drain"] = ds()
        ap = getattr(self.engine, "autopilot_status", None)
        if ap is not None:
            # control-plane view (docs/autopilot.md): the setpoints this
            # replica is actually running, so the autopilot (and an
            # operator postmortem) can confirm pushes took effect
            out["autopilot"] = ap()
        tl = getattr(self.engine, "timeline", None)
        if tl is not None:
            # same key as /debug/flight's stats section — over THERE
            # "timelines" is the list of timeline records
            out["timeline_stats"] = tl.stats()
        ms = getattr(self.engine, "moe_status", None)
        if ms is not None and (moe_view := ms()) is not None:
            # expert-load counts of a model with sparse experts
            # (docs/observability.md): one nested list, not a series a cell
            out["moe"] = moe_view
        rp = getattr(self.engine, "residual_status", None)
        if rp is not None and (residual_view := rp()) is not None:
            # a residual path of several streams: its form and their number
            out["residual"] = residual_view
        sa = getattr(self.engine, "sparse_attention_status", None)
        if sa is not None and (sparse_view := sa()) is not None:
            # a learned index's selection and the form its rows are read in
            out["sparse_attention"] = sparse_view
        da = getattr(self.engine, "decode_attention_status", None)
        if da is not None and (attn_view := da()) is not None:
            # blocks of pages the decode steps' attention listed and fetched
            out["decode_attention"] = attn_view
        rs = getattr(self.engine, "row_steps_status", None)
        if rs is not None:
            # the decode steps' rows: live, spent on an ended request, dropped
            out["row_steps"] = rs()
        kp = getattr(self.engine, "kv_pools_status", None)
        if kp is not None and (pools_view := kp()) is not None:
            # which layers each group of page pools serves, how long a slot
            # keeps a token there, and what each holds now
            out["kv_pools"] = pools_view
        ks = getattr(self.engine, "kernel_stats", None)
        if ks is not None:
            # decode-step phases (docs/observability.md "Decode-step
            # phases"): step counts, per-pass phase means, dominant phase
            out["kernels"] = ks()
        hb = getattr(self.engine, "hbm_ledger", None)
        if hb is not None:
            try:
                # itemized device-memory account incl. OOM headroom
                # (docs/observability.md "HBM ledger")
                out["hbm"] = hb()
            except Exception:  # noqa: BLE001 — statusz must render even if
                # the ledger can't (mid-initialize engine)
                pass
        return web.json_response(out)

    async def h_debug_flight(self, request: web.Request) -> web.Response:
        """Flight-recorder scrape (observability/timeline.py): the bounded
        significant-event ring plus recently completed request timelines.
        ``tools/postmortem.py`` merges these across the fleet into one
        Perfetto trace; ``?timelines=N`` bounds the timeline payload."""
        self._metrics.requests.labels(endpoint="debug_flight").inc()
        try:
            n_tl = int(request.query.get("timelines", "128"))
        except ValueError:
            n_tl = 128
        # snapshot() carries the ring's authoritative role (first claimant
        # — may be a colocated controller's); don't clobber it here or the
        # live scrape and the same ring's disk dumps disagree
        out = self._flight.snapshot()
        out["address"] = self.address
        tl = getattr(self.engine, "timeline", None)
        if tl is not None:
            out["timeline_stats"] = tl.stats()
            out["timelines"] = tl.recent(max(0, n_tl))
        return web.json_response(out)

    async def h_debug_profile(self, request: web.Request) -> web.Response:
        """On-demand XLA device profile: ``POST /debug/profile?duration_s=N``
        starts a jax.profiler capture and returns its dir immediately (the
        xplane/trace files land when the background timer stops it N
        seconds later); ``duration_s=0`` stops an active capture early.
        One capture at a time per process — a second start gets a 409
        carrying the active dir. ``tools/postmortem.py --profile-dirs``
        links the capture next to the merged Perfetto trace."""
        from areal_tpu.utils import perf_tracer

        self._metrics.requests.labels(endpoint="debug_profile").inc()
        try:
            duration = float(request.query.get("duration_s", "5"))
        except ValueError:
            return web.json_response(
                {"error": "duration_s must be a number"}, status=400
            )
        if duration <= 0:
            d = perf_tracer.stop_device_profile()
            return web.json_response(
                {"status": "stopped" if d else "idle", "trace_dir": d}
            )
        active = perf_tracer.device_profile_active()
        if active is not None:
            return web.json_response(
                {"error": "profile already active", "trace_dir": active},
                status=409,
            )
        try:
            d = perf_tracer.profile_for(duration)
        except RuntimeError as e:  # lost the start race
            return web.json_response({"error": str(e)}, status=409)
        return web.json_response(
            {"status": "profiling", "trace_dir": d, "duration_s": duration}
        )

    async def h_flush_prefix_cache(self, request: web.Request) -> web.Response:
        """Ops escape hatch: drop every radix-cached page (e.g. before an
        A/B window, or to reclaim pool headroom without a weight update)."""
        flush = getattr(self.engine, "flush_prefix_cache", None)
        if flush is None:
            return web.json_response({"status": "ok", "freed_pages": 0})
        freed = await asyncio.get_running_loop().run_in_executor(None, flush)
        return web.json_response({"status": "ok", "freed_pages": int(freed)})

    async def h_generate(self, request: web.Request) -> web.Response:
        # trace context rides x-areal-trace from the rollout client so this
        # server's spans correlate with the submitting workflow's session
        tracecontext.extract(request.headers)
        self._metrics.requests.labels(endpoint="generate").inc()
        # admission control (docs/request_lifecycle.md): under overload the
        # right answer is a FAST clean 429 with backpressure hints, not an
        # unbounded queue that converts overload into tail latency
        gate = getattr(self.engine, "check_admission", None)
        if gate is not None:
            admit, reason, snap = gate()
            if not admit:
                lc = getattr(self.engine.config, "lifecycle", None)
                retry_after = getattr(lc, "retry_after_s", 1.0) or 1.0
                # bounded multiplicative jitter scatters honoring clients
                # across [x, x*(1+jitter)] — a fleet shedding in unison
                # must not re-arrive in unison (thundering herd)
                jitter = getattr(lc, "retry_after_jitter", 0.0) or 0.0
                if jitter > 0:
                    retry_after *= 1.0 + _random.random() * jitter
                self._lc_obs.admission_rejected.labels(reason=reason).inc()
                self._flight.record(
                    "admission_reject",
                    severity="warn",
                    reason=reason,
                    queue_depth=snap.get("queue_depth"),
                )
                return web.json_response(
                    {"status": "rejected", "reason": reason, **snap},
                    status=429,
                    headers={"Retry-After": f"{retry_after:g}"},
                )
        d = await request.json()
        req = _req_from_json(d)
        # priority class rides x-areal-priority (gateway load-shedding
        # classes; docs/request_lifecycle.md) into request metadata so the
        # engine's timeline histograms split TTFT by class
        prio = request.headers.get(
            wire.PRIORITY_HEADER, req.metadata.get("priority", "")
        )
        if prio:
            req.metadata["priority"] = str(prio).lower()
        # deadline rides the x-areal-deadline header (absolute unix epoch
        # seconds) end-to-end; a JSON "deadline" field is the fallback for
        # hand-rolled callers. Header wins: the outermost hop (gateway)
        # owns the budget.
        hdr_deadline = request.headers.get(wire.DEADLINE_HEADER)
        if hdr_deadline:
            try:
                req.deadline = float(hdr_deadline)
            except ValueError:
                return web.json_response(
                    {"status": "error", "error": "bad x-areal-deadline"},
                    status=400,
                )
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()

        def cb(resp):
            loop.call_soon_threadsafe(
                lambda: fut.done() or fut.set_result(resp)
            )

        try:
            async with perf_tracer.atrace_scope(
                "server.generate", perf_tracer.Category.COMPUTE, {"rid": req.rid}
            ):
                self.engine.submit(req, cb)
                resp = await fut
        except asyncio.CancelledError:
            # the client disconnected (aiohttp cancels the handler): cancel
            # the engine-side work too, or the slot decodes to completion
            # and holds KV pages for a caller that is gone
            abort = getattr(self.engine, "abort_request", None)
            if abort is not None:
                abort(req.rid)
            raise
        # only requests that actually emitted a token have a TTFT; aborted
        # ones report submit->abort time, which would skew the histogram
        # with pause-wait durations
        if resp.output_tokens:
            self._metrics.ttft.observe(resp.ttft)
        self._metrics.request_latency.observe(resp.latency)
        return web.json_response(
            {
                "output_tokens": resp.output_tokens,
                "output_logprobs": resp.output_logprobs,
                "output_versions": resp.output_versions,
                "output_denoise_pass": resp.output_denoise_pass,  # a block-diffusion model's; else empty
                "stop_reason": resp.stop_reason,
                "truncated_by": resp.truncated_by,
                "latency": resp.latency,
                "ttft": resp.ttft,
                # per-request stage breakdown (observability/timeline.py);
                # the client sums these across abort/resume attempts and
                # stamps them onto its ModelResponse
                "timing": {
                    k: getattr(resp, k) for k in io_struct.TIMING_FIELDS
                },
                # prompt tokens served from radix-cached KV (0 = cold):
                # the "actual" half of the router's hit audit
                "cached_prefix_tokens": int(
                    resp.metadata.get("cached_prefix_tokens") or 0
                ),
                "rid": resp.rid,
            }
        )

    async def h_abort_request(self, request: web.Request) -> web.Response:
        """Cancel one in-flight request by rid (docs/request_lifecycle.md):
        queued, decoding, or parked — the decode loop reaps it between
        chunks, frees/publishes its KV pages, and fires the callback with
        stop_reason="cancelled". Idempotent; unknown rids are a no-op."""
        self._metrics.requests.labels(endpoint="abort_request").inc()
        raw = await request.read()
        rid = ""
        if raw.strip():
            try:
                rid = str(json.loads(raw).get("rid", ""))
            except (ValueError, AttributeError):
                return web.json_response(
                    {"status": "error", "error": "unparsable JSON body"},
                    status=400,
                )
        if not rid:
            return web.json_response(
                {"status": "error", "error": "rid required"}, status=400
            )
        abort = getattr(self.engine, "abort_request", None)
        queued = bool(abort(rid)) if abort is not None else False
        return web.json_response({"status": "ok", "queued": queued})

    async def h_drain(self, request: web.Request) -> web.Response:
        """Ops/driver-initiated graceful drain (the same path a SIGTERM
        preemption takes, minus the process exit): admission closes with
        429 reason="draining", in-flight decodes finish or park within the
        budget, and the summary (incl. the leak audit) comes back.
        Optional JSON body: {"budget_s": seconds}."""
        self._metrics.requests.labels(endpoint="drain").inc()
        drain = getattr(self.engine, "drain", None)
        if drain is None:
            return web.json_response(
                {"status": "error", "error": "engine has no drain"}, status=501
            )
        budget = getattr(
            getattr(self.engine.config, "preemption", None), "drain_budget_s", 10.0
        )
        raw = await request.read()
        if raw.strip():
            try:
                budget = float(json.loads(raw).get("budget_s", budget))
            except (ValueError, AttributeError):
                return web.json_response(
                    {"status": "error", "error": "unparsable JSON body"},
                    status=400,
                )
        summary = await asyncio.get_running_loop().run_in_executor(
            None, drain, budget
        )
        return web.json_response({"status": "ok", **summary})

    async def h_undrain(self, request: web.Request) -> web.Response:
        """Cancel an ops/autopilot-initiated drain (a migration or
        scale-down called off): re-open admission and resume the decode
        loop. A SIGTERM-driven (terminal) drain is REFUSED with 409 —
        that process is exiting, and re-opened admission would accept
        requests that die responseless at the SIGKILL."""
        self._metrics.requests.labels(endpoint="undrain").inc()
        end = getattr(self.engine, "end_drain", None)
        if end is not None and end() is False:
            return web.json_response(
                {"status": "error", "error": "drain is terminal"},
                status=409,
            )
        self.engine.continue_generation()
        return web.json_response({"status": "ok"})

    async def h_autopilot_knobs(self, request: web.Request) -> web.Response:
        """Goodput-autopilot actuation (docs/autopilot.md): apply
        control-plane setpoints to this replica. Authenticated by config:
        when ``ServerConfig.autopilot_token`` is set, the request must
        carry it in ``x-areal-autopilot-token`` (403 otherwise); empty
        token leaves the endpoint open like the other ops endpoints."""
        self._metrics.requests.labels(endpoint="autopilot_knobs").inc()
        token = getattr(self.config, "autopilot_token", "") or ""
        if token and request.headers.get(wire.AUTOPILOT_TOKEN_HEADER) != token:
            return web.json_response(
                {"status": "error", "error": "bad autopilot token"},
                status=403,
            )
        apply = getattr(self.engine, "apply_autopilot_knobs", None)
        if apply is None:
            return web.json_response(
                {"status": "error", "error": "engine has no autopilot knobs"},
                status=501,
            )
        try:
            knobs = await request.json()
        except ValueError:
            return web.json_response(
                {"status": "error", "error": "unparsable JSON body"},
                status=400,
            )
        if not isinstance(knobs, dict):
            return web.json_response(
                {"status": "error", "error": "body must be a knob object"},
                status=400,
            )
        status = apply(knobs)
        return web.json_response({"status": "ok", **status})

    async def h_pause(self, request: web.Request) -> web.Response:
        """Pause modes: default "abort" (legacy §3.4: in-flight requests
        complete with stop_reason=abort), "hold" (zero-pause commit fence:
        the decode loop idles without aborting; see docs/weight_sync.md).
        Mode rides the optional JSON body so old clients keep working."""
        self._metrics.pauses.inc()
        mode = "abort"
        raw = await request.read()
        if raw.strip():
            # only an EMPTY body means legacy abort; a malformed body must
            # not silently downgrade a requested no-abort hold into the
            # destructive abort pause
            try:
                mode = json.loads(raw).get("mode", "abort")
            except (ValueError, AttributeError):
                return web.json_response(
                    {"status": "error", "error": "unparsable JSON body"},
                    status=400,
                )
        if mode == "abort":
            self.engine.pause_generation()  # legacy signature (test engines)
        else:
            self.engine.pause_generation(mode=mode)
            # the fence acks only once the decode loop actually quiesced
            # (in-flight chunk drained) — otherwise the client's commit can
            # land before the hold takes effect and the fence is decorative
            waiter = getattr(self.engine, "wait_fence_ack", None)
            if waiter is not None:
                fenced = await asyncio.get_running_loop().run_in_executor(
                    None, waiter, 10.0
                )
                return web.json_response({"status": "ok", "fenced": bool(fenced)})
        return web.json_response({"status": "ok"})

    async def h_continue(self, request: web.Request) -> web.Response:
        self._metrics.resumes.inc()
        self.engine.continue_generation()
        return web.json_response({"status": "ok"})

    async def h_update_disk(self, request: web.Request) -> web.Response:
        d = await request.json()
        path, version = d["path"], d.get("version")
        await asyncio.get_running_loop().run_in_executor(
            None, self.engine.update_weights_from_disk, path, version
        )
        return web.json_response({"status": "ok", "version": self.engine.get_version()})

    async def h_update_tensors(self, request: web.Request) -> web.Response:
        """mem-path weight update: raw npz body (name -> array)."""
        body = await request.read()
        import io

        loaded = np.load(io.BytesIO(body), allow_pickle=False)
        version = None
        flat = {}
        for k in loaded.files:
            if k == "__version__":
                version = int(loaded[k])
            else:
                flat[k] = loaded[k]
        params = _unflatten(flat)
        await asyncio.get_running_loop().run_in_executor(
            None, self.engine.update_weights_from_params, params, version
        )
        return web.json_response({"status": "ok", "version": self.engine.get_version()})

    async def h_update_begin(self, request: web.Request) -> web.Response:
        """Open the staging area. Generation is NOT paused — buckets stage
        while decoding continues. Optional JSON body {"stage_target":
        "device"|"host"} overrides ServerConfig.weight_stage_target for
        this update."""
        self._update_begin_ts = time.monotonic()
        stage_target = None
        raw = await request.read()
        if raw.strip():
            try:
                stage_target = json.loads(raw).get("stage_target")
            except (ValueError, AttributeError):
                return web.json_response(
                    {"status": "error", "error": "unparsable JSON body"},
                    status=400,
                )
        if stage_target is None:
            self.engine.begin_staged_update()  # legacy signature (test engines)
        else:
            self.engine.begin_staged_update(stage_target=stage_target)
        return web.json_response({"status": "ok"})

    async def h_update_bucket(self, request: web.Request) -> web.Response:
        """One bucket of bf16 tensors: 8-byte LE header length + json header
        {entries: [{name, dtype, shape}]} + concatenated raw buffers.
        device_put happens here, overlapping the next bucket's transport.

        Relay fan-out (reference role: the NCCL broadcast tree of
        fsdp_engine.py:1047-1137): an ``X-Areal-Relay`` header carries the
        downstream addresses this server must forward the SAME body to.
        The trainer then uploads each bucket once instead of n_servers
        times — fleet fan-out bandwidth rides the servers' own NICs, and
        the response acks only after the local stage AND every subtree ack
        (the commit barrier stays correct)."""
        body = await request.read()
        self._metrics.update_bucket_bytes.inc(len(body))
        relay = [a for a in request.headers.get(wire.RELAY_HEADER, "").split(",") if a]
        forwards = []
        if relay:
            # per-hop timeout rides with the request so the operator's
            # client-side request_timeout governs the whole tree
            timeout = float(
                request.headers.get(wire.RELAY_TIMEOUT_HEADER, "300")
            )
            forwards = [
                asyncio.get_running_loop().run_in_executor(
                    None, _relay_bucket, group, body, request.path_qs, timeout
                )
                for group in _split_relay(relay)
            ]
        flat = decode_weight_bucket(body)
        await asyncio.get_running_loop().run_in_executor(
            None, self.engine.stage_weight_bucket, flat
        )
        for f in forwards:
            await f
        return web.json_response({"status": "ok"})

    async def h_update_lora(self, request: web.Request) -> web.Response:
        """LoRA-delta fast path: body is one weight bucket holding only
        ``layers/{t}_lora_{a,b}`` leaves; ``scale`` (= alpha/rank) and
        optional ``version`` ride as query params. The engine folds the
        delta into its base weights — full-tree streaming skipped."""
        body = await request.read()
        flat = decode_weight_bucket(body)
        scale = float(request.query["scale"])
        version = request.query.get("version")
        await asyncio.get_running_loop().run_in_executor(
            None,
            self.engine.update_weights_lora,
            flat,
            scale,
            int(version) if version is not None else None,
        )
        return web.json_response({"status": "ok", "version": self.engine.get_version()})

    async def h_update_commit(self, request: web.Request) -> web.Response:
        d = await request.json()
        await asyncio.get_running_loop().run_in_executor(
            None, self.engine.commit_staged_weights, d.get("version")
        )
        if self._update_begin_ts is not None:
            self._metrics.update_stage_seconds.observe(
                time.monotonic() - self._update_begin_ts
            )
            self._update_begin_ts = None
        return web.json_response(
            {
                "status": "ok",
                "version": self.engine.get_version(),
                # tokens this replica emitted while the update staged —
                # proof of the zero-pause property, summed trainer-side
                "tokens_during_update": int(
                    getattr(self.engine, "last_update_gen_tokens", 0)
                ),
            }
        )

    async def h_update_abort(self, request: web.Request) -> web.Response:
        """Drop a partially staged update (a trainer that died mid-stream
        would otherwise leave the staged device arrays pinning HBM until
        the next begin)."""
        self.engine.abort_staged_update()
        return web.json_response({"status": "ok"})

    async def h_set_version(self, request: web.Request) -> web.Response:
        d = await request.json()
        self.engine.set_version(int(d["version"]))
        return web.json_response({"status": "ok"})

    async def h_release_memory(self, request: web.Request) -> web.Response:
        """Colocated-mode HBM handoff (pause first if not already paused).
        Requires the ABORT pause specifically: a hold fence also reports
        is_paused but keeps slots live, which release_memory must not see."""
        loop = asyncio.get_running_loop()
        if not getattr(self.engine, "is_abort_paused", self.engine.is_paused):
            self.engine.pause_generation()
        await loop.run_in_executor(None, self.engine.release_memory)
        return web.json_response({"status": "ok"})

    async def h_resume_memory(self, request: web.Request) -> web.Response:
        await asyncio.get_running_loop().run_in_executor(
            None, self.engine.resume_memory
        )
        return web.json_response({"status": "ok"})

    async def h_noop(self, request: web.Request) -> web.Response:
        return web.json_response({"status": "ok"})

    # -- lifecycle --------------------------------------------------------
    async def astart(self) -> None:
        if not getattr(self.engine, "initialized", False):
            # initialize() builds slot state + KV cache even when params
            # were injected by the caller
            self.engine.initialize()
        if getattr(self.engine, "config", None) is not None and getattr(
            self.engine.config, "precompile", False
        ):
            self.engine.precompile()
        self.engine.start()
        self._runner = web.AppRunner(self.build_app())
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port)
        await site.start()
        logger.info(f"inference server on {self.address}")

    async def astop(self) -> None:
        if self._runner:
            await self._runner.cleanup()
        self.engine.stop()

    def run_forever(self) -> None:
        loop = asyncio.new_event_loop()
        loop.run_until_complete(self.astart())
        try:
            loop.run_forever()
        finally:
            loop.run_until_complete(self.astop())


RELAY_FANOUT = 2  # branching factor of the weight-broadcast tree


def _split_relay(addrs: list[str]) -> list[list[str]]:
    """Partition downstream addresses into RELAY_FANOUT contiguous subtrees
    (each list's head is the next hop; its tail is that hop's own relay)."""
    k = min(RELAY_FANOUT, len(addrs))
    step = -(-len(addrs) // k)
    return [addrs[i : i + step] for i in range(0, len(addrs), step)]


def _relay_bucket(
    group: list[str], body: bytes, path_qs: str, timeout: float = 300.0
) -> None:
    import urllib.request

    head, tail = group[0], group[1:]
    headers = {
        "Content-Type": "application/octet-stream",
        wire.RELAY_TIMEOUT_HEADER: str(timeout),
    }
    if tail:
        headers[wire.RELAY_HEADER] = ",".join(tail)
    req = urllib.request.Request(
        f"http://{head}{path_qs}", data=body, headers=headers, method="POST"
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        r.read()


def encode_weight_bucket(entries: list[tuple[str, np.ndarray]]) -> bytes:
    """Wire format for streamed weight buckets: 8-byte LE header length, a
    json header [{name, dtype, shape}], then the raw array bytes in order.
    bf16 arrays travel as raw bf16 (half the fp32 npz bytes of round 1)."""
    import struct

    header = []
    bufs = []
    for name, arr in entries:
        arr = np.ascontiguousarray(arr)
        header.append(
            {"name": name, "dtype": arr.dtype.name, "shape": list(arr.shape)}
        )
        bufs.append(arr.tobytes())
    hjson = json.dumps(header).encode()
    return struct.pack("<Q", len(hjson)) + hjson + b"".join(bufs)


def decode_weight_bucket(body: bytes) -> dict:
    import struct

    import ml_dtypes

    (hlen,) = struct.unpack_from("<Q", body, 0)
    header = json.loads(body[8 : 8 + hlen].decode())
    flat = {}
    off = 8 + hlen
    for ent in header:
        dtype = np.dtype(
            ml_dtypes.bfloat16 if ent["dtype"] == "bfloat16" else ent["dtype"]
        )
        n = int(np.prod(ent["shape"])) if ent["shape"] else 1
        nbytes = n * dtype.itemsize
        flat[ent["name"]] = np.frombuffer(
            body, dtype=dtype, count=n, offset=off
        ).reshape(ent["shape"])
        off += nbytes
    assert off == len(body), f"bucket size mismatch: {off} != {len(body)}"
    return flat


def _unflatten(flat: dict) -> dict:
    out: dict = {}
    for k, v in flat.items():
        parts = k.split("/")
        d = out
        for p in parts[:-1]:
            d = d.setdefault(p, {})
        d[parts[-1]] = v
    return out


def flatten_params(params: dict, prefix="") -> dict:
    flat = {}
    for k, v in params.items():
        key = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            flat.update(flatten_params(v, key))
        else:
            flat[key] = np.asarray(v)
    return flat


class ServerThread:
    """In-process server for tests and single-host colocated runs."""

    def __init__(self, config: ServerConfig, engine: DecodeEngine | None = None):
        self.server = InferenceServer(config, engine)
        self._loop: asyncio.AbstractEventLoop | None = None
        self._thread: threading.Thread | None = None

    @property
    def address(self) -> str:
        return self.server.address

    @property
    def engine(self) -> DecodeEngine:
        return self.server.engine

    def start(self) -> None:
        started = threading.Event()
        # created before the thread exists so `self._loop` is never written
        # concurrently with a reader's None-check (arealint THR001)
        self._loop = asyncio.new_event_loop()

        def run():
            asyncio.set_event_loop(self._loop)
            self._loop.run_until_complete(self.server.astart())
            started.set()
            self._loop.run_forever()

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()
        if not started.wait(300):
            raise TimeoutError("inference server failed to start")

    def stop(self) -> None:
        if self._loop:
            fut = asyncio.run_coroutine_threadsafe(self.server.astop(), self._loop)
            try:
                fut.result(30)
            except Exception:  # noqa: BLE001 — a wedged graceful stop must
                # not hang the caller (test teardown, supervisor respawn);
                # force the loop down instead
                logger.warning(
                    "graceful server stop failed; forcing loop stop",
                    exc_info=True,
                )
            self._loop.call_soon_threadsafe(self._loop.stop)
        if self._thread:
            self._thread.join(timeout=30)


def main(argv=None) -> None:
    """CLI: python -m areal_tpu.inference.server --config x.yaml key=val ...

    Registers its address in name_resolve like the reference's server
    wrappers (infra/launcher/sglang_server.py:86-253)."""
    import argparse

    from areal_tpu.api.config import load_expr_config

    p = argparse.ArgumentParser()
    p.add_argument("--name", default="", help="name_resolve key to register")
    args, rest = p.parse_known_args(argv)
    cfg, _ = load_expr_config(rest, ServerConfig)
    server = InferenceServer(cfg)
    pre_cfg = getattr(cfg, "preemption", None)
    if pre_cfg is not None and pre_cfg.enabled:
        # preemption-tolerant serving (docs/fault_tolerance.md): SIGTERM /
        # SIGUSR1 only set a flag; the drainer thread (armed BEFORE the
        # handler installs) closes admission, finish-or-parks in-flight
        # decodes within the drain budget, deregisters from the fleet,
        # persists the flight ring (composing with the PR 7 dump), and
        # exits cleanly inside the grace window
        from areal_tpu.robustness.preemption import PreemptionHandler

        handler = PreemptionHandler(
            role="inference_server",
            grace_s=pre_cfg.grace_s,
            handle_sigusr1=pre_cfg.handle_sigusr1,
        )

        def drain_replica(h: PreemptionHandler) -> None:
            budget = min(pre_cfg.drain_budget_s, max(0.0, h.remaining() - 2.0))
            # terminal: this process is exiting — /undrain (ops or the
            # autopilot's scale-up) must not re-open admission on it
            server.engine.drain(budget, terminal=True)
            if args.name:
                try:
                    name_resolve.delete(args.name)
                except Exception:  # noqa: BLE001 — a dead discovery backend
                    # must not eat the remaining grace window
                    logger.warning("name_resolve deregister failed", exc_info=True)
            ring = tl_mod.get_flight_recorder()
            try:
                ring.dump(tl_mod.default_dump_path("preempt"), "preempt")
            except OSError:
                logger.exception("preempt flight dump failed")

        handler.spawn_drainer(drain_replica, exit_code=pre_cfg.exit_code)
        handler.install()
    else:
        # flight recorder: persist the significant-event ring on SIGTERM so
        # an externally killed replica still leaves a postmortem artifact
        tl_mod.install_signal_dump()
    if args.name:
        name_resolve.add(args.name, server.address, keepalive_ttl=None)
    server.run_forever()


if __name__ == "__main__":
    main()
