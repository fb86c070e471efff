"""Remote inference client: interruptible generation over an HTTP fleet.

Behavioral parity with reference areal/infra/remote_inf_engine.py (1,413 LoC)
+ engine/sglang_remote.py: implements the InferenceEngine contract against
N inference-server addresses. The heart is the **interruptible agenerate
loop** (reference :703-867): on ``stop_reason == "abort"`` (server paused for
a weight update) it waits out the pause and re-submits with the accumulated
tokens, preserving per-token policy versions across the interruption; the
rid→server affinity cache keeps resumed requests on the same server for KV
reuse (reference :753-763).

Weight updates ride the zero-pause protocol (docs/weight_sync.md): buckets
stream and stage while the fleet keeps generating; only the commit swap is
fenced (``weight_commit_fence``), so with the default "hold" fence the abort
path above never fires for updates — sequences spanning a commit simply
carry mixed per-token versions.
"""

from __future__ import annotations

import asyncio
import random
import threading
import time
from collections import OrderedDict
from typing import Callable

import aiohttp
import numpy as np

from areal_tpu.api.config import InferenceEngineConfig
from areal_tpu.api.engine_api import InferenceEngine
from areal_tpu.api import wire
from areal_tpu.api.io_struct import (
    TIMING_FIELDS,
    ModelRequest,
    ModelResponse,
    StopReason,
    WeightUpdateMeta,
)
from areal_tpu.infra.workflow_executor import WorkflowExecutor
from areal_tpu.observability import catalog, tracecontext
from areal_tpu.robustness import retry as _retry
from areal_tpu.robustness.chaos import FaultInjector
from areal_tpu.robustness.retry import FleetHealth, RetryBudget, RetryPolicy
from areal_tpu.routing import AffinityMap, Router
from areal_tpu.utils import logging as alog, name_resolve
from areal_tpu.utils.data import TensorDict

logger = alog.getLogger("remote_inf")

# one ClientSession per (event loop, timeout), keyed by a weakref so a
# GC'd loop can't alias a new one (reference workflow_context.py:60-233
# get_aiohttp_session; ADVICE r1: id(loop) keys were reusable after GC and
# the first caller's timeout was frozen for everyone)
import weakref

_SESSIONS: "weakref.WeakKeyDictionary[asyncio.AbstractEventLoop, dict[float, aiohttp.ClientSession]]" = (
    weakref.WeakKeyDictionary()
)


def _get_session(timeout_s: float) -> aiohttp.ClientSession:
    loop = asyncio.get_running_loop()
    per_loop = _SESSIONS.setdefault(loop, {})
    sess = per_loop.get(timeout_s)
    if sess is None or sess.closed:
        sess = aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=timeout_s),
            connector=aiohttp.TCPConnector(limit=512, ttl_dns_cache=300),
        )
        per_loop[timeout_s] = sess
    return sess


async def _close_sessions() -> None:
    loop = asyncio.get_running_loop()
    for sess in _SESSIONS.pop(loop, {}).values():
        if not sess.closed:
            await sess.close()


async def close_loop_sessions() -> None:
    """Public: close THIS event loop's cached ClientSessions. Scripts that
    drive ``agenerate`` inside their own ``asyncio.run`` must call this
    before the loop exits, or its connector leaks ('Unclosed client
    session' warnings) — destroy() only reaches the executor loop's cache."""
    await _close_sessions()


class RemoteJaxEngine(InferenceEngine):
    """Client handle to a fleet of areal_tpu.inference.server instances."""

    def __init__(self, config: InferenceEngineConfig, addresses: list[str] | None = None):
        self.config = config
        self.addresses = list(addresses or [])
        self._version = 0
        self._rr = 0  # round-robin cursor
        # rid -> replica affinity (resumes + pause polls must follow the
        # replica holding the rid's KV). Idle-TTL swept so rids that never
        # complete (crashed caller, abandoned workflow) can't accumulate
        # forever — the gateway's sweep_stale_routes, client-side.
        self._rid_affinity = AffinityMap(ttl_s=config.routing.affinity_ttl_s)
        # cache-aware routing brain (docs/serving.md "Cache-aware
        # routing"): consulted by choose_server when
        # config.routing_policy == "cache_aware"; its snapshot poller
        # starts in initialize(). The shadow prefix index is only fed
        # under that policy — a round-robin client would pay its memory
        # (bounded, but real) for an index nothing reads.
        self.router = Router(
            config.routing, addresses_fn=lambda: list(self.addresses)
        )
        self.executor = WorkflowExecutor(config, engine=self)
        self._paused = False
        self.last_pause_secs = 0.0  # last update's commit-fence window
        self.last_stage_secs = 0.0  # last update's unpaused staging window
        self.last_update_gen_tokens = 0  # fleet tokens during last update
        self._enc_pool = None  # persistent weight-encoder thread (lazy)
        self._metrics = catalog.client_metrics()
        # fault-tolerance layer (robustness/): retrying transport with a
        # shared budget, per-replica circuit breakers, optional chaos hook
        ft = config.fault_tolerance
        self.fleet = FleetHealth(self.addresses, ft)
        budget = (
            RetryBudget(ft.retry_budget, ft.retry_budget_refill)
            if ft.enabled
            else None
        )
        self._retry_policy = RetryPolicy.from_config(
            ft, attempts=config.request_retries, budget=budget
        )
        if not ft.enabled:
            self._retry_policy.jitter = 0.0
        self._robust = catalog.robustness_metrics()
        self._fault_injector: FaultInjector | None = (
            FaultInjector(ft.chaos) if ft.chaos.enabled else None
        )
        self._probe_thread = None
        self._probe_stop = None
        self._lc_obs = catalog.lifecycle_metrics()
        # request lifecycle: in-flight rids per workflow task, so a failed/
        # quarantined task's outstanding generations can be cancelled
        # server-side instead of orphaning slots (docs/request_lifecycle.md)
        self._task_rids_lock = threading.Lock()
        self._task_rids: dict[str, dict[str, str]] = {}  # task_id -> rid -> addr
        # per-workflow-task latency attribution (observability/timeline.py
        # breakdown summed over the task's requests); WorkflowExecutor pops
        # it via take_task_latency for the per-trajectory latency log line.
        # Taken task ids are tombstoned (bounded): a quarantined task's
        # aborted generations resolve AFTER the executor pops, and their
        # late _note_task_latency must not re-create an entry nobody will
        # ever pop again. Tombstones age out by TTL, not count — a busy
        # trainer completes hundreds of tasks while one quarantined task's
        # abort round-trips, and count-based eviction would churn the
        # tombstone out before its stragglers land
        self._task_latency_lock = threading.Lock()
        self._task_latency: dict[str, dict[str, float]] = {}
        self._task_latency_tombstones: "OrderedDict[str, float]" = OrderedDict()
        # abort posts run off-thread through ONE small shared pool: a mass
        # teardown (N coroutines cancelled at once) must not spawn N
        # threads, and a quarantining dispatcher must not serially block on
        # per-rid HTTP posts (threads spawn lazily on first submit)
        from concurrent.futures import ThreadPoolExecutor

        self._abort_pool = ThreadPoolExecutor(
            max_workers=2, thread_name_prefix="abort-request"
        )

    def install_fault_injector(self, injector: FaultInjector | None) -> None:
        """Chaos harness hook: every outgoing HTTP call passes the injector
        before touching the wire (tests + --chaos-self-test)."""
        self._fault_injector = injector

    # -- discovery / lifecycle -------------------------------------------
    def initialize(self, addresses: list[str] | None = None, timeout: float | None = None) -> None:
        if addresses:
            self.addresses = list(addresses)
        if not self.addresses:
            # name_resolve discovery (reference remote_inf_engine.py:379-454)
            key = name_resolve.rollout_server_key(
                self.config.experiment_name, self.config.trial_name
            )
            deadline = time.monotonic() + (timeout or self.config.setup_timeout)
            while not self.addresses and time.monotonic() < deadline:
                self.addresses = name_resolve.get_subtree(key)
                if not self.addresses:
                    time.sleep(0.5)
        assert self.addresses, "no inference server addresses"
        for addr in self.addresses:
            self.fleet.track(addr)  # discovery may have extended the list
        self._wait_healthy(timeout or self.config.setup_timeout)
        self.executor.initialize()
        if self.config.routing_policy == "cache_aware" and len(self.addresses) > 1:
            # replica snapshot poller (routing/snapshot.py): /statusz view
            # of queue depth / free pages / prefix-cache state per replica.
            # Single-replica fleets have nothing to choose between.
            self.router.start()
        ft = self.config.fault_tolerance
        if ft.enabled and len(self.addresses) > 1:
            # fleet probe: detects replicas rejoining after a circuit
            # opened and re-syncs their version (single-replica clients
            # have nothing to fail over to, so no thread)
            self.start_fleet_probe()

    def _wait_healthy(self, timeout: float) -> None:
        """Block until every server answers /health with 200.

        Connection-refused/reset means the server is still booting — keep
        waiting quietly. An HTTP error status means the server is UP but
        unhealthy (crash-looping handler, failed model load): log it
        periodically so startup failures are diagnosable instead of
        silently timing out. Either way the last error lands in the
        TimeoutError."""
        import urllib.error
        import urllib.request

        deadline = time.monotonic() + timeout
        for addr in self.addresses:
            last_err: BaseException | None = None
            n_http_err = 0
            while True:
                try:
                    with urllib.request.urlopen(
                        f"http://{addr}/health", timeout=2
                    ) as r:
                        if r.status == 200:
                            break
                        last_err = RuntimeError(f"/health status {r.status}")
                except urllib.error.HTTPError as e:
                    last_err = e
                    n_http_err += 1
                    if n_http_err == 1 or n_http_err % 20 == 0:
                        logger.warning(
                            f"server {addr} is up but /health returns "
                            f"{e.code} ({n_http_err} consecutive) — still "
                            "waiting"
                        )
                except (urllib.error.URLError, ConnectionError, OSError) as e:
                    last_err = e  # not accepting connections yet: still booting
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"server {addr} not healthy after {timeout:.0f}s; "
                        f"last error: {last_err!r}"
                    )
                time.sleep(0.5)

    def destroy(self) -> None:
        self.stop_fleet_probe()
        self.router.stop()
        self._abort_pool.shutdown(wait=False)
        if self._enc_pool is not None:
            self._enc_pool.shutdown(wait=True)
            self._enc_pool = None
        try:
            loop = self.executor.runner._loop
            if loop is not None and loop.is_running():
                asyncio.run_coroutine_threadsafe(_close_sessions(), loop).result(5)
        except Exception:  # noqa: BLE001 — runner may already be down
            pass
        self.executor.destroy()

    # -- fleet probe (replica rejoin detection) ---------------------------
    def start_fleet_probe(self) -> None:
        """Daemon loop probing /health so replicas whose circuit tripped
        open rejoin rotation (and get re-synced) without waiting for the
        half-open window to be discovered by live traffic."""
        if self._probe_thread is not None:
            return
        stop = threading.Event()
        self._probe_stop = stop
        interval = max(0.2, self.config.fault_tolerance.probe_interval_s)

        def loop():
            while not stop.wait(interval):
                try:
                    self.probe_fleet()
                except Exception:  # noqa: BLE001 — probing must never die
                    logger.exception("fleet probe round failed")

        self._probe_thread = threading.Thread(
            target=loop, daemon=True, name="fleet-probe"
        )
        self._probe_thread.start()

    def stop_fleet_probe(self) -> None:
        if self._probe_thread is not None:
            self._probe_stop.set()
            self._probe_thread.join(timeout=5)
            self._probe_thread = None
            self._probe_stop = None

    def probe_fleet(self) -> dict[str, str]:
        """One probe round over every address; replicas seen healthy again
        after an open circuit are closed and re-synced to the current
        version. Returns the fleet state snapshot."""
        import json as _json
        import urllib.request

        ft = self.config.fault_tolerance
        for addr in list(self.addresses):
            # half-open counts as "was down": the recovery window elapsing
            # must not skip the rejoin/resync path
            was_down = self.fleet.state(addr) != _retry.CLOSED
            version = None
            try:
                with urllib.request.urlopen(
                    f"http://{addr}/health", timeout=ft.probe_timeout_s
                ) as r:
                    d = _json.loads(r.read() or b"{}")
                ok = d.get("status") == "ok"
                version = d.get("version")
            except Exception as e:  # noqa: BLE001 — a failed probe IS the signal
                logger.debug(f"fleet probe {addr} failed: {e!r}")
                ok = False
            if ok:
                if was_down:
                    self.fleet.mark_rejoined(addr)
                    # the replica likely restarted (supervision respawn):
                    # its radix tree is empty — the router must read it
                    # as cold, not as holding pre-eviction prefixes
                    self.router.on_replica_reset(addr)
                    self._resync_replica(addr, server_version=version)
            else:
                self.fleet.on_failure(addr)
        return self.fleet.snapshot()

    def _resync_replica(self, addr: str, server_version=None) -> None:
        """A rejoined replica's weights AND version counter are whatever it
        restarted with. Overwriting its version with the current one would
        tag stale-weight tokens as fresh — laundering off-policy samples
        past the staleness bound. So: leave its version truthful (the
        staleness manager then accounts/rejects its rollouts correctly) and
        let the next update_weights fan-out — which includes the replica
        again now its circuit is closed — deliver current weights + version
        atomically. Here we only surface the lag."""
        if server_version is not None and int(server_version) == self._version:
            logger.info(f"replica {addr} rejoined at current v{self._version}")
            return
        self._robust.replica_resyncs.inc()
        logger.warning(
            f"replica {addr} rejoined at v{server_version} (current "
            f"v{self._version}) — serving stale weights until the next "
            "weight update reaches it; staleness accounting stays truthful"
        )

    # -- server choice ----------------------------------------------------
    def choose_server(
        self,
        rid: str | None = None,
        req: ModelRequest | None = None,
        deadline: float | None = None,
    ) -> str:
        """Replica selection. ``req``/``deadline`` give the cache-aware
        policy its inputs (prompt token ids, deadline slack, priority
        class); without them — legacy callers, tests — the policy scores
        on load alone. Selection is placement-only: whichever replica is
        chosen, greedy output is byte-identical."""
        if rid:
            addr = self._rid_affinity.get(rid)
            if addr is not None:
                # affinity only survives while the replica is in rotation;
                # a tripped circuit drops it so the resume fails over
                if self.fleet.allow(addr):
                    if self.config.routing_policy == "cache_aware":
                        self.router.note_affinity(
                            addr,
                            rid,
                            token_ids=(
                                list(req.input_ids)
                                if req is not None
                                else None
                            ),
                        )
                    return addr
                self._rid_affinity.pop(rid)
        pool = self.fleet.healthy() or self.addresses  # all open: best effort
        if self.config.routing_policy == "cache_aware":
            addr = self.router.choose(
                pool,
                rid=rid,
                token_ids=(list(req.input_ids) if req is not None else None),
                deadline=(
                    deadline
                    if deadline is not None
                    else (req.deadline if req is not None else None)
                ),
                priority=(
                    str(req.metadata.get("priority") or "")
                    if req is not None
                    else None
                ),
            ).addr
        elif self.config.schedule_policy == "random":
            addr = random.choice(pool)
        else:  # round_robin
            addr = pool[self._rr % len(pool)]
            self._rr += 1
        if rid:
            self._rid_affinity.set(rid, addr)
        return addr

    # -- generation -------------------------------------------------------
    def _register_task_rid(self, rid: str, addr: str) -> str | None:
        """Track this rid under the current workflow task (if any) so a
        failed/quarantined task's in-flight generations can be cancelled
        server-side. Returns the owning task_id (for deregistration)."""
        if not rid:
            return None
        from areal_tpu.infra import workflow_context

        task_id = workflow_context.get().task_id
        if not task_id:
            return None
        with self._task_rids_lock:
            self._task_rids.setdefault(task_id, {})[rid] = addr
        return task_id

    def _deregister_task_rid(self, task_id: str | None, rid: str) -> None:
        if not task_id:
            return
        with self._task_rids_lock:
            rids = self._task_rids.get(task_id)
            if rids is not None:
                rids.pop(rid, None)
                if not rids:
                    self._task_rids.pop(task_id, None)

    def abort_request(self, rid: str, addr: str | None = None) -> None:
        """Best-effort server-side cancellation of one rid: POST
        /abort_request to the replica holding it (affinity), falling back
        to a fleet-wide fan-out when the owner is unknown. Never raises —
        cancellation is cleanup, not the primary path."""
        if not rid:
            return
        targets = [addr or self._rid_affinity.get(rid)]
        if targets[0] is None:
            targets = list(self.addresses)
        for a in targets:
            try:
                self._post_one_nofail(a, "/abort_request", {"rid": rid})
            except Exception as e:  # noqa: BLE001 — replica may be dead;
                # its slots die with it, so there is nothing to leak there
                logger.debug(f"abort_request({rid}) on {a} failed: {e!r}")
        self._rid_affinity.pop(rid, None)

    def abort_task_requests(self, task_id: str) -> int:
        """Cancel every in-flight generation a workflow task still owns
        (WorkflowExecutor calls this when it quarantines the task as
        poison). The posts run on the shared abort pool so the caller —
        the executor's dispatch loop — never blocks on per-rid HTTP.
        Returns the number of rids queued for cancellation."""
        with self._task_rids_lock:
            rids = self._task_rids.pop(task_id, {})
        for rid, addr in rids.items():
            self._abort_pool.submit(self.abort_request, rid, addr)
        return len(rids)

    async def agenerate(self, req: ModelRequest) -> ModelResponse:
        """Interruptible generation loop (reference :771-867)."""
        g = req.gconfig
        accumulated: list[int] = []
        logprobs: list[float] = []
        versions: list[int] = []
        denoise_pass: list[int] = []  # a block-diffusion model's commit pass of each token
        remaining = g.max_new_tokens
        start = time.monotonic()
        ttft = None
        # stage breakdown summed across abort/resume attempts (each server
        # attempt stamps its own timeline; the logical request is the sum)
        timing = {k: 0.0 for k in TIMING_FIELDS}
        stop_reason = StopReason.ABORT.value
        truncated_by = ""
        attempt_input = list(req.input_ids)
        # request lifecycle: stamp the config default deadline on requests
        # that carry none; it propagates as x-areal-deadline so the server
        # reaps the slot between decode chunks when it expires
        lc = getattr(self.config, "lifecycle", None)
        deadline = req.deadline
        if (
            deadline is None
            and lc is not None
            and lc.enabled
            and lc.default_deadline_s
        ):
            deadline = time.time() + lc.default_deadline_s
        # replica choice AFTER the deadline is known: the cache-aware
        # policy weighs deadline slack (a rush request goes to the
        # emptiest replica, not the warmest cache)
        addr = self.choose_server(req.rid, req=req, deadline=deadline)
        owner_task = self._register_task_rid(req.rid, addr)
        # replica-reported cached-prefix tokens, summed across attempts —
        # the "actual" leg of the router's predicted-vs-actual hit audit
        cached_prefix_tokens = 0

        image_b64 = None
        if req.image_data is not None:
            import base64 as b64
            import io

            buf = io.BytesIO()
            np.save(buf, np.asarray(req.image_data, np.float32))
            image_b64 = b64.b64encode(buf.getvalue()).decode()
        grid_thw = (
            np.asarray(req.image_grid_thw).tolist()
            if req.image_grid_thw is not None
            else None
        )

        # outstanding-request accounting (the router's freshest load
        # signal); `counted` tracks which replica currently holds our +1.
        # Taken immediately before the try so EVERY exit path reaches the
        # finally's end_request — an early raise (bad image payload) must
        # not leak a permanent +1 against a healthy replica.
        self.router.begin_request(addr)
        counted = addr
        try:
            while True:
                payload = {
                    "input_ids": attempt_input,
                    "rid": req.rid,
                    "image_data": image_b64,
                    "image_grid_thw": grid_thw,
                    "deadline": deadline,
                    "sampling_params": {
                        "max_new_tokens": remaining,
                        "greedy": g.greedy,
                        "temperature": g.temperature,
                        "top_p": g.top_p,
                        "top_k": g.top_k,
                        "stop_token_ids": g.stop_token_ids,
                        "max_tokens": g.max_tokens,
                        "ignore_eos": g.ignore_eos,
                        "frequency_penalty": g.frequency_penalty,
                        "denoising_steps": g.denoising_steps,
                        "remasking_strategy": g.remasking_strategy,
                        "confidence_threshold": g.confidence_threshold,
                        # abort-resume aware: tokens already accumulated across
                        # attempts count toward the minimum
                        "min_new_tokens": max(
                            0, g.min_new_tokens - len(accumulated)
                        ),
                    },
                }
                headers = {}
                if deadline is not None:
                    headers[wire.DEADLINE_HEADER] = f"{deadline:.6f}"
                prio = req.metadata.get("priority")
                if prio:
                    # priority class rides to the engine so server-side
                    # TTFT histograms split by class (timeline metrics)
                    headers[wire.PRIORITY_HEADER] = str(prio)
                addr, data = await self._post_json_failover(
                    addr, "/generate", payload, extra_headers=headers or None
                )
                if addr != counted:  # failover moved the request
                    self.router.move_request(counted, addr)
                    counted = addr
                tm = data.get("timing") or {}
                for k in timing:
                    timing[k] += float(tm.get(k) or 0.0)
                if req.rid:
                    # failover may have moved us: resumes + pause-polls must
                    # follow the replica that actually holds the request
                    self._rid_affinity.set(req.rid, addr)
                    if owner_task is not None:
                        # arealint: disable-next=ASY003 microsecond dict update, never held across an await; the registry is shared with sync executor threads (abort_task_requests) so the lock must be a threading one
                        with self._task_rids_lock:
                            rids = self._task_rids.get(owner_task)
                            if rids is not None and req.rid in rids:
                                rids[req.rid] = addr
                toks = data["output_tokens"]
                accumulated.extend(toks)
                logprobs.extend(data["output_logprobs"])
                versions.extend(data["output_versions"])
                denoise_pass.extend(data.get("output_denoise_pass") or [])
                cached_prefix_tokens += int(
                    data.get("cached_prefix_tokens") or 0
                )
                if ttft is None and toks:
                    # prefer the ENGINE's first-token stamp: for the
                    # non-streaming /generate the HTTP response lands after
                    # the attempt's whole decode, so a client-side stamp
                    # here would be ~e2e latency, not TTFT. Anchor on the
                    # response receipt minus the engine's own latency —
                    # that locates the engine submit instant on the client
                    # clock even when failover/backoff burned time BEFORE
                    # the successful replica accepted the request
                    eng_ttft = float(data.get("ttft") or 0.0)
                    eng_lat = float(data.get("latency") or 0.0)
                    t_end = time.monotonic()
                    if eng_ttft > 0 and eng_lat > 0:
                        ttft = max(0.0, (t_end - start) - eng_lat + eng_ttft)
                    else:
                        ttft = t_end - start
                stop_reason = data["stop_reason"]
                truncated_by = data.get("truncated_by", "") or ""
                remaining -= len(toks)
                if stop_reason != StopReason.ABORT.value or remaining <= 0:
                    if remaining <= 0 and stop_reason == StopReason.ABORT.value:
                        stop_reason = StopReason.LENGTH.value
                    break
                if deadline is not None and time.time() > deadline:
                    # expired while waiting out a pause: stop resubmitting —
                    # the partial output is the answer
                    stop_reason = StopReason.DEADLINE.value
                    truncated_by = "deadline"
                    break
                # server paused for a weight update: wait, then resume with
                # the accumulated sequence (KV re-prefilled server-side)
                await self._await_unpaused(addr)
                attempt_input = list(req.input_ids) + accumulated
        except asyncio.CancelledError:
            # the caller cancelled this coroutine (task failure, agent
            # teardown): cancel the server-side work too instead of leaving
            # the slot decoding for nobody. Fire-and-forget on the shared
            # abort pool — this loop is being torn down, and a mass cancel
            # must not spawn a thread per coroutine.
            try:
                self._abort_pool.submit(self.abort_request, req.rid, addr)
            except RuntimeError:
                # destroy() already shut the pool down (loop teardown after
                # engine teardown); cancellation must still propagate clean
                pass
            raise
        finally:
            # on error paths too (retry/backpressure exhaustion): retries
            # use fresh rids, so a surviving entry is a pure leak
            self.router.end_request(counted)
            self._rid_affinity.pop(req.rid, None)
            self._deregister_task_rid(owner_task, req.rid)

        # routing feedback (success paths only): the finished sequence is
        # now presumably radix-cached on its replica (shadow prefix index),
        # the TTFT feeds the replica's EWMA, and a replica-reported cache
        # hit closes the predicted-vs-actual audit loop
        # the hit audit is gated like the shadow feed: without the
        # cache-aware policy there are no predictions, and actual-hit
        # counts alone would read as shadow-index drift on the dashboard
        cache_aware = self.config.routing_policy == "cache_aware"
        self.router.note_result(
            addr,
            ids=(
                list(req.input_ids) + accumulated if cache_aware else None
            ),
            version=versions[-1] if versions else self._version,
            ttft_s=ttft,
            cached_prefix_tokens=cached_prefix_tokens if cache_aware else 0,
        )
        resp = ModelResponse(
            input_tokens=list(req.input_ids),
            output_tokens=accumulated,
            output_logprobs=logprobs,
            output_versions=versions,
            output_denoise_pass=denoise_pass,
            stop_reason=stop_reason,
            truncated_by=truncated_by,
            latency=time.monotonic() - start,
            ttft=ttft or (time.monotonic() - start),
            **timing,
            rid=req.rid,
            metadata=dict(req.metadata),
        )
        if owner_task is not None:
            self._note_task_latency(owner_task, resp)
        return resp

    def _note_task_latency(self, task_id: str, resp: ModelResponse) -> None:
        """Fold one finished request's stage breakdown into its workflow
        task's aggregate (popped by WorkflowExecutor per trajectory)."""
        with self._task_latency_lock:
            if task_id in self._task_latency_tombstones:
                return  # straggler of an already-popped (quarantined) task
            agg = self._task_latency.setdefault(
                task_id,
                {
                    "requests": 0.0,
                    "tokens": 0.0,
                    "e2e_s": 0.0,
                    **{k: 0.0 for k in TIMING_FIELDS},
                    "ttft_max_s": 0.0,
                },
            )
            agg["requests"] += 1
            agg["tokens"] += resp.output_len
            agg["e2e_s"] += resp.latency
            for k in TIMING_FIELDS:
                agg[k] += getattr(resp, k)
            agg["ttft_max_s"] = max(agg["ttft_max_s"], resp.ttft)

    def take_task_latency(self, task_id: str) -> dict[str, float] | None:
        """Pop the accumulated latency breakdown of one workflow task (all
        generation requests it issued). None when nothing was recorded."""
        now = time.monotonic()
        with self._task_latency_lock:
            self._task_latency_tombstones[task_id] = now
            ts = self._task_latency_tombstones
            # insertion order is time order: purge from the oldest end
            while ts and (
                now - next(iter(ts.values())) > 600.0 or len(ts) > 65536
            ):
                ts.popitem(last=False)
            return self._task_latency.pop(task_id, None)

    async def _await_unpaused(self, addr: str) -> None:
        while True:
            try:
                d = await self._get_json(addr, "/metrics")
                # server_paused is the server's authoritative boolean;
                # "paused" is kept as a fallback for pre-observability
                # servers (and may be an engine stat on new ones)
                if not d.get("server_paused", d.get("paused")):
                    return
            except Exception as e:  # noqa: BLE001 — server mid-restart
                logger.debug(f"pause-poll on {addr} failed: {e!r}")
                if self.fleet.state(addr) == _retry.OPEN:
                    # the replica left rotation while we waited — stop
                    # polling a corpse; the resume request fails over
                    return
            await asyncio.sleep(0.1)

    async def _post_json(self, addr: str, path: str, payload: dict) -> dict:
        """Retrying POST pinned to one address (no failover)."""
        _, data = await self._post_json_failover(
            addr, path, payload, failover=False
        )
        return data

    async def _post_json_failover(
        self,
        addr: str,
        path: str,
        payload: dict,
        failover: bool = True,
        extra_headers: dict | None = None,
    ) -> tuple[str, dict]:
        """POST through the retry policy + circuit breakers, failing over to
        a healthy replica when the target trips open. Returns
        ``(address_that_answered, json)`` so callers can repair affinity.

        429 (admission rejected) is backpressure, not replica failure: it
        never trips the circuit or triggers failover (a saturated fleet
        would cascade), and it does NOT consume the bounded failure-retry
        attempts — sustained shedding would otherwise convert into client
        exceptions within ~attempts×Retry-After. Instead 429 waits honor
        Retry-After under their own wall-clock budget,
        ``lifecycle.backpressure_wait_s``."""
        ft = self.config.fault_tolerance
        policy = self._retry_policy
        can_failover = failover and ft.enabled and ft.failover
        last_exc: Exception | None = None
        headers = tracecontext.inject()
        if extra_headers:
            headers = {**headers, **extra_headers}
        lc = getattr(self.config, "lifecycle", None)
        bp_budget = (
            lc.backpressure_wait_s if lc is not None and lc.enabled else 0.0
        )
        retry_after = 0.0  # >0 after a 429: sleep this instead of backoff
        attempt = 0  # failed-POST attempts; 429 backpressure doesn't count
        bp_deadline: float | None = None  # wall budget for 429 waits
        while attempt < policy.attempts:
            if retry_after > 0:
                await asyncio.sleep(retry_after)
                retry_after = 0.0
            elif attempt > 0:
                if not policy.allow_retry():
                    self._robust.budget_exhausted.inc()
                    break
                self._robust.retries.labels(kind="post").inc()
                await asyncio.sleep(policy.delay(attempt - 1))
            if not self.fleet.allow(addr):
                alt = self.fleet.pick_failover(addr) if can_failover else None
                if alt is not None:
                    self._robust.failovers.inc()
                    addr = alt
                # no healthy alternative: try the tripped replica anyway —
                # a long-shot request beats guaranteed failure
            try:
                if self._fault_injector is not None:
                    await self._fault_injector.aperturb(addr, path)
                sess = _get_session(self.config.request_timeout)
                async with sess.post(
                    f"http://{addr}{path}", json=payload, headers=headers
                ) as r:
                    if r.status == 429:
                        try:
                            retry_after = float(
                                r.headers.get("Retry-After", "1")
                            )
                        except ValueError:
                            retry_after = 1.0
                        # client-side half of the thundering-herd fix:
                        # even against a pre-jitter server (or a proxy
                        # that rounded the hint), scatter the wait into
                        # [x, x*(1+jitter)] so the herd never re-arrives
                        # on one tick
                        bp_jitter = (
                            getattr(lc, "retry_after_jitter", 0.0) or 0.0
                            if lc is not None and lc.enabled
                            else 0.0
                        )
                        if bp_jitter > 0 and retry_after > 0:
                            retry_after *= (
                                1.0 + random.random() * bp_jitter
                            )
                        try:
                            body_429 = await r.json()
                        except Exception:  # noqa: BLE001 — a bare 429 is
                            # still backpressure; the body is a hint only
                            body_429 = {}
                        drained_over = False
                        if body_429.get("reason") == "draining" and can_failover:
                            # a DRAINING replica is leaving the fleet (ops
                            # drain, autopilot scale-down, preemption) —
                            # waiting out Retry-After for it to come back
                            # is wrong; go to a sibling now. Parked work
                            # resumes elsewhere with a re-prefill. The hop
                            # still pays a short pace and rides the
                            # backpressure budget below: a whole fleet
                            # draining at once (preemption wave) must not
                            # become a zero-sleep ping-pong request storm
                            # against replicas trying to leave.
                            alt = self.fleet.pick_failover(addr)
                            if alt is not None and alt != addr:
                                self._robust.failovers.inc()
                                last_exc = RuntimeError(
                                    f"replica {addr} draining"
                                )
                                addr = alt
                                retry_after = min(retry_after, 0.05)
                                drained_over = True
                        if not drained_over:
                            if self.config.routing_policy == "cache_aware":
                                # backpressure is routing signal, not replica
                                # death: demote this replica's score for a few
                                # seconds so new placements drift elsewhere —
                                # the circuit/failover machinery stays out of it
                                self.router.note_backpressure(addr)
                            last_exc = RuntimeError(
                                f"admission rejected (429) by {addr}{path}"
                            )
                        now = time.monotonic()
                        if bp_deadline is None:
                            bp_deadline = now + bp_budget
                        if now + retry_after > bp_deadline:
                            break  # saturated past the backpressure budget
                        continue  # backpressure: no failure attempt burned
                    r.raise_for_status()
                    data = await r.json()
                self.fleet.on_success(addr)
                policy.on_success()
                return addr, data
            except asyncio.CancelledError:
                raise
            except Exception as e:  # noqa: BLE001
                last_exc = e
                self.fleet.on_failure(addr)
                if can_failover:
                    alt = self.fleet.pick_failover(addr)
                    if alt is not None and alt != addr:
                        self._robust.failovers.inc()
                        addr = alt
                attempt += 1
        raise RuntimeError(f"POST {addr}{path} failed after retries") from last_exc

    # metric scrapes must not inherit the hour-scale generation timeout: a
    # dead server would park the caller (the pause-wait loop, the fleet
    # aggregator) for request_timeout seconds per probe
    _SCRAPE_TIMEOUT_S = 5.0

    async def _get_json(
        self, addr: str, path: str, timeout: float | None = None
    ) -> dict:
        """GET with a short timeout and a single retry with backoff, so one
        dead server cannot stall a scrape/poll loop."""
        timeout = timeout or min(
            self._SCRAPE_TIMEOUT_S, self.config.request_timeout
        )
        policy = self._retry_policy
        last_exc: Exception | None = None
        for attempt in range(2):  # initial try + one retry (scrapes stay cheap)
            if attempt > 0:
                if not policy.allow_retry():
                    self._robust.budget_exhausted.inc()
                    break
                self._metrics.scrape_retries.inc()
                self._robust.retries.labels(kind="scrape").inc()
                await asyncio.sleep(policy.delay(0))
            try:
                if self._fault_injector is not None:
                    await self._fault_injector.aperturb(addr, path)
                sess = _get_session(timeout)
                async with sess.get(
                    f"http://{addr}{path}", headers=tracecontext.inject()
                ) as r:
                    r.raise_for_status()
                    data = await r.json()
                self.fleet.on_success(addr)
                policy.on_success()
                return data
            except asyncio.CancelledError:
                raise
            except Exception as e:  # noqa: BLE001
                last_exc = e
                self.fleet.on_failure(addr)
        raise RuntimeError(f"GET {addr}{path} failed after retry") from last_exc

    def _fanout_targets(self) -> list[str]:
        """The snapshot of replicas a multi-step fan-out protocol should
        address. Only CLOSED (fully in-rotation) replicas participate: an
        OPEN one is dead, and a HALF_OPEN one is a recovering maybe —
        neither can be *required* to ack a weight update. Callers running
        begin→buckets→commit sequences must take ONE snapshot and reuse it,
        so a replica rejoining mid-protocol cannot receive a commit for
        buckets it never staged. Falls back to every address when none are
        closed (best effort beats guaranteed failure)."""
        if not self.config.fault_tolerance.enabled:
            return list(self.addresses)
        closed = [
            a for a in self.addresses if self.fleet.state(a) == _retry.CLOSED
        ]
        skipped = [a for a in self.addresses if a not in closed]
        if skipped and closed:
            logger.warning(f"fan-out skipping out-of-rotation replicas {skipped}")
            return closed
        return list(self.addresses)

    def _retry_sync(self, addr: str, path: str, send):
        """One address, retried in place through the shared policy (the
        sync twin of the transport loop in _post_json_failover). Fan-out
        calls are not failover-able — they must reach this replica — so an
        ultimate failure raises."""
        policy = self._retry_policy
        last_exc: Exception | None = None
        for attempt in range(policy.attempts):
            if attempt > 0:
                if not policy.allow_retry():
                    self._robust.budget_exhausted.inc()
                    break
                self._robust.retries.labels(kind="fanout").inc()
                time.sleep(policy.delay(attempt - 1))
            try:
                if self._fault_injector is not None:
                    self._fault_injector.perturb(addr, path)
                out = send(addr)
                self.fleet.on_success(addr)
                policy.on_success()
                return out
            except Exception as e:  # noqa: BLE001
                last_exc = e
                self.fleet.on_failure(addr)
        raise RuntimeError(f"POST {addr}{path} failed after retries") from last_exc

    def _send_json_once(
        self, addr: str, path: str, payload: dict, timeout: float
    ) -> dict:
        """The ONE place that builds a synchronous JSON POST (both the
        retried and the no-retry fan-out paths go through here)."""
        import json
        import urllib.request

        req = urllib.request.Request(
            f"http://{addr}{path}",
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read() or b"{}")

    def _post_json_one(
        self, addr: str, path: str, payload: dict, timeout: float | None = None
    ) -> dict:
        """Synchronous retried JSON POST to ONE replica (fan-out building
        block; rides the shared retry policy + circuit accounting).
        ``timeout`` bounds EACH attempt (default: request_timeout)."""
        t = timeout or self.config.request_timeout
        return self._retry_sync(
            addr,
            path,
            lambda a: self._send_json_once(a, path, payload, t),
        )

    def _post_all(
        self, path: str, payload: dict, targets: list[str] | None = None
    ) -> list[dict]:
        """Synchronous fan-out (weight updates, pause). ``targets`` lets a
        multi-step protocol pin one _fanout_targets() snapshot across all
        its steps; None snapshots fresh for standalone calls."""
        import concurrent.futures

        targets = targets if targets is not None else self._fanout_targets()
        with concurrent.futures.ThreadPoolExecutor(max_workers=8) as pool:
            return list(
                pool.map(
                    lambda a: self._post_json_one(a, path, payload), targets
                )
            )

    # -- rollout submission (delegated to the executor) -------------------
    def set_completion_callback(self, url: str, worker_id: str = "") -> None:
        """Push task completions to the controller (fleet-scale wait path;
        reference rollout_controller.py per-worker callback servers)."""
        self.executor.set_completion_callback(url, worker_id)

    def submit(
        self, data: dict, workflow=None, should_accept_fn=None, is_eval=False
    ) -> str:
        return self.executor.submit(data, workflow, should_accept_fn, is_eval=is_eval)

    def wait(self, count: int, timeout: float | None = None) -> TensorDict:
        return self.executor.wait(count, timeout)

    def wait_for_task(self, task_id: str, timeout: float | None = None):
        return self.executor.wait_for_task(task_id, timeout)

    def rollout_batch(
        self, data, workflow=None, should_accept_fn=None, is_eval=False
    ) -> TensorDict:
        return self.executor.rollout_batch(
            data, workflow, should_accept_fn, is_eval=is_eval
        )

    def prepare_batch(self, dataloader, workflow=None, should_accept_fn=None) -> TensorDict:
        return self.executor.prepare_batch(dataloader, workflow, should_accept_fn)

    def pause(self) -> None:
        self._paused = True
        self.executor.pause()

    def resume(self) -> None:
        self._paused = False
        self.executor.resume()

    # -- preemption / durability (docs/fault_tolerance.md) -----------------
    def attach_journal(self, journal) -> None:
        """Durable trajectory journal: accepted trajectories survive a
        trainer crash and replay on recovery (infra/trajectory_journal.py)."""
        self.executor.attach_journal(journal)

    def replay_from_journal(self, max_staleness: int | None = None) -> tuple[int, int]:
        return self.executor.replay_from_journal(max_staleness)

    def set_interrupt(self, event) -> None:
        """Preemption: alias the handler's requested-event into the
        executor's blocking waits (they raise RolloutInterrupted)."""
        self.executor.set_interrupt(event)

    # -- server-side generation pause (weight-update window) --------------
    def pause_generation(
        self, targets: list[str] | None = None, mode: str = "abort"
    ) -> None:
        """mode "abort" = legacy §3.4 full pause (in-flight requests abort);
        mode "hold" = zero-pause commit fence (the decode loop idles for one
        commit roundtrip, nothing aborts)."""
        payload = {} if mode == "abort" else {"mode": mode}
        self._post_all("/pause_generation", payload, targets=targets)

    def continue_generation(self, targets: list[str] | None = None) -> None:
        self._post_all("/continue_generation", {}, targets=targets)

    def _fence_fanout(
        self, path: str, payload: dict, addrs: list[str], retried: bool = False
    ) -> list[str]:
        """Parallel per-replica fence fan-out that never raises: returns
        the addresses that acked.

        The two fence legs want opposite transports. The PAUSE leg gets
        one short-timeout attempt per replica (``retried=False``): while
        it runs, siblings that already acked sit fenced, so a dead replica
        must cost seconds, not a backoff budget — and a missed pause only
        means that replica commits unfenced. The CONTINUE leg gets the
        full retry policy (``retried=True``): every replica is posted
        concurrently so nobody waits on a sick one, and a LOST continue
        is the one fence failure with teeth — the replica stays held
        (serving /health ok!) until its hold auto-expires server-side.
        Both legs bound each attempt well under hold_fence_timeout_s so a
        dead replica can never stall the trainer past the self-release."""
        import concurrent.futures

        # pause-leg timeout must exceed the server's 10 s hold-ack wait
        # (h_pause blocks until the decode loop quiesces) — a slow chunk
        # drain is a SUCCESSFUL fence, not a dead replica
        send = (
            (lambda a: self._post_json_one(a, path, payload, timeout=10.0))
            if retried
            else (lambda a: self._send_json_once(a, path, payload, 15.0))
        )
        ok: list[str] = []
        with concurrent.futures.ThreadPoolExecutor(max_workers=8) as pool:
            futs = {a: pool.submit(send, a) for a in addrs}
            for a, f in futs.items():
                try:
                    r = f.result()
                    ok.append(a)
                    if isinstance(r, dict) and r.get("fenced") is False:
                        logger.warning(
                            f"{a} acked the hold but its decode loop had "
                            "not quiesced within the server wait; commit "
                            "may land between its chunks unfenced"
                        )
                except Exception:  # noqa: BLE001 — fence is best-effort
                    logger.warning(
                        f"{path} fence fan-out to {a} failed; proceeding "
                        "without it (a still-held replica self-releases "
                        "after ServerConfig.hold_fence_timeout_s)",
                        exc_info=True,
                    )
        return ok

    def _commit_fence(self, targets: list[str]):
        """Context manager for the commit window, per
        ``config.weight_commit_fence``: "hold" soft-fences the fleet (no
        aborts), "abort" restores the legacy full pause, "none" commits with
        generation running (each replica swaps between decode chunks). The
        fence is best-effort per replica: a pause/continue failure on one
        replica must not fail the commit or leave its siblings fenced —
        that replica just commits unfenced (the swap between decode chunks
        is correct regardless; the fence only tightens fleet simultaneity)."""
        from contextlib import contextmanager

        fence = getattr(self.config, "weight_commit_fence", "hold")
        if fence not in ("hold", "abort", "none"):
            raise ValueError(f"unknown weight_commit_fence {fence!r}")

        @contextmanager
        def cm():
            if fence == "none":
                yield
                return
            payload = {} if fence == "abort" else {"mode": fence}
            paused = self._fence_fanout("/pause_generation", payload, targets)
            try:
                yield
            finally:
                self._fence_fanout(
                    "/continue_generation", {}, paused, retried=True
                )

        return cm()

    def _encoder_pool(self):
        """One persistent encoder thread shared by every update_weights call
        (previously a fresh ThreadPoolExecutor per call, leaked via
        shutdown(wait=False)); closed in destroy()."""
        pool = self._enc_pool
        if pool is None:
            import concurrent.futures

            pool = self._enc_pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="weight-enc"
            )
        return pool

    # -- weights + versioning --------------------------------------------
    def update_weights(self, meta: WeightUpdateMeta, params: dict | None = None) -> None:
        """Zero-pause §3.4 protocol (docs/weight_sync.md): stream and stage
        every bucket WHILE generation continues; only the commit swap sits
        behind a fence. The availability cost of an update therefore scales
        with the commit roundtrip, not with model bytes / wire bandwidth.

        Split windows are measured and exported: ``areal_update_stage_secs``
        (staging, generation running) vs ``areal_update_pause_secs`` (the
        fence; reference target: <3 s at scale, blog/AReaL_v0_2.md:79-83),
        plus ``generation_tokens_during_update`` summed from the commit
        responses — the work the fleet did NOT lose to the update."""
        version = self._version + 1 if meta.with_version else self._version
        # ONE snapshot of in-rotation replicas for the whole begin→stage→
        # commit protocol: a replica rejoining mid-update must not receive
        # a commit for buckets it never staged
        targets = self._fanout_targets()
        if meta.type == "mem" and meta.lora_only:
            # LoRA-delta fast path: one tiny bucket of adapter leaves, no
            # full-tree stream (see WeightUpdateMeta.lora_only). Encoding
            # happens unfenced; only the upload+fold POST is the gap.
            assert params is not None
            assert all("_lora_" in k for k in params), (
                "lora_only update got non-adapter leaves — caller must pass "
                "the flat layers/{t}_lora_{a,b} dict, not the merged tree"
            )
            t_enc = time.monotonic()
            body = self._encode_bucket(sorted(params.items()))
            stage_secs = time.monotonic() - t_enc
            t0 = time.monotonic()
            with self._commit_fence(targets):
                self._post_all_bytes(
                    f"/update_weights_lora?scale={meta.lora_scale}"
                    f"&version={version}",
                    body,
                    targets=targets,
                )
            self._finish_update(
                version,
                stage_secs,
                time.monotonic() - t0,
                gen_tokens=0,
                kind="lora",
            )
            self._metrics.update_bytes.inc(len(body))
            return
        if meta.type == "disk":
            # disk reloads run inside the engine's apply path (the decode
            # loop blocks for the whole load) — the fence covers it all and
            # the window IS the availability gap; no staging to split out
            assert meta.path
            t0 = time.monotonic()
            with self._commit_fence(targets):
                self._post_all(
                    "/update_weights_from_disk",
                    {"path": meta.path, "version": version},
                    targets=targets,
                )
            self._finish_update(
                version, 0.0, time.monotonic() - t0, gen_tokens=0, kind="disk"
            )
            return
        if meta.type != "mem":
            raise NotImplementedError(meta.type)
        assert params is not None
        if meta.wire_format == "q8":
            params = self._quantize_for_wire(params)
        elif meta.wire_format not in (None, "", "bf16"):
            raise ValueError(f"unknown wire_format {meta.wire_format!r}")
        plan = self._plan_weight_buckets(params)
        enc_pool = self._encoder_pool()
        first = enc_pool.submit(self._encode_bucket, plan[0])
        # STAGE — generation keeps running on every replica
        t0 = time.monotonic()
        commit_targets = self._stream_stage_buckets(plan, enc_pool, first, targets)
        stage_secs = time.monotonic() - t0
        # COMMIT — the only fenced window
        import concurrent.futures

        t1 = time.monotonic()
        replies: list[dict] = []
        failed: list[tuple[str, Exception]] = []
        with self._commit_fence(commit_targets):
            with concurrent.futures.ThreadPoolExecutor(max_workers=8) as pool:
                futs = {
                    a: pool.submit(
                        self._post_json_one,
                        a,
                        "/update_weights_commit",
                        {"version": version},
                    )
                    for a in commit_targets
                }
                for a, f in futs.items():
                    try:
                        replies.append(f.result())
                    except Exception as e:  # noqa: BLE001 — tallied below
                        failed.append((a, e))
        if failed:
            # the version number is burned no matter what: a commit POST
            # that failed CLIENT-side (timeout) may still have applied
            # server-side, so some replica may already serve weights tagged
            # `version`. Advance the client counter before raising so a
            # retried update can never reuse the number for DIFFERENT
            # weights (per-token staleness correction depends on version ↔
            # policy being one-to-one; a skipped number is harmless).
            self._version = version
            # failed-commit replicas may still hold their full staged copy
            # (2x weight HBM); committed ones no-op the abort
            self._abort_stage_on([a for a, _ in failed])
            raise RuntimeError(
                f"weight-update commit failed on "
                f"{[a for a, _ in failed]} "
                f"({len(replies)}/{len(commit_targets)} committed)"
            ) from failed[0][1]
        gen_tokens = sum(
            int(r.get("tokens_during_update", 0) or 0) for r in replies
        )
        self._finish_update(
            version, stage_secs, time.monotonic() - t1, gen_tokens, kind="mem"
        )

    def _finish_update(
        self,
        version: int,
        stage_secs: float,
        pause_secs: float,
        gen_tokens: int,
        kind: str,
    ) -> None:
        """Book one completed update: split stage/pause metrics + version."""
        self.last_stage_secs = stage_secs
        self.last_pause_secs = pause_secs
        self.last_update_gen_tokens = gen_tokens
        self._metrics.updates.inc()
        self._metrics.pause_seconds.observe(pause_secs)
        self._metrics.stage_seconds.observe(stage_secs)
        self._metrics.commit_pause_seconds.observe(pause_secs)
        if gen_tokens:
            self._metrics.tokens_during_update.inc(gen_tokens)
        logger.info(
            f"{kind} weight update v{version}: staged {stage_secs:.2f}s "
            f"(unpaused), commit fence {pause_secs:.2f}s, "
            f"{gen_tokens} tokens generated during the update"
        )
        self._version = version
        # the fleet flushed its radix trees at the commit (PR 5
        # across_updates="flush"): the shadow prefix index follows suit
        self.router.on_weight_commit(version)

    @staticmethod
    def _quantize_for_wire(params: dict) -> dict:
        """q8 wire format: pre-quantize the dense projection leaves with the
        SAME transform an int8-serving server runs (qwen.quantize_dense_int8)
        — half the wire bytes, and strictly more faithful than bf16-then-
        server-requantize (no double rounding). The staged tree arrives in
        served form; non-int8 servers reject it at stage time."""
        from areal_tpu.models import qwen

        return qwen.quantize_params_int8(params)

    def _plan_weight_buckets(self, params: dict) -> list[list[tuple[str, object]]]:
        """Greedy-pack flattened leaves into ~weight_chunk_mb buckets."""
        flat: list[tuple[str, object]] = []

        def walk(tree, prefix=""):
            for k, v in tree.items():
                key = f"{prefix}/{k}" if prefix else str(k)
                if isinstance(v, dict):
                    walk(v, key)
                else:
                    flat.append((key, v))

        walk(params)
        limit = max(1, self.config.weight_chunk_mb) * (1 << 20)
        buckets: list[list[tuple[str, object]]] = [[]]
        size = 0
        for key, v in flat:
            if not hasattr(v, "shape"):
                nbytes = 8
            else:
                # wire bytes: floats travel bf16 (except f32 scale planes),
                # int8 stays int8
                kind = getattr(v.dtype, "kind", "f")
                itemsize = (
                    4
                    if key.endswith("_scale")
                    else 2
                    if kind == "f"
                    else v.dtype.itemsize
                )
                nbytes = int(np.prod(v.shape)) * itemsize
            if size and size + nbytes > limit:
                buckets.append([])
                size = 0
            buckets[-1].append((key, v))
            size += nbytes
        return buckets

    @staticmethod
    def _encode_bucket(bucket: list[tuple[str, object]]) -> bytes:
        """Host-transfer + bf16-cast + wire-encode one bucket."""
        import ml_dtypes

        from areal_tpu.inference.server import encode_weight_bucket

        entries = []
        for name, v in bucket:
            arr = np.asarray(jax_leaf_to_host(v))
            if (
                arr.dtype.kind == "f"
                and arr.dtype != np.dtype(ml_dtypes.bfloat16)
                and not name.endswith("_scale")  # q8 scale planes stay f32
            ):
                arr = arr.astype(ml_dtypes.bfloat16)
            entries.append((name, arr))
        return encode_weight_bucket(entries)

    def _stream_stage_buckets(
        self, buckets, enc_pool, first, targets: list[str] | None = None
    ) -> list[str]:
        """Pipelined STAGING upload, fully unpaused: encode bucket i+1
        (device->host + bf16 cast) while bucket i is in flight to every
        server; servers stage each bucket on arrival (device_put or host
        RAM per weight_stage_target) without touching served params, so
        transport/serialisation/H2D all overlap generation. ``first`` is
        bucket 0's encode future. Returns the subset of ``targets`` still
        in rotation afterwards — PR 3's pinned-snapshot rule extended to
        the unpaused stream: a replica whose circuit tripped mid-stage may
        have missed buckets and MUST be excluded from the commit (it
        re-syncs on the next update fan-out, like any rejoining replica).

        With ``weight_update_relay`` and >1 server, each bucket is uploaded
        ONCE to the tree root with an X-Areal-Relay header; servers forward
        down a fanout-2 tree (server.py:_relay_bucket) — the trainer's
        uplink carries 1x the model instead of n_servers x (the reference's
        NCCL broadcast role, fsdp_engine.py:1047-1137)."""
        import concurrent.futures

        ft = self.config.fault_tolerance
        targets = targets if targets is not None else self._fanout_targets()
        live = list(targets)  # replicas still receiving this update
        relay = (
            getattr(self.config, "weight_update_relay", False)
            and len(targets) > 1
        )

        def drop(addr: str, exc: Exception, what: str) -> None:
            """Per-replica failure during the unpaused stream. With fault
            tolerance on and healthy siblings, the sick replica leaves
            THIS update only (it must not receive a commit for buckets it
            missed); it serves stale weights with a truthful version until
            the next fan-out re-syncs it. Relay mode can't drop mid-tree
            — failures there fail the update as before."""
            if relay or not ft.enabled or len(live) <= 1:
                raise exc
            live.remove(addr)
            self._robust.replica_resyncs.inc()
            logger.warning(
                f"replica {addr} failed during weight-update {what}; "
                f"excluded from this update's commit ({exc!r})"
            )

        with concurrent.futures.ThreadPoolExecutor(max_workers=8) as net_pool:

            def fanout(path: str, make_call) -> None:
                futs = {a: net_pool.submit(make_call, a) for a in live}
                for a, f in futs.items():
                    try:
                        f.result()
                    except Exception as e:  # noqa: BLE001 — drop re-raises
                        drop(a, e, path)

            # open the staging areas — generation keeps running throughout
            fanout(
                "/update_weights_begin",
                lambda a: self._post_json_one(a, "/update_weights_begin", {}),
            )

            if relay:
                hdr = {
                    wire.RELAY_HEADER: ",".join(targets[1:]),
                    wire.RELAY_TIMEOUT_HEADER: str(self.config.request_timeout),
                }

                def send(body: bytes) -> None:
                    self._post_bytes(
                        targets[0], "/update_weights_bucket", body, headers=hdr
                    )

            else:

                def send(body: bytes) -> None:
                    fanout(
                        "/update_weights_bucket",
                        lambda a: self._post_bytes(
                            a, "/update_weights_bucket", body
                        ),
                    )

            nxt = first
            try:
                for i in range(len(buckets)):
                    body = nxt.result()
                    if i + 1 < len(buckets):
                        nxt = enc_pool.submit(self._encode_bucket, buckets[i + 1])
                    self._metrics.update_bytes.inc(len(body))
                    send(body)
            except Exception:
                # an unrecoverable stream failure must not leave partial
                # buckets pinning server HBM until the next begin —
                # best-effort abort; serving weights and version stay
                # untouched on every replica (abort drops only staging).
                # Replicas already dropped as dead get the no-retry path:
                # burning the shared retry budget on a known corpse starves
                # concurrent generate/scrape traffic.
                try:
                    self._post_all("/update_weights_abort", {}, targets=live)
                except Exception:  # noqa: BLE001
                    logger.warning(
                        "weight-update abort fan-out failed; servers drop "
                        "the staged buckets at the next begin",
                        exc_info=True,
                    )
                self._abort_stage_on([a for a in targets if a not in live])
                raise
        if not ft.enabled:
            return live
        # a replica whose circuit tripped from CONCURRENT traffic (probe,
        # generate) may have acked its buckets yet be mid-crash — exclude
        # it from the commit too; it re-syncs like any rejoining replica
        healthy = [a for a in live if self.fleet.state(a) == _retry.CLOSED]
        circuit_dropped = [a for a in live if a not in healthy]
        if not healthy:
            raise RuntimeError(
                f"all replicas left rotation mid-stage: {targets}"
            )
        if circuit_dropped:
            logger.warning(
                f"replicas {circuit_dropped} tripped their circuit "
                "mid-stage; excluded from the commit (stale until the next "
                "update fan-out re-syncs them)"
            )
            self._robust.replica_resyncs.inc(len(circuit_dropped))
        # EVERY excluded replica — dropped by a failed bucket POST or by a
        # tripped circuit — gets a best-effort stage-abort: a merely-slow
        # replica that missed one bucket is still alive and would otherwise
        # pin up to a full staged weight copy in HBM until the next begin
        self._abort_stage_on([a for a in targets if a not in healthy])
        return healthy

    def _post_one_nofail(
        self,
        addr: str,
        path: str,
        payload: dict | None = None,
        timeout: float = 2.0,
    ) -> None:
        """Single short-timeout POST outside the retry machinery — for
        calls that must never stall on a sick replica (pause fence posts
        while siblings sit paused; stage-aborts to likely-dead replicas).
        No retries, no circuit accounting."""
        self._send_json_once(addr, path, payload or {}, timeout)

    def _abort_stage_on(self, addrs: list[str]) -> None:
        """Best-effort /update_weights_abort to excluded replicas so a
        partially staged update does not pin HBM until the next begin."""
        for addr in addrs:
            try:
                self._post_one_nofail(addr, "/update_weights_abort")
            except Exception as e:  # noqa: BLE001 — replica likely dead
                logger.debug(f"stage-abort on {addr} failed: {e!r}")

    def _post_all_bytes(
        self, path: str, body: bytes, targets: list[str] | None = None
    ) -> None:
        import concurrent.futures

        targets = targets if targets is not None else self._fanout_targets()
        with concurrent.futures.ThreadPoolExecutor(max_workers=8) as pool:
            list(
                pool.map(
                    lambda addr: self._post_bytes(addr, path, body),
                    targets,
                )
            )

    def _post_bytes(
        self, addr: str, path: str, body: bytes, headers: dict | None = None
    ) -> None:
        import urllib.request

        def send(a):
            req = urllib.request.Request(
                f"http://{a}{path}",
                data=body,
                headers={
                    "Content-Type": "application/octet-stream",
                    **(headers or {}),
                },
                method="POST",
            )
            with urllib.request.urlopen(
                req, timeout=self.config.request_timeout
            ) as r:
                r.read()

        self._retry_sync(addr, path, send)

    def set_version(self, version: int) -> None:
        self._version = version
        self.router.on_weight_commit(version)
        try:
            self._post_all("/set_version", {"version": version})
        except Exception:  # noqa: BLE001 — servers may be mid-update
            logger.warning("set_version fan-out failed", exc_info=True)

    def get_version(self) -> int:
        return self._version

    def get_capacity(self) -> int:
        return self.executor.staleness.get_capacity()

    def export_stats(self) -> dict[str, float]:
        stats = self.executor.export_stats()
        stats["update_weights_pause_secs"] = self.last_pause_secs
        stats["update_weights_stage_secs"] = self.last_stage_secs
        stats["generation_tokens_during_update"] = float(
            self.last_update_gen_tokens
        )
        return stats


def jax_leaf_to_host(x):
    """Device array -> host numpy (bf16 preserved via ml_dtypes)."""
    if isinstance(x, np.ndarray):
        return x
    import jax

    return np.asarray(jax.device_get(x))


def jax_tree_to_host(params: dict) -> dict:
    import jax

    return jax.tree.map(jax_leaf_to_host, params)
