"""TPU-native generation engine: continuous batching + interruptible decode.

Replaces the external SGLang/vLLM servers the reference depends on
(areal/engine/sglang_remote.py, vllm_remote.py + infra/launcher/*_server.py)
with a JAX decode engine built for the async-RL protocol (SURVEY §7.1):

Three owners serve a request, and the arrows point one way. This module is
the public surface and the **pass scheduler**: the request queue, the loop
(hold, reap, admit, one packed scatter, dispatch, drain), lifecycle, drain,
weights. It asks ``decode_programs.DecodePrograms`` for callables (which
jitted programs exist, under which key, which a start-up warms) and
``slot_cache.SlotCache`` for pages (who owns a page: live slot, parked
request, prefix tree), and threads the device arrays (weights, the paged
cache, the slot state, the rng) through the donated programs itself.

- **slot-based continuous batching over a paged KV cache**: S decode slots
  draw fixed-size KV pages from a shared pool via host-side block tables.
  Requests admit into free slots via a bucketed prefill, then all slots
  step together in a jitted multi-token decode chunk
  (``decode_steps_per_call``) — static shapes everywhere, a bounded set of
  compiled programs. The device queue is at most two chunks deep: the
  running one and, from the pass's commit point part-way through it
  (inference/commit_point.py), the next; what arrives before that point is
  admitted into the next chunk.
- **GRPO prefix sharing by page aliasing**: a group's identical prompts
  prefill once; duplicates share the full prompt pages and copy only the
  final partial page. Pool exhaustion evicts cached and parked KV (the
  slot cache's ladder), then preempts the highest-budget slots (abort +
  client retry; the scheduler's choice).
- **interruptible generation** (the reference's crown jewel,
  remote_inf_engine.py:771-867 + §3.4 pause protocol):
  ``pause_generation("abort")`` completes all in-flight requests with
  ``stop_reason="abort"`` and their partial tokens; the client loops,
  re-submitting accumulated prompts after ``continue_generation``. Weight
  swaps happen between chunks, so aborts cost at most one chunk of latency.
- **zero-pause weight sync** (docs/weight_sync.md): streamed buckets stage
  via ``begin_staged_update``/``stage_weight_bucket`` WHILE generation
  continues (staging never touches served params); the commit is a pointer
  swap between decode chunks, optionally behind a ``pause_generation("hold")``
  soft fence that idles the loop for one commit roundtrip WITHOUT aborting.
  Sequences that span a commit simply carry both versions token-by-token.
- **per-token policy versions**: every emitted token is stamped with the
  weight version that produced it — the input to decoupled-PPO staleness
  correction (reference io_struct.py output_versions). Version tags are
  chunk-granular: tokens before a commit carry v, tokens after carry v+1,
  within one response.

The engine is transport-free; inference/server.py wraps it in aiohttp HTTP
speaking the reference's small protocol (/generate, /pause_generation, ...).
"""

from __future__ import annotations

import contextlib
import queue
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from areal_tpu.api.config import ServerConfig
from areal_tpu.api import io_struct
from areal_tpu.api.io_struct import ModelRequest, ModelResponse, StopReason
from areal_tpu import models
from areal_tpu.inference import commit_point
from areal_tpu.inference.decode_programs import PREFILL_SIZES, TOPK_CAP, DecodePrograms, pack_row, slot_state
from areal_tpu.inference.slot_cache import SlotCache
from areal_tpu.models import qwen
from areal_tpu.models.hf import load_params_from_hf
from areal_tpu.observability import catalog as obs_catalog
from areal_tpu.observability import hw_accounting as hw
from areal_tpu.observability import kernel_probe
from areal_tpu.observability import timeline as tl_mod
from areal_tpu.parallel import mesh as mesh_lib
from jax import set_mesh
from areal_tpu.utils import compile_cache
from areal_tpu.utils import logging as alog
from areal_tpu.utils import perf_tracer

logger = alog.getLogger("decode_engine")

# the exact leaf names quantize_params_int8 produces — suffix matching would
# misroute any future base param that happens to end in _scale (ADVICE r04)
_SERVED_FORM_LEAVES = frozenset(
    f"{t}{suf}" for t in qwen.QUANT_TARGETS for suf in ("_q8", "_scale")
)

# a start from idle without a prefix cache (DecodeEngine._await_siblings): the
# admission waits until no request has arrived for SIBLING_GAP_S_PER_TOKEN a
# token of the longest queued prompt (a twentieth of what a prompt token costs
# the slowest family served, 125 us on a v5e), SIBLING_GAP_S at most, and
# SIBLING_WAIT_S in all
SIBLING_GAP_S_PER_TOKEN = 5e-6
SIBLING_GAP_S = 0.05
SIBLING_WAIT_S = 0.5

# the row ledger of a drained chunk (DecodeEngine._drain): the steps it ran,
# the rows it stepped for, and the row-steps that were spent on a request
# already ended or dropped with a request gone by the drain; the live ones are
# the tokens credited, so tokens + spent + dropped == rows x steps
LEDGER_KEYS = ("steps", "rows", "spent", "dropped")


@dataclass
class _Task:
    req: ModelRequest
    callback: Callable[[ModelResponse], None]
    submit_time: float = field(default_factory=time.monotonic)
    slot: int = -1
    prompt_len: int = 0
    out_tokens: list[int] = field(default_factory=list)
    out_logprobs: list[float] = field(default_factory=list)
    out_versions: list[int] = field(default_factory=list)
    # a block model's: the pass of its block (0-based) that committed each token
    out_passes: list[int] = field(default_factory=list)
    first_token_time: float | None = None
    # lifecycle truncation flag carried into the response: "deadline",
    # "watchdog", or "cancelled" ("" = normal termination)
    truncated_by: str = ""
    # request timeline (observability/timeline.py): stage events + the
    # fence-stall/park accumulators, attached at submit time
    timeline: tl_mod.RequestTimeline | None = None


def _iter_tree_paths(tree: dict, prefix: str = ""):
    for k, v in tree.items():
        key = f"{prefix}/{k}" if prefix else str(k)
        if isinstance(v, dict):
            yield from _iter_tree_paths(v, key)
        else:
            yield key, v


class DecodeEngine:
    """Continuous-batching generation over one model replica."""

    def __init__(
        self,
        config: ServerConfig,
        params: dict | None = None,
        model_cfg: "qwen.ModelConfig | models.hybrid.HybridConfig | None" = None,
        mesh=None,
    ):
        self.config = config
        self.params = params
        self.model_cfg = model_cfg
        # the module of model_cfg's family (models.family_of): every forward
        # the engine runs is called through it. Set in initialize()
        self.model = None
        self.mesh = mesh
        self._version = 0
        self._paused = threading.Event()  # set = paused (aborts in-flight)
        self._held = threading.Event()  # set = commit fence (no aborts)
        # _pause_ack's contract is strict: no chunk in flight AND _abort_all
        # completed — release_memory depends on it. The hold fence acks on
        # its OWN event (slots stay live under a hold; the two must never
        # be conflated)
        self._pause_ack = threading.Event()  # loop reached the ABORT branch
        self._hold_ack = threading.Event()  # loop reached the hold fence
        self._hold_since = 0.0  # monotonic ts of the current hold fence
        self._shutdown = threading.Event()
        self._queue: queue.Queue[_Task] = queue.Queue()
        self._pending_weight_update: tuple[str, Any, int] | None = None
        self._weight_lock = threading.Lock()
        self._thread: threading.Thread | None = None
        # the jitted programs (decode_programs.py) and the page-and-slot ledger
        # (slot_cache.py: pool, prefix tree, slot page lists, page table, parked
        # requests): built in initialize(), the ledger anew at resume_memory()
        self.programs: DecodePrograms | None = None
        self.slots: SlotCache | None = None
        self._wakeup = threading.Event()
        self._backlog: deque[_Task] = deque()  # tasks popped but not admitted
        self._staged_flat: dict[str, Any] | None = None  # streamed-update staging
        self._stage_target = "device"  # per-update: "device" | "host"
        self.last_update_gen_tokens = 0  # tokens emitted during last update
        self.initialized = False
        self.stats = {
            "generated_tokens": 0,
            "completed": 0,
            "aborted": 0,
            "chunks": 0,
            "kv_resumes": 0,
            "prefills": 0,
            "prefill_batches": 0,
            "prefill_tokens": 0,
            "prefix_cache_hits": 0,
            "prefix_cache_misses": 0,
            "prefix_hit_tokens": 0,
            "deadline_exceeded": 0,
            "cancelled": 0,
            "watchdog_fired": 0,
            # speculative decoding (docs/serving.md): per-round draft/accept
            # accounting; acceptance rate = accepted / drafted
            "spec_rounds": 0,
            "spec_draft_tokens": 0,
            "spec_accepted_tokens": 0,
            "spec_rollback_pages": 0,
        }
        # registry counters mirror the hot stats (thread-sharded: the
        # decode thread increments contention-free; scrapes sum shards)
        self._obs = obs_catalog.engine_metrics()
        self._obs_pc = obs_catalog.prefix_cache_metrics()
        self._obs_lc = obs_catalog.lifecycle_metrics()
        self._obs_spec = obs_catalog.speculative_metrics()
        # speculative decoding: non-None only while enabled (the loop's
        # per-pass mode switch); the drafter is built in initialize() /
        # set_speculative() so it can see the prefix tree
        self._spec_cfg = None
        self._drafter = None
        self._radix_flush_req: tuple[threading.Event, list[int]] | None = None
        # request lifecycle (docs/request_lifecycle.md): rids queued for
        # cancellation by any thread (/abort_request, generate_sync
        # timeouts); the decode loop services them between chunks
        self._abort_lock = threading.Lock()
        self._abort_rids: set[str] = set()
        # decode-loop liveness: last time the loop completed a pass (the
        # wedge detector /health consults) — monotonic seconds
        self._last_loop_ts = time.monotonic()
        # request timeline observatory + flight recorder
        # (observability/timeline.py): per-request stage attribution and
        # the significant-event ring /debug/flight serves
        self.timeline = tl_mod.TimelineRecorder()
        self.flight = tl_mod.get_flight_recorder()
        self._hold_marked = False  # one FENCE_STALL mark per hold window
        self._wedge_dumped = False  # one flight dump per wedge escalation
        # preemption drain (docs/fault_tolerance.md): set = admission
        # closed, replica finishing-or-parking toward process exit
        self._draining = threading.Event()
        self._drain_terminal = False  # True = drain of an exiting process
        self._drain_summary: dict | None = None
        self._obs_preempt = obs_catalog.preemption_metrics()
        # goodput-autopilot setpoints applied to this replica via POST
        # /autopilot/knobs (docs/autopilot.md): what /statusz reports back
        # so the control plane can see its pushes took effect
        self._autopilot_lock = threading.Lock()
        self._autopilot_knobs: dict[str, float] = {}
        self._autopilot_applied_at: float | None = None
        # kernel observatory (observability/kernel_probe.py): per-pass phase
        # timeline. Built in initialize(); None until then, and _ktl holds
        # the current pass's open timeline on the decode thread
        self.kprobe: kernel_probe.KernelProbe | None = None
        self._ktl: kernel_probe.DecodeStepTimeline | None = None
        # the commit point of the next chunk's batch (inference/commit_point.py):
        # the chunk-time estimate, and the clock and the interruptible wait it
        # is kept on (tests put a simulated clock and wait in their place)
        self._pacer = commit_point.ChunkPacer()
        self._pace_clock: Callable[[], float] = time.monotonic
        self._pace_wait: Callable[[float], bool] = self._wakeup.wait
        self._pull_s = 0.0  # seconds of the current pass inside blocking pulls
        # the row ledger (_drain): what the rows the chunks stepped for were
        # doing, summed since the engine was built (/statusz ``row_steps``; a
        # pass's span carries what the pass added)
        self._row_steps = dict.fromkeys(LEDGER_KEYS, 0)
        self._pass_start = 0.0  # time.monotonic() at the top of the pass
        # one WARNING line for a productive pass over 3 x the median of the
        # last 64, with what the span record holds of it
        self._pass_watch = perf_tracer.SlowSpanWatch("areal.decode.pass")
        self._pass_productive = False  # did the current pass drain, dispatch or admit

    # -- lifecycle --------------------------------------------------------
    def initialize(self) -> None:
        with perf_tracer.trace_scope(
            "areal.setup.engine_init", args={"engine": "decode"}
        ) as span:
            self._initialize()
            state_bytes = SlotCache.state_bytes(self.cache)
            span.set(
                param_bytes=hw.tree_bytes(self.params),
                recurrent_state_bytes=state_bytes,
                kv_page_bytes=hw.tree_bytes(self.cache) - state_bytes,
            )

    def _initialize(self) -> None:
        cfg = self.config
        # serving-side compile visibility: a recompile storm (drifting
        # chunk/scatter shape keys) shows as areal_xla_compiles_total climb
        compile_cache.install_compile_counters()
        # before the first compile: the persistent compile cache, and the
        # program store beside it (TPU-only gating and the placement rule
        # live in utils/compile_cache.py)
        compile_cache.enable_persistent_cache()
        if self.mesh is None:
            self.mesh = mesh_lib.make_mesh(cfg.mesh)
        if self.params is None:
            assert cfg.model_path, "ServerConfig.model_path required"
            self.model_cfg = models.config_from_hf_path(cfg.model_path)
            self.model = models.family_of(self.model_cfg)
            self.model_cfg = self.model.serving_config(self.model_cfg, cfg.dtype)
            self._check_model_limits()
            self.param_shardings = mesh_lib.param_sharding(
                self.mesh, self.model.param_partition_specs(self.model_cfg)
            )

            self.params, _ = load_params_from_hf(
                cfg.model_path, self.model_cfg, put=self._place
            )
            if self.model_cfg.vision is not None and "vision" not in self.params:
                # checkpoint shipped no visual.* weights (models/hf.py loads
                # them when present); serve a from-scratch tower rather than
                # KeyError on the first image
                logger.warning(
                    "VLM serving: checkpoint has no visual.* weights; vision "
                    "tower initializes from scratch"
                )
                from areal_tpu.models.vision import (
                    init_vision_params,
                    vision_partition_specs,
                )

                vshard = mesh_lib.param_sharding(
                    self.mesh, vision_partition_specs()
                )
                with set_mesh(self.mesh):
                    self.params["vision"] = jax.jit(
                        lambda k: init_vision_params(
                            k, self.model_cfg.vision, dtype=self.model_cfg.jax_dtype
                        ),
                        out_shardings=vshard,
                    )(jax.random.PRNGKey(0))
        else:
            assert self.model_cfg is not None
            self.model = models.family_of(self.model_cfg)
            self._check_model_limits()
            self.param_shardings = mesh_lib.param_sharding(
                self.mesh, self.model.param_partition_specs(self.model_cfg)
            )
            # caller-provided params (colocated trainers, tests) arrive with
            # whatever placement the caller had — often replicated or
            # single-device. Reshard toward the serving specs; without this
            # a TP mesh serves fully-replicated weights (no memory saving,
            # and the quantized leaves inherit the replication)
            from areal_tpu.inference.server import _unflatten

            with set_mesh(self.mesh):
                self.params = _unflatten(
                    {p: self._place(p, a) for p, a in _iter_tree_paths(self.params)}
                )

        # the UNQUANTIZED param structure: weight updates arrive as bf16
        # trees with base names regardless of serving quantization, so
        # completeness checks and shard lookups use this, not self.params
        self._base_param_paths = {p for p, _ in _iter_tree_paths(self.params)}
        if cfg.quantization == "int8":
            self.params = self._quantize(self.params)
            # shardings for the SERVED (quantized) structure — offload/onload
            # walks self.params paths, which carry _q8/_scale names
            self._serving_shardings = mesh_lib.param_sharding(
                self.mesh, self.model.quant_partition_specs(self.model_cfg)
            )
        elif cfg.quantization not in (None, "", "none"):
            raise ValueError(f"unknown quantization {cfg.quantization!r}")
        else:
            self._serving_shardings = self.param_shardings

        S, T = cfg.max_batch_size, cfg.max_seq_len
        self.programs = DecodePrograms(self.model, self.model_cfg, cfg, self.mesh)
        self._init_paged_cache()
        # host mirror of per-slot state. The authoritative decode state lives
        # ON DEVICE (self._dev_state): the loop never round-trips it through
        # the host — one packed upload per admission event, one packed
        # download per chunk. (Round-1 uploaded 9 arrays and downloaded 7
        # per chunk; over a high-latency host<->TPU link each transfer is an
        # RPC, and that overhead tripled per-token cost.)
        self._slot_task: list[_Task | None] = [None] * S
        # last time each slot made progress (admission or token emission);
        # the per-slot watchdog compares against lifecycle.watchdog_s
        self._slot_progress: list[float] = [0.0] * S
        self._state = slot_state(S, self.programs.block)
        # per-slot generated-token counts (OpenAI frequency_penalty
        # semantics) live DEVICE-ONLY — the host never reads them back, so
        # no [S, V] host mirror. uint16 with saturating updates. Config-
        # gated so default fleets pay neither the memory nor new variants.
        self._freq_enabled = bool(cfg.enable_frequency_penalty) and "frequency_penalty" not in self._model_limits()
        self._pending_count_restore: list[tuple[int, np.ndarray]] = []
        # COMMITTED to the mesh (replicated) from the start: every jitted
        # serving fn hands its state/rng outputs back committed, and an
        # uncommitted first generation would make the first call (and every
        # program precompile() lowers from these arrays) a different cache
        # key from all later calls — each program compiled twice
        repl = NamedSharding(self.mesh, P())
        self._dev_state = {
            k: jax.device_put(v, repl) for k, v in self._state.items()
        }
        if self._freq_enabled:
            self._dev_state["freq_counts"] = jax.device_put(
                jnp.zeros((S, self.model_cfg.vocab_size), jnp.uint16), repl
            )
        seed = self.config.seed
        if seed is None:
            seed = int(time.time_ns()) % (2**31)
        self._rng = jax.device_put(jax.random.PRNGKey(seed), repl)
        self.kprobe = kernel_probe.KernelProbe()
        spec = cfg.speculative
        if spec.enabled:
            from areal_tpu.inference import speculative as spec_mod

            self._spec_cfg = spec
            self._drafter = spec_mod.build_drafter(spec, radix=self.slots.radix)
        self.initialized = True
        logger.info(
            f"decode engine ready: {S} slots × {T} ctx, "
            f"{self.slots.n_pages} KV pages × {cfg.page_size} tokens, "
            f"mesh {dict(self.mesh.shape)}, attention {self.attention_impl()}"
        )

    def _model_limits(self) -> dict[str, str]:
        """{feature: why} of what the model's module does not implement for
        this model (``models/*.serving_limits``; none before the engine is
        initialized), beside ``reason``, the word ``/statusz`` gives for it."""
        return self.model.serving_limits(self.model_cfg) if self.model is not None else {}

    def _check_model_limits(self) -> None:
        """What the model's module does not implement, refused when the
        engine is configured and not at the first request that would need
        it. The list is the module's, by feature (a recurrent state cannot be
        cut back to a token boundary, so nothing may roll it back or hand out
        a prefix of it: inference/paged_kv.py STATE_LEAVES; latent pages have
        no suffix kernel): nothing here asks what kind of model it is."""
        limits = self._model_limits()
        cfg = self.config
        if cfg.speculative.enabled and "speculative" in limits:
            raise ValueError(limits["speculative"])
        if cfg.quantization == "int8" and "int8_weights" in limits:
            raise ValueError(limits["int8_weights"])
        if cfg.kv_quantization in ("int8", "fp8") and "int8_pages" in limits:
            raise ValueError(limits["int8_pages"])
        if int(np.prod(list(self.mesh.shape.values()))) != 1 and "sharded" in limits:
            raise ValueError(limits["sharded"])

    def _place(self, path: str, arr) -> jax.Array:
        """THE placement policy for incoming weights. Base-named leaves cast
        to the serving dtype toward the base param shardings; served-form
        quantized leaves (``*_q8``/``*_scale`` from a q8-wire update against
        an int8 engine) keep their own dtype and take the quantized specs.
        Used by HF load, caller-provided-params reshard, staged-bucket
        ingest, and disk updates — keep them identical."""
        name = path.rsplit("/", 1)[-1]
        if name in _SERVED_FORM_LEAVES:
            # served-form leaf from a q8-wire update
            if self.config.quantization != "int8":
                raise RuntimeError(
                    "q8-wire weight update against a non-quantized engine; "
                    "set ServerConfig.quantization='int8' or use "
                    "wire_format='bf16'"
                )
            if not hasattr(self, "_serving_shardings"):
                raise RuntimeError("q8-wire leaf before engine initialize()")
            return jax.device_put(
                jnp.asarray(arr),
                mesh_lib.shard_for_path(self._serving_shardings, path),
            )
        return jax.device_put(
            jnp.asarray(arr, dtype=self.model_cfg.jax_dtype),
            mesh_lib.shard_for_path(self.param_shardings, path),
        )

    def _quantize(self, params: dict) -> dict:
        """int8 weight-only transform of a served tree (jitted; sharding
        propagates from the inputs — q8 is elementwise in W, so GSPMD keeps
        the base weight's placement). The caller's bf16 tree is NOT donated:
        colocated callers may still hold references into it. The jitted fn
        is built once — a per-call jax.jit would retrace inside every
        weight-update pause window."""
        fn = getattr(self, "_quantize_jit", None)
        if fn is None:
            fn = self._quantize_jit = jax.jit(self.model.quantize_params_int8)
        with set_mesh(self.mesh):
            return fn(params)

    def _init_paged_cache(self) -> None:
        """Create the paged KV cache: the host's ledger of it
        (inference/slot_cache.py: allocator, block tables, prefix tree; it
        sizes the pool) and the page arrays on device (inference/paged_kv.py),
        which stay HERE: the loop threads them, with the slot state and the
        rng, through the donated programs."""
        from areal_tpu.inference import paged_kv

        cfg = self.config
        mcfg = self.model_cfg
        S, psz = cfg.max_batch_size, cfg.page_size
        self.slots = SlotCache(
            cfg, mcfg, no_prefix=self._model_limits().get("prefix_cache"), record=self.flight.record
        )
        kv_quant = self.slots.kv_quant
        tp = self.mesh.shape["model"]
        # the pools this model has (a latent model: no V pool)
        kv_spec = paged_kv.paged_cache_specs(quant=kv_quant, pools=tuple(mcfg.kv_pools))
        if mcfg.num_kv_heads % max(tp, 1):
            kv_spec = dict.fromkeys(kv_spec, P())
        kv_spec.update({name: P() for name in (*mcfg.state_shapes(S), *mcfg.ring_shapes(S, psz))})
        with set_mesh(self.mesh):
            self.cache = jax.jit(
                lambda: paged_kv.init_paged_cache(
                    mcfg, self.slots.n_pages, psz, quant=kv_quant, slots=S
                ),
                out_shardings={
                    k: NamedSharding(self.mesh, s) for k, s in kv_spec.items()
                },
            )()
        self._obs.state_bytes.set(SlotCache.state_bytes(self.cache))
        load_shape = mcfg.moe_count_shapes.get("moe_load")
        self._moe_load = np.zeros(load_shape, np.int64) if load_shape else None
        self._attn_blocks = (0, 0)  # (listed, fetched) by the decode steps' attention launches: decode_attention_status
        self._block_passes = (0, 0)  # (denoise, commit) slot-passes of a block model's drained chunks

    def precompile(
        self,
        prompt_buckets: list[int] | None = None,
        budget_s: float | None = None,
    ) -> None:
        """AOT compile-warm every jitted variant the serving loop can reach
        (``DecodePrograms.warm_keys``: decode chunks, slot scatters, page
        copies, prefill programs; ``prompt_buckets`` narrows the last).

        A compile stall mid-serving blocks ALL slots for tens of seconds;
        round-2 profiling showed cold prefill variants alone cost ~25% of
        measured decode throughput on the first request waves. Servers call
        this at startup (``ServerConfig.precompile``) — the role SGLang's
        warmup phase plays for the reference's launchers. Warming builds
        each program from abstract arguments through the function its first
        call would go through (``compile_cache.FirstCall``: the program
        store's executable loaded, or the program traced, lowered, compiled
        and written there) — no device execution (ADVICE r02 #1/#2). The
        runtime path then calls the loaded executable: it traces nothing.

        ``budget_s`` bounds wall-clock: compilation stops (with a log of the
        skipped count) once the budget is spent. Programs are ordered hot
        loop first, so an out-of-budget stop costs admission-wave stalls,
        never mid-decode stalls. Fresh compiles land in the persistent
        cache and the program store, so a budget-truncated run completes
        further on the next start.
        """
        assert self.initialized, "initialize() first"
        t0 = time.monotonic()

        def sds(x):
            # WITH the live array's sharding: the runtime call passes
            # committed arrays, and a program built from unplaced shapes is
            # another program (and another entry of the store)
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)

        shapes = jax.tree.map(sds, (self.params, self.cache, self._dev_state, self._rng))
        # which programs, in which order, and how each is lowered: the
        # programs' own (decode_programs.py warm_keys / lower)
        keys = self.programs.warm_keys(prompt_buckets)
        n_prog = 0
        with set_mesh(self.mesh), perf_tracer.trace_scope(
            "areal.setup.precompile", args={"programs": len(keys)}
        ):
            for key in keys:
                if budget_s is not None and time.monotonic() - t0 > budget_s:
                    logger.warning(
                        f"precompile budget {budget_s:.0f}s spent after "
                        f"{n_prog} programs; {len(keys) - n_prog} deferred "
                        "to lazy compile"
                    )
                    break
                self.programs.build(key, *shapes)
                n_prog += 1
        logger.info(
            f"precompiled {n_prog}/{len(keys)} serving programs in "
            f"{time.monotonic() - t0:.1f}s; {compile_cache.store_summary()}"
        )

    def start(self) -> None:
        assert self._thread is None
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._shutdown.set()
        self._wakeup.set()
        if self._thread:
            self._thread.join(timeout=30)
            self._thread = None

    # -- request API (any thread) ----------------------------------------
    def submit(self, req: ModelRequest, callback: Callable[[ModelResponse], None]):
        # timeline starts at submission; the x-areal-trace ids are whatever
        # the calling context carries (the HTTP server seats them before
        # submitting), so cross-process postmortems correlate on them
        task_id, session_id = perf_tracer.get_task_context()
        tl = self.timeline.start(
            req.rid,
            priority=str(req.metadata.get("priority") or "interactive"),
            task_id=task_id,
            session_id=session_id,
        )
        self._queue.put(_Task(req=req, callback=callback, timeline=tl))
        self._wakeup.set()

    def generate_sync(self, req: ModelRequest, timeout: float = 600.0) -> ModelResponse:
        done = threading.Event()
        box: list[ModelResponse] = []

        def cb(resp):
            box.append(resp)
            done.set()

        self.submit(req, cb)
        if not done.wait(timeout):
            # cancel the engine-side work before giving up: without this
            # the engine decodes to completion (and holds KV pages) for a
            # caller that is gone — the wasted-work bug the lifecycle
            # manager exists to close
            # the abort resolves at the next decode-loop pass; give the
            # callback a short grace so the slot/pages are reclaimed (and
            # the partial response, if any, is not lost to a near-miss).
            # No grace for rid-less requests: nothing was queued for them.
            if self.abort_request(req.rid) and done.wait(5.0):
                return box[0]
            raise TimeoutError(f"generation timed out after {timeout}s")
        return box[0]

    def abort_request(self, rid: str) -> bool:
        """Cancel one request by rid, wherever it is — queued, decoding, or
        parked. Thread-safe: the rid is queued and the decode loop reaps it
        between chunks (slot deactivated, KV pages freed or published,
        callback fired with stop_reason="cancelled"). Returns True if the
        rid was queued for cancellation (False for an empty rid)."""
        if not rid:
            return False
        with self._abort_lock:
            self._abort_rids.add(rid)
        self._wakeup.set()
        return True

    # -- lifecycle (deadlines / cancellation / watchdog) -------------------
    def _lifecycle(self):
        lc = getattr(self.config, "lifecycle", None)
        return lc if (lc is not None and lc.enabled) else None

    def admission_snapshot(self) -> dict:
        """Point-in-time admission-control inputs (the 429 payload and the
        /statusz lifecycle section): queue depth, free-page headroom, and
        slot occupancy. Reads are racy-but-monotone (queue/backlog sizes),
        which is fine for a gate that only needs to be approximately
        right."""
        return {
            "queue_depth": self._queue.qsize() + len(self._backlog),
            "free_pages": self.slots.free_pages,
            "radix_pages": self.slots.radix_pages,
            # pool size so remote consumers (the routing snapshot poller)
            # can turn free_pages into a headroom fraction
            "n_pages": self.slots.n_pages,
            "active_slots": sum(
                1 for t in getattr(self, "_slot_task", ()) if t is not None
            ),
            "max_batch_size": self.config.max_batch_size,
        }

    def check_admission(self) -> tuple[bool, str, dict]:
        """Admission-control gate for new generation requests. Returns
        (admit, reason, snapshot); ``reason`` names the tripped gate
        ("queue_depth" | "page_headroom" | "draining") when admit is
        False."""
        lc = self._lifecycle()
        snap = self.admission_snapshot()
        # a draining replica admits NOTHING, lifecycle config or not — the
        # process is on its way out (preemption grace window); clients see
        # 429 + Retry-After and fail over to a sibling
        if self._draining.is_set():
            return False, "draining", snap
        if lc is None:
            return True, "", snap
        if lc.max_queue_depth > 0 and snap["queue_depth"] >= lc.max_queue_depth:
            return False, "queue_depth", snap
        if (
            lc.min_free_pages > 0
            and snap["free_pages"] + snap["radix_pages"] < lc.min_free_pages
        ):
            # radix pages count as headroom: they are reclaimable cache,
            # first rung of the eviction ladder
            return False, "page_headroom", snap
        return True, "", snap

    def apply_autopilot_knobs(self, knobs: dict) -> dict:
        """Apply control-plane setpoints (docs/autopilot.md): admission
        gates (``max_queue_depth``, ``min_free_pages`` — plain int stores
        the admission gate reads racily-but-atomically) and the radix
        cache's ``radix_max_fraction`` (recomputed into a page cap; a live
        decode loop evicts LRU leaves down to a shrunk cap between chunks,
        a stopped engine converges inline). Unknown keys are ignored so an
        older server survives a newer control plane. Returns the applied
        status (same shape as the /statusz ``autopilot`` section)."""
        applied: dict[str, float] = {}
        lc = getattr(self.config, "lifecycle", None)
        if lc is not None:
            for k in ("max_queue_depth", "min_free_pages"):
                if knobs.get(k) is not None:
                    setattr(lc, k, max(0, int(knobs[k])))
                    applied[k] = float(getattr(lc, k))
        frac = knobs.get("radix_max_fraction")
        if frac is not None and self.slots.radix is not None:
            frac = max(0.0, min(1.0, float(frac)))
            self.slots.set_prefix_fraction(frac)
            applied["radix_max_fraction"] = frac
            if self._thread is not None and self._thread.is_alive():
                # the tree is decode-loop-private while the loop runs: it
                # converges to the new cap between chunks
                self._wakeup.set()
            else:
                self.slots.shrink_prefix_to_cap()
        if applied:
            with self._autopilot_lock:
                self._autopilot_knobs.update(applied)
                self._autopilot_applied_at = time.time()
        return self.autopilot_status()

    def autopilot_status(self) -> dict:
        """The /statusz ``autopilot`` section: setpoints this replica is
        actually running (empty until the control plane pushes one)."""
        with self._autopilot_lock:
            return {
                "knobs": dict(self._autopilot_knobs),
                "applied_at": self._autopilot_applied_at,
            }

    def is_wedged(self) -> bool:
        """True when the decode loop has made no pass for
        ``lifecycle.engine_stall_escalate_s`` while work is pending — the
        per-slot watchdog cannot run then (it lives on the same loop), so
        /health turns 503 and PR 3's probe/supervision path evicts and
        respawns the replica."""
        lc = self._lifecycle()
        if lc is None or lc.engine_stall_escalate_s <= 0:
            return False
        if self._thread is None:  # never started / cleanly stopped
            return False
        busy = any(t is not None for t in getattr(self, "_slot_task", ())) or (
            self._queue.qsize() + len(self._backlog) > 0
        )
        if not self._thread.is_alive():
            # the loop CRASHED (stop() nulls _thread after joining): pending
            # work can never drain, so escalate immediately — the heartbeat
            # below would never go stale-r, and waiting helps nobody
            wedged = busy
        elif self.is_paused:  # held/paused loops idle legitimately
            return False
        else:
            wedged = busy and (
                time.monotonic() - self._last_loop_ts
                > lc.engine_stall_escalate_s
            )
        if not wedged:
            # a transient stall (slow cold compile) that recovered must not
            # consume the once-only dump: re-arm so a LATER real wedge
            # still leaves its postmortem artifact (one dump per episode)
            self._wedge_dumped = False
        elif not self._wedge_dumped:
            # flight ring to disk NOW — supervision is about to evict and
            # respawn this replica, and the postmortem needs the last
            # events even if the process never answers another scrape
            self._wedge_dumped = True
            self.flight.record("wedge", severity="error")
            try:
                self.flight.dump(tl_mod.default_dump_path("wedge"), "wedge")
            except OSError:
                logger.exception("wedge flight dump failed")
        return wedged

    def _reap_lifecycle(self, pending: dict | None) -> dict | None:
        """Service cancellations, deadline expirations, and the per-slot
        watchdog — runs between decode chunks on the decode loop (the only
        thread that owns slots/pages). Reaped requests leave through
        ``_finish`` with a non-abort reason, so their pages are freed or
        published into the radix tree exactly like a completion.

        Takes/returns the loop's in-flight chunk record: when anything is
        actually reaped the chunk is drained FIRST, so tokens it emitted
        are credited (per-token version tags intact) instead of lost with
        the slot teardown. The no-reap fast path touches nothing."""
        lc = self._lifecycle()
        with self._abort_lock:
            aborts = self._abort_rids
            self._abort_rids = set()
        now = time.time()
        if lc is None and not aborts:
            return pending

        def expired(task: _Task) -> bool:
            dl = task.req.deadline
            return lc is not None and dl is not None and now > dl

        def watchdog_hit(slot: int) -> bool:
            return (
                lc is not None
                and lc.watchdog_s > 0
                and self._state["active"][slot]
                and self._slot_progress[slot] > 0
                and time.monotonic() - self._slot_progress[slot] > lc.watchdog_s
            )

        # fast path: nothing queued/decoding is affected — don't disturb
        # the chunk pipeline
        any_hit = bool(aborts) or any(
            expired(t) for t in self._backlog
        )
        if not any_hit:
            for slot, task in enumerate(self._slot_task):
                if task is not None and (expired(task) or watchdog_hit(slot)):
                    any_hit = True
                    break
        if not any_hit:
            # queued-task deadlines are enforced at admission time
            # (_admit_pending) before any prefill happens
            return pending
        # credit the in-flight chunk before any slot teardown
        self._drain(pending)
        pending = None
        # queued work first: the submission queue joins the backlog (same
        # FIFO order _admit_pending uses) and both are filtered
        self._queue_to_backlog()
        kept: deque[_Task] = deque()
        counted: set[str] = set()  # rids whose cancel _finish already counted
        for task in self._backlog:
            if task.req.rid and task.req.rid in aborts:
                task.truncated_by = "cancelled"
                counted.add(task.req.rid)
                self._finish(task, StopReason.CANCEL.value)
            elif expired(task):
                task.truncated_by = "deadline"
                self._finish(task, StopReason.DEADLINE.value)
            else:
                kept.append(task)
        # arealint: disable-next=THR001 single-writer by design: the backlog is owned by the decode loop thread (this method runs between chunks on it); other threads only read its len() for racy-but-monotone depth snapshots
        self._backlog = kept
        # active slots: deadline, cancellation, watchdog
        st = self._state
        rows: list[np.ndarray] = []
        for slot, task in enumerate(self._slot_task):
            if task is None:
                continue
            reason = None
            if task.req.rid and task.req.rid in aborts:
                task.truncated_by = "cancelled"
                counted.add(task.req.rid)
                reason = StopReason.CANCEL.value
            elif expired(task):
                task.truncated_by = "deadline"
                reason = StopReason.DEADLINE.value
            elif watchdog_hit(slot):
                task.truncated_by = "watchdog"
                reason = StopReason.CANCEL.value
                self.stats["watchdog_fired"] += 1
                self._obs_lc.watchdog_fired.inc()
                self.flight.record(
                    "watchdog",
                    severity="error",
                    slot=slot,
                    rid=task.req.rid,
                )
                logger.warning(
                    f"slot {slot} watchdog: no token in {lc.watchdog_s:.1f}s "
                    f"(rid={task.req.rid}); aborting the slot"
                )
            if reason is None:
                continue
            if st["active"][slot]:
                rows.append(self._deact_row(slot))
            self._finish(task, reason)
        if rows and self.cache is not None:
            self._apply_slot_updates(rows)
        # parked rids: cancellation drops the parking and frees its pages
        # (deadlines leave parked KV alone — the rid owner may still resume
        # with time left on a fresh attempt; eviction pressure bounds it)
        for rid in aborts:
            # a parked rid whose resume was reaped above already counted
            # through _finish — one cancelled request, one increment
            if self.slots.drop_parked(rid) and rid not in counted:
                self.stats["cancelled"] += 1
                self._obs_lc.aborts.inc()
        return None  # in-flight chunk was drained above

    # -- pause / weights (the §3.4 protocol) ------------------------------
    def pause_generation(self, mode: str = "abort") -> None:
        """Stop the decode loop until ``continue_generation``.

        mode "abort" (legacy §3.4): all in-flight requests complete with
        stop_reason "abort" and the client's interruptible loop resumes
        them after the pause. mode "hold" (zero-pause commit fence): the
        loop finishes its in-flight chunk and idles WITHOUT aborting —
        slots, KV, and device state stay intact, and decoding resumes
        exactly where it stopped. Holds are meant to last one weight-commit
        roundtrip; per-token version tags make the resulting mixed-version
        sequences safe for decoupled PPO."""
        if mode == "hold":
            self._hold_since = time.monotonic()
            self._held.set()
        elif mode == "abort":
            self._paused.set()
        else:
            raise ValueError(f"unknown pause mode {mode!r}")
        self._wakeup.set()

    def wait_fence_ack(self, timeout: float = 10.0) -> bool:
        """Block until the decode loop has actually reached the hold fence
        (in-flight chunk drained) — what /pause_generation mode=hold acks
        to the client. True immediately when the loop is not running."""
        if self._thread is None:
            return True
        return self._hold_ack.wait(timeout)

    def continue_generation(self) -> None:
        self._paused.clear()
        self._held.clear()
        self._pause_ack.clear()
        self._hold_ack.clear()
        self._wakeup.set()

    @property
    def is_paused(self) -> bool:
        return self._paused.is_set() or self._held.is_set()

    @property
    def is_abort_paused(self) -> bool:
        """True only for the legacy ABORT pause (slots emptied) — what
        release_memory requires; a hold fence keeps slots live and does
        NOT qualify."""
        return self._paused.is_set()

    # -- preemption drain (docs/fault_tolerance.md) ------------------------
    @property
    def is_draining(self) -> bool:
        return self._draining.is_set()

    def begin_drain(self, terminal: bool = False) -> None:
        """Close admission (check_admission rejects with reason
        "draining") while in-flight decodes keep running — the first half
        of the finish-or-park drain. ``terminal`` marks a drain whose
        process is EXITING (SIGTERM preemption): it can never be
        cancelled. Idempotent; terminal is sticky across overlapping
        drains."""
        if terminal:
            self._drain_terminal = True
        if not self._draining.is_set():
            self._draining.set()
            self.flight.record(
                "drain_begin", severity="warn", terminal=bool(terminal)
            )
        self._wakeup.set()

    def end_drain(self) -> bool:
        """Re-open admission (ops escape hatch / autopilot scale-up).
        REFUSED for a terminal drain: the process is on its way out (the
        platform will SIGKILL it) and re-opened admission would accept
        requests that die responseless — the autoscaler must pick a
        different replica. Returns True when admission re-opened."""
        if getattr(self, "_drain_terminal", False):
            logger.warning(
                "end_drain refused: this drain is terminal (preemption "
                "grace window) — the process is exiting"
            )
            return False
        self._draining.clear()
        return True

    def _queue_to_backlog(self) -> None:
        while True:
            try:
                self._backlog.append(self._queue.get_nowait())
            except queue.Empty:
                return

    def _abort_queued(self) -> None:
        """Finish every queued/backlogged task with stop_reason=abort —
        decode-loop-thread only (backlog ownership). A draining replica
        must leave no request without a terminal: the callback's partial
        response is what lets the client resubmit elsewhere."""
        self._queue_to_backlog()
        while self._backlog:
            task = self._backlog.popleft()
            self._finish(task, StopReason.ABORT.value)

    def drain(self, budget_s: float = 10.0, terminal: bool = False) -> dict:
        """Graceful preemption drain: stop admission, let in-flight
        decodes finish inside ``budget_s``, then park (rid-affinity KV,
        partial tokens returned) or abort the survivors and the queue.
        Blocks until the engine is quiescent; returns (and stores for
        /statusz) a summary incl. the leak audit. Any thread.
        ``terminal=True`` (the SIGTERM preemption path) makes the drain
        uncancellable — see :meth:`begin_drain`."""
        t0 = time.monotonic()
        self.begin_drain(terminal=terminal)
        aborted_before = self.stats["aborted"]
        deadline = t0 + max(0.0, budget_s)
        finished_in_budget = True
        while True:
            loop_alive = self._thread is not None and self._thread.is_alive()
            busy = any(t is not None for t in self._slot_task) or (
                self._queue.qsize() + len(self._backlog) > 0
            )
            if not busy:
                break
            if not loop_alive or self.is_paused:
                # nothing will finish on its own — park/abort immediately
                finished_in_budget = False
                break
            if time.monotonic() >= deadline:
                finished_in_budget = False
                break
            time.sleep(0.02)
        # survivors: the abort pause parks rid'd in-flight requests
        # (_abort_all) and the paused loop branch clears the queue
        self.pause_generation()
        if self._thread is not None and self._thread.is_alive():
            self._pause_ack.wait(timeout=max(5.0, budget_s))
            # _pause_ack may pre-date this drain (engine already abort-
            # paused): the loop aborts the queue on its NEXT pass — wait
            # for it so the summary reflects every terminal having fired
            qdeadline = time.monotonic() + 5.0
            while (
                self._queue.qsize() + len(self._backlog) > 0
                and time.monotonic() < qdeadline
            ):
                self._wakeup.set()
                time.sleep(0.01)
        else:
            # no loop: this thread owns the state — drain inline
            self._abort_all()
            self._abort_queued()
        summary = {
            "draining": True,
            "drain_seconds": time.monotonic() - t0,
            "finished_in_budget": finished_in_budget,
            "budget_s": budget_s,
            "parked": len(self.slots.parked),
            "aborted": self.stats["aborted"] - aborted_before,
            "leaked_pages": self.slots.leaked_pages(),
            "unterminated_timelines": self.timeline.stats()["unterminated"],
        }
        self._drain_summary = summary
        self._obs_preempt.drain_seconds.observe(summary["drain_seconds"])
        self.flight.record(
            "drain_end",
            severity="warn",
            seconds=round(summary["drain_seconds"], 3),
            parked=summary["parked"],
            aborted=summary["aborted"],
            leaked_pages=summary["leaked_pages"],
        )
        logger.warning(
            f"drain complete in {summary['drain_seconds']:.2f}s "
            f"(finished_in_budget={finished_in_budget}, "
            f"parked={summary['parked']}, aborted={summary['aborted']}, "
            f"leaked_pages={summary['leaked_pages']})"
        )
        return summary

    def drain_status(self) -> dict:
        """The /statusz drain section: live flag + last drain summary
        (``draining`` always reflects the CURRENT state — an undrained
        replica must not keep reporting its historical drain as live)."""
        out = (
            dict(self._drain_summary) if self._drain_summary is not None else {}
        )
        out["draining"] = self._draining.is_set()
        # the autoscaler (and ops) must distinguish a cancellable drain
        # from a process that is exiting — only the former can undrain
        out["terminal"] = bool(self._drain_terminal)
        return out

    def _wait_weight_update_applied(self) -> None:
        """Wait for the decode loop to apply the pending update (or apply it
        inline when the loop is not running); re-raise its failure."""
        if self._thread is None:
            self._apply_weight_update()
        else:
            while True:
                with self._weight_lock:
                    if self._pending_weight_update is None:
                        break
                time.sleep(0.01)
        self._take_update_error()

    def update_weights_from_disk(self, path: str, version: int | None = None) -> None:
        with self._weight_lock:
            self._pending_weight_update = ("disk", path, version)
        self._wakeup.set()
        self._wait_weight_update_applied()

    def update_weights_from_params(self, params: dict, version: int | None = None) -> None:
        """Colocated/mem-path update: resharded device arrays or host arrays."""
        with self._weight_lock:
            self._pending_weight_update = ("params", params, version)
        self._wakeup.set()
        self._wait_weight_update_applied()

    def update_weights_lora(
        self, flat: dict[str, np.ndarray], scale: float, version: int | None = None
    ) -> None:
        """LoRA-delta fast path: fold adapter deltas into the served base
        weights WITHOUT streaming the full tree (reference ships the PEFT
        config to SGLang, lora docs; a 1.5B bf16 tree is ~3 GB/server while
        rank-32 adapters are ~25 MB). Cumulative-correct: the engine keeps
        the previously applied (a, b) per target and folds
        W += scale·(a_new@b_new − a_old@b_old).

        PRECONDITION: the serving params this engine STARTED with must be
        the adapter-free base checkpoint (the single-host entry injects the
        trainer's unmerged base; fleet servers load the base model path). A
        server cold-started from a MERGED export would double-fold on the
        first delta — in-process transitions are guarded (_lora_prev=None
        after any full update), but the engine cannot detect a merged
        checkpoint at load time."""
        with self._weight_lock:
            self._pending_weight_update = ("lora", (flat, float(scale)), version)
        self._wakeup.set()
        self._wait_weight_update_applied()

    def _apply_lora_delta(self, flat: dict, scale: float) -> None:
        prev = getattr(self, "_lora_prev", {})
        if prev is None:
            # a full weight update replaced the base since the last delta;
            # the fold base is unknown (the full tree may already contain
            # merged adapters) — folding now would double-apply silently
            raise RuntimeError(
                "lora_only update after a full weight update: the serving "
                "base is no longer the adapter-free checkpoint; push full "
                "updates (lora_only=False) or restart servers from the base"
            )
        layers = dict(self.params["layers"])
        targets = sorted(
            {k.split("/")[-1].rsplit("_lora_", 1)[0] for k in flat}
        )
        # validate BEFORE any fold: the fold donates live weight buffers, so
        # a mid-loop KeyError/shape error would strand self.params on
        # deleted arrays and brick the server
        for t in targets:
            for s in ("a", "b"):
                if f"layers/{t}_lora_{s}" not in flat:
                    raise ValueError(f"lora bucket missing layers/{t}_lora_{s}")
            if t not in layers:
                raise ValueError(f"unknown lora target {t!r}")
            a_s = flat[f"layers/{t}_lora_a"].shape
            b_s = flat[f"layers/{t}_lora_b"].shape
            w_s = tuple(layers[t].shape)
            if (
                len(a_s) != 3
                or len(b_s) != 3
                or (a_s[0], a_s[1], b_s[2]) != w_s
                or a_s[2] != b_s[1]
            ):
                raise ValueError(
                    f"lora shapes {a_s}x{b_s} do not fold into {t} {w_s}"
                )
        if not hasattr(self, "_lora_fold_fn"):

            def fold(w, a, b, pa, pb, s):
                delta = jnp.einsum("nir,nro->nio", a, b) - jnp.einsum(
                    "nir,nro->nio", pa, pb
                )
                return (w.astype(jnp.float32) + s * delta).astype(w.dtype)

            self._lora_fold_fn = jax.jit(fold, donate_argnums=(0,))
        new_prev = {}
        with set_mesh(self.mesh):
            for t in targets:
                a = jnp.asarray(flat[f"layers/{t}_lora_a"], jnp.float32)
                b = jnp.asarray(flat[f"layers/{t}_lora_b"], jnp.float32)
                pa, pb = prev.get(t, (jnp.zeros_like(a), jnp.zeros_like(b)))
                layers[t] = self._lora_fold_fn(
                    layers[t], a, b, pa, pb, jnp.float32(scale)
                )
                new_prev[t] = (a, b)
        # merge, don't replace: a bucket covering a subset of targets must
        # not drop the fold state of absent targets (a later delta for them
        # would then double-apply)
        self._lora_prev = {**prev, **new_prev}
        self.params = {**self.params, "layers": layers}

    # -- streamed (bucketed) weight update --------------------------------
    # The round-1 mem path serialized the whole model as one fp32 npz inside
    # the pause window (VERDICT "What's weak" #4). The streamed protocol
    # uploads bf16 buckets that are device_put as they arrive — transport of
    # bucket i+1 overlaps the host->device transfer of bucket i — and the
    # commit is a pointer swap between decode chunks. Reference behavior:
    # fsdp_engine.py:998-1137 bucketed NCCL broadcast.
    def begin_staged_update(self, stage_target: str | None = None) -> None:
        """Open a staging area for streamed buckets. Generation KEEPS RUNNING
        while buckets stage — the availability cost of an update is only the
        commit swap. ``stage_target`` overrides
        ``ServerConfig.weight_stage_target`` for this update: "device" puts
        buckets on device as they arrive (2x weight HBM until commit, pointer
        -swap commit), "host" keeps them in host RAM (one batched H2D inside
        the commit window instead)."""
        target = stage_target or getattr(
            self.config, "weight_stage_target", "device"
        )
        if target not in ("device", "host"):
            raise ValueError(f"unknown weight_stage_target {target!r}")
        with self._weight_lock:
            self._staged_flat: dict[str, Any] = {}
            self._stage_target = target
            # tokens emitted between begin and commit-applied = the work the
            # fleet did NOT lose to this update (zero-pause visibility)
            self._stage_gen_snapshot = self.stats["generated_tokens"]
        self.flight.record("weight_stage", target=target)

    def stage_weight_bucket(self, flat: dict[str, np.ndarray]) -> None:
        """Stage one bucket WITHOUT touching served params: device target
        device_puts each tensor toward its serving sharding immediately
        (async dispatch, overlapping the next bucket's transport); host
        target keeps the host arrays and defers the H2D to commit."""
        with self._weight_lock:
            assert self._staged_flat is not None, "begin_staged_update first"
            target = self._stage_target
        if target == "host":
            staged = {name: np.asarray(arr) for name, arr in flat.items()}
        else:
            staged = {
                name: self._place(name, arr) for name, arr in flat.items()
            }
        with self._weight_lock:
            assert self._staged_flat is not None, "begin_staged_update first"
            self._staged_flat.update(staged)

    def commit_staged_weights(self, version: int | None = None) -> None:
        from areal_tpu.inference.server import _unflatten

        with self._weight_lock:
            flat = self._staged_flat
            self._staged_flat = None
        if not flat:
            if version is not None and self._version == int(version):
                # idempotent retry: the previous commit applied but its
                # response was lost on the wire (the exact fault the chaos
                # harness injects) — re-acking beats failing a succeeded
                # fleet-wide update
                logger.info(
                    f"commit v{version} retried after it already applied; "
                    "acking idempotently"
                )
                return
            raise AssertionError("no staged weights")
        tree = _unflatten(flat)
        got_paths = {p for p, _ in _iter_tree_paths(tree)}
        # served_form is decided HERE, once, and travels with the payload —
        # the apply side must not re-derive it (ADVICE r04: two detections
        # drift apart)
        served_form = any(
            p.rsplit("/", 1)[-1] in _SERVED_FORM_LEAVES for p in got_paths
        )
        # sanity: staged tree must cover the whole param structure — the
        # UNQUANTIZED one for bf16-wire updates (engine re-quantizes on
        # apply), or the SERVED (quantized) one for q8-wire updates
        if served_form:
            ref_paths = {p for p, _ in _iter_tree_paths(self.params)}
        else:
            ref_paths = self._base_param_paths
        missing = ref_paths - got_paths
        assert not missing, f"staged update missing params: {sorted(missing)[:5]}"
        with self._weight_lock:
            self._pending_weight_update = ("staged", (tree, served_form), version)
        self._wakeup.set()
        self._wait_weight_update_applied()
        # per-update availability visibility: tokens the engine generated
        # while this update was staging (begin -> commit applied)
        self.last_update_gen_tokens = self.stats["generated_tokens"] - getattr(
            self, "_stage_gen_snapshot", self.stats["generated_tokens"]
        )

    def abort_staged_update(self) -> None:
        """Drop a partially staged update without committing (e.g. a
        stream-rate probe, or a client that died mid-stream). Serving
        weights and version are untouched. Safe when nothing is staged."""
        with self._weight_lock:
            self._staged_flat = None

    def _apply_weight_update(self) -> None:
        try:
            self._apply_weight_update_inner()
        except Exception as e:  # noqa: BLE001 — a bad update payload must
            # fail THAT update (waiter re-raises, HTTP caller gets a 500),
            # not kill the decode loop or wedge the pending-update wait
            with self._weight_lock:
                self._weight_update_error = e
                self._pending_weight_update = None
            logger.error(f"weight update failed: {type(e).__name__}: {e}")

    def _take_update_error(self) -> None:
        with self._weight_lock:
            err = getattr(self, "_weight_update_error", None)
            self._weight_update_error = None
        if err is not None:
            raise err

    def _apply_weight_update_inner(self) -> None:
        with self._weight_lock:
            upd = self._pending_weight_update
            if upd is None:
                return
            kind, payload, version = upd
            t0 = time.monotonic()
            if kind != "lora":
                # any full update invalidates the delta-fold base: the new
                # tree may already contain merged adapters, so subsequent
                # lora_only pushes must be refused (see _apply_lora_delta)
                self._lora_prev = None
            quantized = self.config.quantization == "int8"
            if kind == "staged":
                # already sharded device arrays — pointer swap. bf16-wire
                # trees re-quantize in one fused device pass; q8-wire trees
                # (client pre-quantized, served_form decided once at commit
                # time) are already in served form. (A served-form tree
                # can't reach a non-quantized engine: _place rejects q8-wire
                # leaves at stage time.)
                tree, already_served = payload
                if any(
                    isinstance(v, np.ndarray)
                    for _, v in _iter_tree_paths(tree)
                ):
                    # host-staged buckets: pay the ONE batched H2D here,
                    # inside the commit window (weight_stage_target="host")
                    from areal_tpu.inference.server import _unflatten

                    tree = _unflatten(
                        {
                            p: self._place(p, a)
                            if isinstance(a, np.ndarray)
                            else a
                            for p, a in _iter_tree_paths(tree)
                        }
                    )
                self.params = (
                    self._quantize(tree)
                    if quantized and not already_served
                    else tree
                )
            elif kind == "lora":
                if quantized:
                    raise RuntimeError(
                        "lora_only updates cannot fold into int8-quantized "
                        "serving weights; push full updates or serve with "
                        "quantization='none'"
                    )
                self._apply_lora_delta(*payload)
            elif kind == "disk":
                loaded, _ = load_params_from_hf(
                    payload, self.model_cfg, put=self._place
                )
                self.params = self._quantize(loaded) if quantized else loaded
            else:
                tgt = jax.tree.map(
                    lambda x, s: jax.device_put(
                        jnp.asarray(x, dtype=self.model_cfg.jax_dtype), s
                    ),
                    payload,
                    self.param_shardings,
                )
                self.params = self._quantize(tgt) if quantized else tgt
            if version is not None:
                self._version = version
            if not self.config.kv_reuse_across_updates:
                self.slots.evict_all_parked()
            # cross-request prefix cache: KV cached under the old policy is
            # stale after this commit. The default policy flushes the tree
            # (only the tree's own refs drop — pages aliased by live slots
            # survive until those slots free them); "keep" retains it for
            # the staleness-ablation arm, audited by per-token version tags.
            if self.config.prefix_cache.across_updates == "flush":
                self.slots.flush_prefix()
            self._pending_weight_update = None
            self.flight.record(
                "weight_commit",
                update_kind=kind,
                version=self._version,
                secs=round(time.monotonic() - t0, 4),
            )
            logger.info(
                f"weights updated ({kind}) to v{self._version} in "
                f"{time.monotonic()-t0:.2f}s"
            )

    # -- offload / onload (server /release_memory_occupation) -------------
    def release_memory(self) -> None:
        """Free HBM for a colocated trainer: offload params to host, drop
        the KV slab (decode state is already aborted by pause). Reference:
        sglang /release_memory_occupation via torch_memory_saver."""
        from areal_tpu.utils.offload import offload_tree

        assert self._paused.is_set(), "pause_generation before release_memory"
        # synchronize with the decode loop: pause_generation only sets an
        # event; a chunk may still be in flight (it would resurrect the KV
        # slab by assigning its donated result back) and _abort_all may not
        # have parked yet (we'd clear the parkings too early and the loop would
        # re-add entries pointing at the dropped cache)
        if self._thread is not None and not self._pause_ack.wait(timeout=120):
            raise TimeoutError("decode loop did not acknowledge pause")
        if getattr(self, "_offload_mode", None):
            return
        t0 = time.monotonic()
        self.params, mode = offload_tree(self.params)
        self._offload_mode = mode
        self.cache = None  # pages are zeros-recreatable; parked KV is lost
        self.slots.evict_all_parked()
        logger.info(f"released memory ({mode}) in {time.monotonic()-t0:.2f}s")

    def resume_memory(self) -> None:
        from areal_tpu.utils.offload import onload_tree

        mode = getattr(self, "_offload_mode", None)
        if not mode:
            return
        t0 = time.monotonic()
        with set_mesh(self.mesh):
            if mode == "pinned_host":
                self.params = onload_tree(self.params, None, mode)
            else:
                # rebuild target shardings from the SERVED structure's spec
                # map (carries _q8/_scale names under int8 quantization)
                def shard_of(path):
                    return mesh_lib.shard_for_path(self._serving_shardings, path)

                flat = dict(_iter_tree_paths(self.params))
                shardings_flat = {p: shard_of(p) for p in flat}
                tree_shardings: dict = {}
                for p, s in shardings_flat.items():
                    d = tree_shardings
                    ks = p.split("/")
                    for k in ks[:-1]:
                        d = d.setdefault(k, {})
                    d[ks[-1]] = s
                self.params = onload_tree(self.params, tree_shardings, mode)
        self._init_paged_cache()  # fresh pool; all requests were aborted
        self._offload_mode = None
        logger.info(f"resumed memory in {time.monotonic()-t0:.2f}s")

    def set_version(self, v: int) -> None:
        self._version = v

    def get_version(self) -> int:
        return self._version

    # -- HBM ledger (docs/observability.md "Trainer observatory") ----------
    def hbm_ledger(self, override_hbm_gb: float | None = None) -> dict:
        """Itemized device-memory account of this serving replica: params,
        the paged KV pool, the radix cache's held-page share (a view INTO
        the pool — excluded from the itemized total), and any staged
        weight-update buffers. Device memory_stats where the backend has
        them; analytic byte sums on CPU. Exported on /statusz."""
        components = {
            "params": hw.tree_bytes(self.params),
            **self.slots.hbm_rows(self.cache),
            "staged_update": hw.tree_bytes(self._staged_flat),
        }
        return hw.build_hbm_ledger(
            components,
            override_hbm_gb=override_hbm_gb,
            exclude_from_total=("radix_cache",),
        )

    def _credit_counts(self, flat: np.ndarray) -> None:
        """A drained chunk's device-side counts (the leaves and the order of
        ``model_cfg.count_shapes``, flat) into the host's running sums."""
        at = 0
        counts = {}
        for name, shape in self.model_cfg.count_shapes.items():
            n = int(np.prod(shape))
            counts[name] = flat[at : at + n].reshape(shape).astype(np.int64)
            at += n
        if "moe_load" in counts:
            # arealint: disable-next=THR001 single writer (the decode loop, at a drain); /statusz reads whichever whole array the name holds: a rebind, never an in-place add
            self._moe_load = self._moe_load + counts["moe_load"]
            self._obs.moe_assignments.inc(int(counts["moe_load"].sum()))
            self._obs.moe_experts_touched.inc(int(counts["moe_touched"].sum()))
            self._obs.moe_experts_streamed.inc(int(counts["moe_streamed"].sum()))
        if "blk_denoise_passes" in counts:
            den, com = int(counts["blk_denoise_passes"].sum()), int(counts["blk_commit_passes"].sum())
            self._obs.block_denoise_passes.inc(den)
            self._obs.block_commit_passes.inc(com)
            self._obs.blocks.inc(int(counts["blk_blocks"].sum()))
            self._obs.block_attn_tokens_read.inc(int(counts["blk_attn_tokens_read"].sum()))
            # arealint: disable-next=THR001 single writer (the decode loop, at a drain); the pass's span reads the pair after it
            self._block_passes = (self._block_passes[0] + den, self._block_passes[1] + com)
        if "gdn_updates" in counts:
            self._obs.gdn_state_updates.inc(int(counts["gdn_updates"].sum()))
        if "kda_updates" in counts:
            self._obs.kda_state_updates.inc(int(counts["kda_updates"].sum()))
        if "mhc_row_sublayers" in counts:
            self._obs.mhc_row_sublayers.inc(int(counts["mhc_row_sublayers"].sum()))
        if "latent_tokens_read" in counts:
            self._obs.latent_tokens_read.inc(int(counts["latent_tokens_read"].sum()))
        for leaf, counter in (
            ("shared_kv_tokens_read", self._obs.shared_kv_tokens_read),
            ("window_tokens_read", self._obs.window_tokens_read),
            ("s6_updates", self._obs.s6_state_updates),
        ):
            if leaf in counts:
                counter.inc(int(counts[leaf].sum()))
        if "index_tokens_scored" in counts:
            self._obs.index_tokens_scored.inc(int(counts["index_tokens_scored"].sum()))
            self._obs.latent_tokens_selected.inc(int(counts["latent_tokens_selected"].sum()))
        if "attn_blocks_listed" in counts:
            listed, fetched = int(counts["attn_blocks_listed"].sum()), int(counts["attn_blocks_fetched"].sum())
            self._obs.attn_blocks_listed.inc(listed)
            self._obs.attn_blocks_fetched.inc(fetched)
            # arealint: disable-next=THR001 single writer (the decode loop, at a drain); /statusz reads whichever pair the name holds
            self._attn_blocks = (self._attn_blocks[0] + listed, self._attn_blocks[1] + fetched)

    def kv_pools_status(self) -> dict | None:
        """/statusz ``kv_pools``: the groups of page pools the model's layers
        are served from (``model_cfg.kv_groups``: which layers write and read
        a group, how many tokens a slot keeps there) with what each holds
        now: the full group's pages under the page table, the window group's
        rings (a fixed ``pages_per_slot`` a window layer for every slot that
        holds a request), and the bytes of recurrent state beside them. None
        for a model whose attending layers all share one page table."""
        groups = getattr(self.model_cfg, "kv_groups", None) if self.model_cfg is not None else None
        if not groups or "window" not in groups or getattr(self, "slots", None) is None:
            return None
        out = {name: {k: (list(v) if isinstance(v, tuple) else v) for k, v in g.items()} for name, g in groups.items()}
        pool = self.slots.pool
        out["full"].update(pages_total=pool.n_pages - 1, pages_held=pool.used)
        ring = self.model_cfg.ring_pages(self.config.page_size)
        holding = sum(1 for i in range(self.config.max_batch_size) if self.slots.pages(i)) + len(self.slots.parked)
        out["window"].update(
            pages_per_slot=ring, layers=len(groups["window"]["writers"]),
            pages_total=ring * self.config.max_batch_size, pages_held=ring * holding,
        )
        rows = self.slots.hbm_rows(self.cache)
        out["state_bytes"] = rows["recurrent_state"]
        out["window_bytes"] = rows.get("window_rings", 0)
        out["serving_limit"] = self._model_limits().get("reason")  # why no cached prefix is served: ``window_rings`` where a ring is the slot's only tenant
        return out

    def decode_attention_status(self) -> dict | None:
        """/statusz ``decode_attention``: blocks of pages the decode steps'
        attention launches would fetch at one item a (live slot, block with
        tokens), and the blocks they fetched (a block that several slots'
        table rows name is one item), a step, since the engine started;
        ``fetched_share`` of 1 says no live slots alias pages. None for a
        model without K and V pages (or latent rows without a learned
        index) under the page table, and before a chunk has run on the
        kernel path."""
        listed, fetched = getattr(self, "_attn_blocks", (0, 0))
        if not listed:
            return None
        return {"blocks_listed": listed, "blocks_fetched": fetched, "fetched_share": round(fetched / listed, 4)}

    def row_steps_status(self) -> dict:
        """/statusz ``row_steps``: what the decode steps' rows were doing
        since the engine was built, summed at every drain: ``steps`` the
        chunk programs ran (x ``slots`` = every row-step paid for), ``live``
        row-steps that emitted a credited token, ``spent`` ones run for a
        request that had ended and ``dropped`` ones whose token went with a
        request gone by the drain; the rest were empty rows."""
        led = self._row_steps
        return {
            "slots": self.config.max_batch_size,
            "steps": led["steps"],
            "live": self.stats["generated_tokens"],
            "spent": led["spent"],
            "dropped": led["dropped"],
        }

    def sparse_attention_status(self) -> dict | None:
        """/statusz ``sparse_attention``: what a learned index selects for a
        decode step's queries (``index_topk`` cached tokens a slot and
        layer) and the form the selected rows are read in: ``masked`` =
        every page that holds tokens of the slot is fetched and the
        unselected rows meet a probability of 0 (the one form this engine
        has: PERF.md has what a gather of the selected rows cost). None for
        a model without an index."""
        topk = getattr(self.model_cfg, "index_topk", 0) if self.model_cfg is not None else 0
        return {"index_topk": int(topk), "read_form": "masked"} if topk else None

    def moe_status(self) -> dict | None:
        """/statusz ``moe``: ``load`` = rows of live slots every expert of
        every expert layer got from decode steps since the engine started,
        [expert layers][experts the router scores]; ``held`` = [first, last +
        1] of the experts whose weights this replica holds, where that is a
        share of them; None for a model without experts."""
        load = getattr(self, "_moe_load", None)  # None before initialize()
        if load is None:
            return None
        first = self.model_cfg.expert_first
        held = [first, first + self.model_cfg.num_experts]
        return {"load": load.tolist(), **({"held": held} if held != [0, load.shape[1]] else {})}

    def residual_status(self) -> dict | None:
        """/statusz ``residual``: the form of the model's residual path where
        it is not one vector a token and a sum (``mhc``: ``streams`` residual
        streams mixed before and after every sublayer, the stream-to-stream
        matrix made doubly stochastic by ``sinkhorn_iters`` rounds); None
        for every other model."""
        cfg = self.model_cfg
        if cfg is None or getattr(cfg, "residual_form", "sum") == "sum":
            return None
        return {"form": cfg.residual_form, "streams": int(cfg.hc_mult), "sinkhorn_iters": int(cfg.hc_sinkhorn_iters)}

    # -- prefix cache (cross-request radix reuse) --------------------------
    def prefix_cache_stats(self) -> dict:
        """Point-in-time radix-cache state for /statusz and tests."""
        tree = self.slots.prefix_stats()
        if tree is None:
            limits = self._model_limits()
            if "prefix_cache" in limits:
                return {"enabled": False, "disabled_by": limits["reason"]}
            return {"enabled": False}
        return {
            "enabled": True,
            **tree,
            # hit accounting is engine-owned: counted once per ADMITTED
            # request, so backlog retries can't inflate the hit rate
            "hits": self.stats["prefix_cache_hits"],
            "misses": self.stats["prefix_cache_misses"],
            "hit_tokens": self.stats["prefix_hit_tokens"],
        }

    def flush_prefix_cache(self, timeout: float = 10.0) -> int:
        """Drop every radix-cached page (ops endpoint /flush_prefix_cache).
        The tree is decode-loop-private, so a live loop performs the flush
        itself between chunks; we only marshal the request. Returns freed
        page count (0 on timeout or when the cache is disabled)."""
        if self.slots.radix is None:
            return 0
        if self._thread is None or not self._thread.is_alive():
            return self.slots.flush_prefix()
        with self._weight_lock:
            req = self._radix_flush_req
            if req is None:
                # concurrent flush calls SHARE one request: a second caller
                # overwriting the slot would leave the first blocking its
                # full timeout and reporting freed_pages=0
                req = (threading.Event(), [])
                self._radix_flush_req = req
        ev, box = req
        self._wakeup.set()
        ev.wait(timeout)
        return box[0] if box else 0

    def _service_radix_flush(self) -> None:
        with self._weight_lock:
            req = self._radix_flush_req
            self._radix_flush_req = None
        if req is None:
            return
        ev, box = req
        box.append(self.slots.flush_prefix())
        ev.set()

    def _image_embeds_for(self, group: list[tuple[_Task, int]], ids_np, bucket: int):
        """VLM admission: run the vision tower over each request's pixel
        patches (ModelRequest.image_data: [P_i, patch_dim]) and position the
        merged embeddings at the prompt's image-token slots. Returns
        [A, bucket, D] fp32 or None when the group carries no images."""
        mcfg = self.model_cfg
        if mcfg.vision is None or not any(
            t.req.image_data is not None for t, _ in group
        ):
            return None
        from areal_tpu.models import vision as vis

        merge2 = mcfg.vision.spatial_merge**2
        emb = np.zeros((len(group), bucket, mcfg.hidden_size), np.float32)
        # phase 1 — dispatch every image's ViT forward, keeping results ON
        # DEVICE: pulling each result inside the loop (the pre-burn-down
        # shape, PRF003) serialized every image's transfer behind its
        # compute instead of overlapping the group
        pending: list[tuple[int, _Task, int, Any]] = []  # (j, task, P, dev out)
        for j, (task, _) in enumerate(group):
            if task.req.image_data is None:
                continue
            px = np.asarray(task.req.image_data, np.float32)  # [P, pd]
            P = px.shape[0]
            if task.req.image_grid_thw is not None:
                pos = vis.grid_pos_ids(
                    task.req.image_grid_thw, mcfg.vision.spatial_merge
                )
            else:
                # all-zero rope positions lose all spatial structure — real
                # Qwen2-VL weights will produce garbage embeddings
                logger.warning(
                    f"rid={task.req.rid}: image_data without image_grid_thw; "
                    "vision rope positions default to (0,0) per patch"
                )
                pos = np.zeros((P, 2), np.int32)
            with set_mesh(self.mesh):
                vision, args = self.programs.vision_call(px, pos)
                out_dev = vision(self.params["vision"], *args)
            pending.append((j, task, P, out_dev))
        if not pending:
            return emb
        # phase 2 — ONE batched device->host pull for the whole admission
        # group, then the host-side scatter into image-token slots
        # arealint: disable-next=PRF001 designed admission-boundary sync: single batched pull after every image is dispatched
        fetched = jax.device_get([o for _, _, _, o in pending])
        for (j, task, P, _), out in zip(pending, fetched):
            out = np.asarray(out, np.float32)
            pos = np.where(ids_np[j] == mcfg.image_token_id)[0]
            if len(pos) != P // merge2:
                logger.warning(
                    f"VLM mismatch rid={task.req.rid}: {len(pos)} image-pad "
                    f"tokens vs {P // merge2} merged patch embeddings"
                )
            n = min(len(pos), P // merge2)
            emb[j, pos[:n]] = out[:n]
        return emb

    # -- decode loop ------------------------------------------------------
    @property
    def _slot_pages(self) -> list[list[int]]:
        # forwarding name: benchmarks/chip/benchlib/cells/rollout_family_select.py
        # reads the pages of the slot a probe request decodes in
        return [self.slots.pages(s) for s in range(self.config.max_batch_size)]

    def _deact_row(self, slot: int) -> np.ndarray:
        """The scatter row that takes ``slot`` out of the batch where it stands."""
        return pack_row(self._state, slot, 0, int(self._state["pos"][slot]), False, 0)

    def _slot_update_row(
        self, task: _Task, slot: int, last_id: int, pos: int, remaining: int
    ) -> np.ndarray:
        """Admit ``task`` into ``slot``: derive per-slot sampling state from
        the request and pack the device scatter row."""
        self._slot_progress[slot] = time.monotonic()  # watchdog baseline
        if task.timeline is not None:
            task.timeline.version = self._version
            # the prefill paths mark ADMITTED pre-prefill; only resumes and
            # other direct admissions stamp it here (a second mark would
            # drag the trace's queue_wait span over the prefill window)
            if task.timeline.ts_of(tl_mod.ADMITTED) is None:
                task.timeline.mark(tl_mod.ADMITTED, slot=slot)
        g = task.req.gconfig
        temp = 0.0 if g.greedy else g.temperature
        greedy = bool(g.greedy or g.temperature == 0.0)
        top_k = g.top_k if g.top_k and g.top_k > 0 else -1
        if top_k > TOPK_CAP:
            # the candidate set is statically capped; top_k beyond it (or a
            # top-p nucleus wider than the cap) samples from the top
            # TOPK_CAP tokens only — clamp loudly instead of silently
            logger.warning(
                f"top_k={top_k} exceeds the static candidate cap "
                f"{TOPK_CAP}; clamping (rid={task.req.rid})"
            )
            top_k = TOPK_CAP
        block = None
        if (B := self.programs.block) > 1:
            # a block model's slot starts at the first position past the whole blocks its pages hold: the prompt's
            # tokens beyond them (none after a resume: a request's emitted blocks end on a boundary) are the clean
            # positions its first block starts with. The request may override the model's generation defaults
            ids = task.req.input_ids
            pos = len(ids) // B * B
            mc = self.model_cfg
            steps = g.denoising_steps or mc.denoising_steps
            rule = g.remasking_strategy if g.remasking_strategy in qwen.REMASKING_RULES else mc.remasking_strategy
            thresh = mc.confidence_threshold if g.confidence_threshold is None else g.confidence_threshold
            block = (max(1, B // max(1, min(int(steps), B))), qwen.REMASKING_RULES.index(rule), float(thresh), list(ids[pos:]))
        return pack_row(
            self._state,
            slot,
            last_id,
            pos,
            True,
            remaining,
            top_k=top_k,
            greedy=greedy,
            temp=temp,
            top_p=g.top_p if g.top_p else 1.0,
            stops=[] if g.ignore_eos else g.stop_token_ids,
            # min_new_tokens gate, resume-aware: stops unlock after the
            # request has min_new tokens TOTAL (tokens emitted before an
            # abort/park count)
            min_rem=max(
                0,
                remaining - max(0, g.min_new_tokens - len(task.out_tokens)),
            ),
            freq_pen=self._effective_freq_pen(task),
            block=block,
        )

    def _effective_freq_pen(self, task: _Task) -> float:
        fp = float(task.req.gconfig.frequency_penalty or 0.0)
        if fp and not self._freq_enabled:
            # config-gated: honoring it needs the [S, V] count table +
            # penalized chunk variants — warn once, serve unpenalized
            # (pre-knob behavior) rather than failing agent traffic
            if not getattr(self, "_freq_pen_warned", False):
                self._freq_pen_warned = True
                logger.warning(
                    "frequency_penalty requested but "
                    "ServerConfig.enable_frequency_penalty is off — ignoring"
                )
            return 0.0
        return fp

    def _budget(self, task: _Task, prompt_len: int) -> int:
        g = task.req.gconfig
        T = self.config.max_seq_len
        budget = g.max_new_tokens
        if g.max_tokens is not None:
            budget = min(budget, g.max_tokens - prompt_len)
        return max(1, min(budget, T - 1 - prompt_len))

    def _try_resume(self, task: _Task) -> np.ndarray | None:
        """rid-affinity KV reuse: if this rid's previous abort left its slot
        cache intact and the resubmitted ids are exactly prompt+emitted,
        restore decode state with zero prefill. Returns the slot-update row."""
        rid = task.req.rid
        ids = list(task.req.input_ids)
        # page ownership + block-table row come back with the entry; a rid
        # reused with different content drops its stale parking instead
        p = self.slots.resume(rid, ids) if rid else None
        if p is None:
            return None
        slot = p.slot
        P_len = len(ids)
        if task.timeline is not None:
            # the abort-pause round-trip this resume closes: attributed to
            # the RESUMED attempt (the aborted attempt's timeline already
            # terminated with stop_reason=abort)
            park_s = max(0.0, time.monotonic() - p.park_time)
            task.timeline.park_s += park_s
            task.timeline.mark(tl_mod.RESUME, park_s=round(park_s, 6))
        task.slot = slot
        task.prompt_len = P_len
        self._slot_task[slot] = task
        row = self._slot_update_row(
            task, slot, ids[-1], p.pos, self._budget(task, P_len)
        )
        if self._freq_enabled and self._effective_freq_pen(task) != 0.0 and p.n_emitted:
            # one logical request across an abort: the COMPLETION tokens
            # emitted before the park (the tail of full_ids) keep their
            # repeat counts; the admission scatter zeroes the slot, so the
            # restore applies right after it
            emitted = np.asarray(ids[-p.n_emitted :], np.int64)
            counts = np.zeros(self.model_cfg.vocab_size, np.int64)
            np.add.at(counts, emitted, 1)
            self._pending_count_restore.append(
                (slot, np.minimum(counts, 65535).astype(np.uint16))
            )
        self.stats["kv_resumes"] += 1
        return row

    def _admit_pending(self) -> list[np.ndarray]:
        """Admit backlog + queue into slots: resume parked rids in place,
        then group fresh prompts by length bucket and batch-prefill. Returns
        the packed slot-update rows to scatter on device (the prefill cache
        writes are already enqueued).

        Prefix sharing: tasks with IDENTICAL prompts (a GRPO group's
        n_samples of one question) prefill ONCE; the other slots get a
        cheap on-device KV row copy — (k-1)/k of group prefill FLOPs saved
        (reference leans on SGLang's radix cache for this,
        remote_inf_engine.py:753-763)."""
        T = self.config.max_seq_len
        rows: list[np.ndarray] = []
        to_prefill: list[tuple[_Task, int]] = []  # (task, slot)
        free = self.slots.free_slots(t is not None for t in self._slot_task)
        in_wait = 0  # admitted here, submitted after this pass began
        while not self._paused.is_set():
            if self._backlog:
                task = self._backlog.popleft()
            else:
                try:
                    task = self._queue.get_nowait()
                except queue.Empty:
                    break
            P_len = len(task.req.input_ids)
            if P_len >= T - 2 or P_len == 0:
                self._finish(task, StopReason.LENGTH.value)
                continue
            dl = task.req.deadline
            if (
                self._lifecycle() is not None
                and dl is not None
                and time.time() > dl
            ):
                # expired while queued: don't waste a prefill on a request
                # whose budget is already gone (docs/request_lifecycle.md)
                task.truncated_by = "deadline"
                self._finish(task, StopReason.DEADLINE.value)
                continue
            row = self._try_resume(task)
            if row is not None:
                rows.append(row)
                in_wait += task.submit_time > self._pass_start
                continue
            if not free:
                evicted = self.slots.evict_oldest_parked()
                if evicted is None:
                    self._backlog.appendleft(task)  # all slots busy
                    break
                free.append(evicted)
            to_prefill.append((task, free.pop(0)))
            in_wait += task.submit_time > self._pass_start
        if in_wait:
            # arrived after the pass began: a commit at the pass's start
            # would have left them a chunk behind
            self._obs.admitted_in_wait.inc(in_wait)

        # split identical-prompt duplicates off (vision requests excluded —
        # their KV depends on image data too)
        primaries: list[tuple[_Task, int]] = []
        dup_pairs: list[tuple[_Task, int, int]] = []  # (task, slot, src_slot)
        first_slot: dict[tuple, int] = {}
        for task, slot in to_prefill:
            key = tuple(task.req.input_ids)
            if task.req.image_data is None and key in first_slot:
                dup_pairs.append((task, slot, first_slot[key]))
            else:
                if task.req.image_data is None:
                    first_slot[key] = slot
                primaries.append((task, slot))

        # radix lookup (cross-request prefix cache): primaries whose prompt
        # has a cached page-aligned prefix alias those pages and prefill
        # only the suffix; the rest take the plain full-prefill path
        cold: list[tuple[_Task, int]] = []
        warm: list[tuple[_Task, int, list[int], list[int]]] = []
        with self._kphase("radix_match"):
            for task, slot in primaries:
                m = self._radix_match(task)
                if m is None:
                    cold.append((task, slot))
                else:
                    warm.append((task, slot, m[0], m[1]))

        # group by length bucket, prefill in batches of PREFILL_SIZES
        by_bucket: dict[int, list[tuple[_Task, int]]] = {}
        for task, slot in cold:
            bucket = self.programs.prompt_bucket(len(task.req.input_ids))
            by_bucket.setdefault(bucket, []).append((task, slot))
        with self._kphase("prefill"):
            for bucket, group in sorted(by_bucket.items()):
                i = 0
                sizes = self.programs.prefill_sizes(bucket)
                while i < len(group):
                    A = next(a for a in sizes if a <= len(group) - i)
                    rows.extend(self._prefill_group(group[i : i + A], bucket))
                    i += A
        # warm admissions group by SUFFIX bucket (the only tokens prefilled)
        warm_by_bucket: dict[int, list[tuple[_Task, int, list[int], list[int]]]] = {}
        psz = self.config.page_size
        for task, slot, mpages, mvers in warm:
            sfx = len(task.req.input_ids) - len(mpages) * psz
            bucket = self.programs.prompt_bucket(sfx)
            warm_by_bucket.setdefault(bucket, []).append(
                (task, slot, mpages, mvers)
            )
        with self._kphase("prefill"):
            for bucket, group in sorted(warm_by_bucket.items()):
                i = 0
                while i < len(group):
                    A = next(a for a in PREFILL_SIZES if a <= len(group) - i)
                    rows.extend(
                        self._prefill_group_prefixed(group[i : i + A], bucket)
                    )
                    i += A
        if dup_pairs:
            rows.extend(self._admit_duplicates(dup_pairs))
        return rows

    def _radix_match(self, task: _Task) -> tuple[list[int], list[int]] | None:
        """Longest cached page-aligned prefix for a fresh admission, with
        the pool refs on its pages taken (``SlotCache.match``): a task that
        later backlogs must release them (``unmatch``)."""
        if self.slots.radix is None or task.req.image_data is not None:
            return None
        m = self.slots.match(task.req.input_ids)
        if m is None:
            self.stats["prefix_cache_misses"] += 1
        # hit stats are counted at ADMISSION (in _prefill_group_prefixed),
        # not here: a pool-pressure backlog retries the match every wave
        # and would inflate the hit rate with re-counted tokens
        return m

    def _prefill_group_prefixed(
        self, group: list[tuple[_Task, int, list[int], list[int]]], bucket: int
    ) -> list[np.ndarray]:
        """Admit tasks whose prompt prefix is radix-cached: alias the
        matched pages (already pool-ref'd by ``_radix_match``), allocate
        pages for the suffix only, and run the suffix-only prefill variant
        attending over the cached prefix. ``bucket`` buckets the SUFFIX
        length; the prefix page-table width compiles per power-of-two."""
        psz = self.config.page_size
        npg = -(-bucket // psz)
        admitted: list[tuple[_Task, int, list[int], list[int]]] = []
        page_rows: list[np.ndarray] = []
        for task, slot, mpages, mvers in group:
            plen = len(task.req.input_ids)
            sfx = plen - len(mpages) * psz
            need = -(-sfx // psz)
            pages = self.slots.take(need)
            if pages is None:
                # pool pressure: release the match refs and retry the task
                # as a fresh admission later
                self.slots.unmatch(mpages)
                self._backlog.append(task)
                continue
            self.slots.assign(
                slot, list(mpages) + pages, list(mvers) + [self._version] * len(pages)
            )
            row = np.zeros(npg, np.int32)  # 0 = trash page for padded rows
            row[:need] = pages
            page_rows.append(row)
            admitted.append((task, slot, mpages, mvers))
        if not admitted:
            return []
        for task, slot, mpages, _mvers in admitted:
            # the hit rides response metadata -> /generate JSON so the
            # routing brain can audit predicted-vs-actual prefix locality
            task.req.metadata["cached_prefix_tokens"] = len(mpages) * psz
            if task.timeline is not None:
                task.timeline.mark(tl_mod.ADMITTED, slot=slot)
                task.timeline.mark(
                    tl_mod.RADIX_MATCH,
                    hit_pages=len(mpages),
                    hit_tokens=len(mpages) * psz,
                )
                task.timeline.mark(tl_mod.PREFILL_START)
        A = len(admitted)
        ids_np = np.zeros((A, bucket), np.int32)
        plens = np.zeros(A, np.int32)
        offs = np.zeros(A, np.int32)
        for j, (task, _slot, mpages, _mvers) in enumerate(admitted):
            ids = list(task.req.input_ids)
            n_tok = len(mpages) * psz
            ids_np[j, : len(ids) - n_tok] = ids[n_tok:]
            plens[j] = len(ids) - n_tok
            offs[j] = n_tok
        with set_mesh(self.mesh):
            prefill, args = self.programs.prefill_paged_call(
                ids_np, plens, offs, np.stack(page_rows), [mpages for _, _, mpages, _ in admitted]
            )
            self.cache = prefill(self.params, self.cache, *args)
        rows = []
        sfx_tokens = 0
        hit_tokens = 0
        for j, (task, slot, mpages, _mvers) in enumerate(admitted):
            full = list(task.req.input_ids)
            P_len = len(full)
            if task.timeline is not None:
                task.timeline.mark(tl_mod.PREFILL_END, suffix_tokens=int(plens[j]))
            task.slot = slot
            task.prompt_len = P_len
            self._slot_task[slot] = task
            sfx_tokens += int(plens[j])
            hit_tokens += len(mpages) * psz
            rows.append(
                self._slot_update_row(
                    task, slot, full[-1], P_len - 1, self._budget(task, P_len)
                )
            )
        self.stats["prefills"] += A
        self.stats["prefill_batches"] += 1
        self.stats["prefill_tokens"] += sfx_tokens
        self.stats["prefix_cache_hits"] += A
        self.stats["prefix_hit_tokens"] += hit_tokens
        self._obs.prefills.inc(A)
        self._obs.prefill_tokens.inc(sfx_tokens)
        self._obs_pc.hit_tokens.inc(hit_tokens)
        return rows

    def _admit_duplicates(
        self, pairs: list[tuple[_Task, int, int]]
    ) -> list[np.ndarray]:
        """Shared-prefix admission by **page aliasing**: duplicates share the
        primary's full prompt pages (refcount++, zero copies) and take a
        private copy of only the page the decode head writes into (the page
        holding row ``plen-1``). This is the GRPO-group radix-cache
        equivalent (reference leans on SGLang's radix cache,
        remote_inf_engine.py:753-763) at page granularity."""
        psz = self.config.page_size
        rows: list[np.ndarray] = []
        copy_dst: list[int] = []
        copy_src: list[int] = []
        slot_dst: list[int] = []  # the same pairs by slot: the recurrent
        slot_src: list[int] = []  # state, where there is one, is copied too
        for task, slot, src_slot in pairs:
            ids = list(task.req.input_ids)
            plen = len(ids)
            n_shared = (plen - 1) // psz  # pages decode will never write
            pair = self.slots.alias(slot, src_slot, n_shared)
            if pair is None:
                # the primary wasn't admitted (pool pressure backlogged it in
                # _prefill_group), so this duplicate has nothing to alias, or
                # no private page can be had: retry it as a fresh admission
                # next round
                self._backlog.append(task)
                continue
            copy_dst.append(pair[0])
            copy_src.append(pair[1])
            slot_dst.append(slot)
            slot_src.append(src_slot)
            task.slot = slot
            task.prompt_len = plen
            self._slot_task[slot] = task
            rows.append(
                self._slot_update_row(
                    task, slot, ids[-1], plen - 1, self._budget(task, plen)
                )
            )
        if copy_dst:
            with set_mesh(self.mesh):
                copy, args = self.programs.pagecopy_call(copy_dst, copy_src, slot_dst, slot_src)
                self.cache = copy(self.cache, *args)
            # what a sibling cannot share by reference: a recurrent state, or the window layers' rings (a ring may be
            # the slot's ONLY tenant beside its pages)
            if self.model_cfg.has_slot_tenant:
                self._obs.state_copies.inc(len(copy_dst))
        self.stats["prefix_shared"] = self.stats.get("prefix_shared", 0) + len(
            copy_dst
        )
        return rows

    def _prefill_group(
        self, group: list[tuple[_Task, int]], bucket: int
    ) -> list[np.ndarray]:
        psz = self.config.page_size
        npg = -(-bucket // psz)  # ceil: tiny max_seq_len can make bucket < psz
        admitted: list[tuple[_Task, int]] = []
        page_rows: list[np.ndarray] = []
        # what the slots already decoding, and those admitted here, take for
        # their next chunks (one in flight, one ahead) stays free: a wave of
        # long prompts that took the pool's last page would have the first
        # decode step preempt one of them for it
        per_slot = -(-(2 * self.programs.chunk_ahead + 1) // psz)
        decoding = int(np.count_nonzero(self._state["active"]))
        for task, slot in group:
            plen = len(task.req.input_ids)
            need = -(-plen // psz)
            # nothing decodes and nothing was admitted: nobody to wait for, so nothing to keep
            keep = (decoding + len(admitted) + 1) * per_slot if decoding or admitted else 0
            pages = self.slots.take(need, keep=keep)
            if pages is None:
                self._backlog.append(task)  # pool pressure: retry later
                continue
            self.slots.assign(slot, pages, [self._version] * need)
            row = np.zeros(npg, np.int32)  # 0 = trash page for padded rows
            row[:need] = pages
            page_rows.append(row)
            admitted.append((task, slot))
        if not admitted:
            return []
        for task, slot in admitted:
            if task.timeline is not None:
                task.timeline.mark(tl_mod.ADMITTED, slot=slot)
                task.timeline.mark(tl_mod.PREFILL_START)
        A = len(admitted)
        ids_np = np.zeros((A, bucket), np.int32)
        plens = np.zeros(A, np.int32)
        for j, (task, _slot) in enumerate(admitted):
            ids = list(task.req.input_ids)
            ids_np[j, : len(ids)] = ids
            plens[j] = len(ids)
        slots_np = np.asarray([slot for _task, slot in admitted], np.int32)
        img = self._image_embeds_for(admitted, ids_np, bucket)
        with set_mesh(self.mesh):
            prefill, args = self.programs.prefill_call(ids_np, plens, np.stack(page_rows), slots_np, img)
            self.cache = prefill(self.params, self.cache, *args)
        rows = []
        for j, (task, slot) in enumerate(admitted):
            P_len = int(plens[j])
            if task.timeline is not None:
                task.timeline.mark(tl_mod.PREFILL_END, prompt_tokens=P_len)
            task.slot = slot
            task.prompt_len = P_len
            self._slot_task[slot] = task
            rows.append(
                self._slot_update_row(
                    task,
                    slot,
                    int(ids_np[j, P_len - 1]),
                    P_len - 1,
                    self._budget(task, P_len),
                )
            )
        self.stats["prefills"] += A
        self.stats["prefill_batches"] += 1
        prompt_tokens = int(plens.sum())
        self.stats["prefill_tokens"] += prompt_tokens
        self._obs.prefills.inc(A)
        self._obs.prefill_tokens.inc(prompt_tokens)
        if self.model.prefill_attn_launch(self.model_cfg, bucket):
            self._obs.prefill_attn_launch_tokens.inc(prompt_tokens)
        if self.model.kda_prefill_launch(self.model_cfg, bucket):
            self._obs.prefill_kda_launch_tokens.inc(prompt_tokens)
        if "shared_kv_tokens_read" in self.model_cfg.count_shapes:
            # the program's rows ended at the shared layer's K and V: a prompt's one row of the layers past it is the
            # decode step's that feeds its last token again
            self._obs.prefill_last_token_rows.inc(A)
        if rings := (getattr(self.model_cfg, "kv_groups", None) or {}).get("window"):
            # query-key pairs inside the band the window layers' prompt pass attended: sum_t min(t + 1, window) a
            # row x window layers, from the rows' lengths, whatever computed the product
            from areal_tpu.inference.paged_kv import band_pairs

            self._obs.window_prompt_pairs.inc(len(rings["writers"]) * sum(band_pairs(int(n), rings["keeps"]) for n in plens))
        if getattr(self.model_cfg, "residual_form", "sum") == "mhc":
            # (prompt token, sublayer) stream mixes the prompt pass made: two a layer, from the rows' lengths,
            # whatever implements the mix
            self._obs.prefill_mhc_token_sublayers.inc(2 * self.model_cfg.num_layers * prompt_tokens)
        rebuilt = self.slots.readmitted(t.req.rid for t, _ in admitted)
        if self.model_cfg.has_slot_tenant:
            self._obs.state_prefills.inc(rebuilt)
        return rows

    def _apply_slot_updates(self, rows: list[np.ndarray]) -> None:
        """Scatter admission rows into the device state: one upload, one
        jitted execute (``DecodePrograms.update_call``)."""
        if not rows:
            return
        with set_mesh(self.mesh):
            apply, args = self.programs.update_call(rows)
            self._dev_state = apply(self._dev_state, *args)
            for slot, counts in self._pending_count_restore:
                self._dev_state["freq_counts"] = (
                    self._dev_state["freq_counts"].at[slot].set(
                        jnp.asarray(counts)
                    )
                )
            self._pending_count_restore.clear()

    def _finish(self, task: _Task, reason: str) -> None:
        if task.slot >= 0:
            self._slot_task[task.slot] = None
            self._state["active"][task.slot] = False
            if reason != StopReason.ABORT.value:
                # completed requests publish their prompt+output pages into
                # the radix tree BEFORE the release below — the tree's
                # own refs keep published pages alive. Aborts don't publish
                # here: parked rids publish in _abort_all (and keep page
                # ownership), preemptions exist to free memory.
                self.slots.publish(
                    list(task.req.input_ids) + list(task.out_tokens),
                    self.slots.pages(task.slot),
                    self.slots.page_versions(task.slot),
                    int(self._state["pos"][task.slot]),
                    self._version,
                )
            # release KV pages (a parked rid already transferred ownership
            # to its parked entry, leaving this list empty)
            self.slots.release(task.slot)
        bd: dict[str, float] = {}
        if task.timeline is not None:
            # terminal stage event + catalogued histogram observation; the
            # breakdown rides the response so callers attribute latency
            # without scraping (docs/observability.md "Request timelines")
            bd = self.timeline.complete(
                task.timeline, reason, len(task.out_tokens)
            )
        resp = ModelResponse(
            input_tokens=list(task.req.input_ids),
            output_tokens=task.out_tokens,
            output_logprobs=task.out_logprobs,
            output_versions=task.out_versions,
            output_denoise_pass=task.out_passes,
            stop_reason=reason,
            truncated_by=task.truncated_by,
            latency=time.monotonic() - task.submit_time,
            ttft=(task.first_token_time or time.monotonic()) - task.submit_time,
            **{k: bd.get(k, 0.0) for k in io_struct.TIMING_FIELDS},
            rid=task.req.rid,
            metadata=dict(task.req.metadata),
        )
        if reason == StopReason.ABORT.value:
            self.stats["aborted"] += 1
            self._obs.aborted.inc()
        elif reason == StopReason.DEADLINE.value:
            self.stats["deadline_exceeded"] += 1
            self._obs_lc.deadline_exceeded.inc()
        elif reason == StopReason.CANCEL.value:
            self.stats["cancelled"] += 1
            self._obs_lc.aborts.inc()
        else:
            self.stats["completed"] += 1
            self._obs.completed.inc()
        try:
            task.callback(resp)
        except Exception:
            logger.exception("generation callback failed")

    def _abort_all(self) -> None:
        st = self._state
        deact: list[int] = []
        for slot, task in enumerate(self._slot_task):
            if task is not None:
                rid = task.req.rid
                if rid and st["active"][slot]:
                    # retain KV for rid-affinity resume (client resubmits
                    # prompt+emitted after continue_generation); page
                    # ownership moves to the parked entry so _finish below
                    # doesn't free them
                    # (and the prefix is published at park time)
                    self.slots.park(
                        rid,
                        slot,
                        list(task.req.input_ids) + list(task.out_tokens),
                        int(st["pos"][slot]),
                        len(task.out_tokens),
                        self._version,
                    )
                    if task.timeline is not None:
                        task.timeline.mark(
                            tl_mod.PARK, n_emitted=len(task.out_tokens)
                        )
                if st["active"][slot]:
                    deact.append(slot)
                self._finish(task, StopReason.ABORT.value)
        # the device state is authoritative between uploads: deactivate the
        # aborted slots there too, or the next dispatched chunk would keep
        # decoding into parked/released caches
        if deact and self.cache is not None:
            self._apply_slot_updates([self._deact_row(slot) for slot in deact])

    def _ensure_pages(self, ahead: int | None = None) -> None:
        """Allocation-ahead: every active slot gets pages covering
        ``pos + ahead`` writes — by default ``2*n_steps`` (host pos can be
        one in-flight chunk stale); speculative rounds pass their exact
        synchronous coverage instead. On pool exhaustion, evict parked KV
        first, then preempt the active slots with the most remaining budget
        (they abort with their partial tokens; the client's retry loop
        re-submits them — the same backpressure role SGLang's
        RETRACT_DECODE preemption plays)."""
        st = self._state
        psz = self.config.page_size
        n_steps = self.programs.chunk_ahead  # positions a chunk may write (a block model: blocks, not steps)
        if ahead is None:
            ahead = 2 * n_steps
        deact_rows: list[np.ndarray] = []
        clamp_rows: list[tuple[int, int]] = []  # (slot, remaining cap)
        for slot in np.nonzero(st["active"])[0]:
            if not st["active"][slot]:  # preempted by an earlier iteration
                continue
            need = min(
                self.slots.maxp, -(-(int(st["pos"][slot]) + ahead + 1) // psz)
            )
            # the pool and the ladder below it are the slot cache's; once
            # they are exhausted, whom to preempt is decided here
            while not self.slots.extend(slot, need, self._version):
                victim = self._preempt_victim()
                if victim is None or victim == slot:
                    # cannot free enough. If the pages this slot already
                    # holds cover further decoding EVEN IF the device is
                    # a full in-flight chunk ahead of the host view,
                    # clamp its remaining budget to that coverage via a
                    # remaining-only scatter (a full _pack_row would
                    # rewind device pos/ids by up to n_steps — the
                    # device state is authoritative); it then finishes
                    # by length inside a chunk. Otherwise abort it.
                    covered = (
                        len(self.slots.pages(slot)) * psz
                        - 1
                        - (int(st["pos"][slot]) + n_steps)
                    )
                    if covered <= 0:
                        deact_rows.append(self._preempt(int(slot)))
                        break
                    st["remaining"][slot] = min(
                        int(st["remaining"][slot]), covered
                    )
                    clamp_rows.append((int(slot), covered))
                    break
                deact_rows.append(self._preempt(victim))
        if deact_rows:
            self._apply_slot_updates(deact_rows)
        if clamp_rows:
            with set_mesh(self.mesh):
                clamp, args = self.programs.clamp_call(clamp_rows)
                self._dev_state = clamp(self._dev_state, *args)

    def _preempt_victim(self) -> int | None:
        """Active slot with the most remaining generation budget (frees the
        most future page demand per abort)."""
        st = self._state
        best, best_rem = None, -1
        for slot, task in enumerate(self._slot_task):
            if task is None or not st["active"][slot]:
                continue
            if int(st["remaining"][slot]) > best_rem:
                best, best_rem = slot, int(st["remaining"][slot])
        return best

    def _preempt(self, slot: int) -> np.ndarray:
        """Abort one active slot to reclaim its pages (no parking — the
        point is to free memory). Returns the deactivation scatter row."""
        task = self._slot_task[slot]
        row = self._deact_row(slot)
        self.flight.record(
            "preempt", severity="warn", slot=slot, rid=task.req.rid
        )
        if task.req.rid:
            self.slots.mark_dropped(task.req.rid)
        self._finish(task, StopReason.ABORT.value)
        self.stats["preempted"] = self.stats.get("preempted", 0) + 1
        return row

    def _dispatch_chunk(self) -> dict | None:
        """Enqueue one decode chunk against the device-resident state and
        return a pending record; the packed emissions are downloaded later
        (next iteration) so the chunk's compute overlaps host processing of
        the previous chunk — over a high-latency link the download RTT is
        fully hidden behind device compute."""
        cfg = self.config
        st = self._state
        active = st["active"]
        if not active.any():
            return None
        self._ensure_pages()
        active = st["active"]  # _ensure_pages may preempt
        if not active.any():
            return None
        n_steps = cfg.decode_steps_per_call
        # host pos can be one in-flight chunk stale -> widen by 2 chunks
        wp = self.programs.window_pages(int(st["pos"][active].max()), 2 * self.programs.chunk_ahead)
        capped = bool(((st["top_k"] > 0) | (st["top_p"] < 1.0))[active].any())
        greedy_any = bool(st["greedy"][active].any())
        freq_any = self._freq_enabled and bool(
            (st["freq_pen"] != 0.0)[active].any()
        )
        chunk = self.programs.chunk_fn(n_steps, wp, capped, greedy_any, freq_any)
        # a block model's chunk stamps every position it commits with the weights of that pass
        block = self.programs.block > 1
        version = (jnp.asarray(self._version, jnp.int32),) if block else ()
        with set_mesh(self.mesh):
            pt = jnp.asarray(self.slots.page_table(wp))
            self.cache, self._dev_state, self._rng, packed = chunk(
                self.params, self.cache, pt, self._dev_state, self._rng, *version
            )
        return {
            "block": block,
            "packed": packed,
            "n_steps": n_steps,
            "version": self._version,
            "was_active": active.copy(),
            # the chunk program's key: another program, another chunk time
            "key": (wp, capped, greedy_any, freq_any),
            # task identity per slot at dispatch: a slot can turn over
            # between dispatch and drain (its task finished in an earlier
            # drain, a new task admitted) — results then belong to the OLD
            # task, and the new one must not be touched
            "tasks": list(self._slot_task),
        }

    def attention_impl(self) -> dict[str, str]:
        """Which attention implementation each serving path uses
        (``DecodePrograms.attention_impl``; ``verify`` is ``off`` while no
        speculative round runs)."""
        return self.programs.attention_impl(speculative=self._spec_cfg is not None)

    @property
    def _use_kernel(self) -> bool:
        # forwarding name: benchmarks/chip/benchlib/cells/rollout_family_select.py
        # reads it to call the model's forward the way the chunk program does
        return self.programs.use_kernel

    def set_speculative(self, enabled: bool) -> None:
        """Runtime toggle for speculative decoding (on/off runs without an
        engine rebuild); applies from the next loop pass. Safe from any
        thread: the loop reads ``_spec_cfg`` once per pass and a spec pass
        always drains the pipelined chunk before its own round."""
        spec = self.config.speculative
        refused = self._model_limits().get("speculative")
        if enabled and refused:
            raise ValueError(refused)
        spec.enabled = bool(enabled)
        if enabled:
            from areal_tpu.inference import speculative as spec_mod

            self._drafter = spec_mod.build_drafter(spec, radix=self.slots.radix)
            self._spec_cfg = spec
        else:
            self._spec_cfg = None
            self._drafter = None
        self._wakeup.set()

    def _spec_round(self) -> tuple[int, bool]:
        """One SYNCHRONOUS speculative round: host drafter proposes, one
        jitted verify+accept call scores and commits, the packed result
        drains through the normal bookkeeping, then over-allocated pages
        roll back through the pool. Synchronous because the accept decision
        gates the next round's drafts — the pipelined-chunk overlap trick
        cannot apply; the round itself must beat ``accepted+1`` sequential
        steps to win. Returns (credited tokens, whether a round ran)."""
        cfg = self.config
        spec = self._spec_cfg
        st = self._state
        if not st["active"].any():
            return 0, False
        B = spec.max_nodes()
        K = B - 1
        # exact coverage for this round's writes (rows pos..pos+K) plus the
        # next pending row; host pos is authoritative here (no in-flight
        # chunk), unlike the pipelined path's 2-chunk slack
        self._ensure_pages(ahead=B)
        active = st["active"]
        if not active.any():
            return 0, False
        with self._kphase("draft"):
            from areal_tpu.inference import speculative as spec_mod

            contexts: dict[int, list[int]] = {}
            for slot in np.nonzero(active)[0]:
                task = self._slot_task[slot]
                if task is None:
                    continue
                # context ends with the pending token (st["ids"][slot]):
                # drafts propose what FOLLOWS it
                contexts[int(slot)] = task.req.input_ids + task.out_tokens
            bundle = spec_mod.draft_batch(self._drafter, contexts, len(st["active"]), K)
            for slot in contexts:
                task = self._slot_task[slot]
                nd = int(bundle.n_draft[slot])
                if nd and task is not None and task.timeline is not None:
                    task.timeline.mark(
                        tl_mod.DRAFT, n_draft=nd, source=bundle.sources[slot]
                    )
        wp = self.programs.window_pages(int(st["pos"][active].max()), B)
        capped = bool(((st["top_k"] > 0) | (st["top_p"] < 1.0))[active].any())
        greedy_any = bool(st["greedy"][active].any())
        fn = self.programs.spec_fn(B, wp, capped, greedy_any)
        with self._kphase("dispatch"):
            with set_mesh(self.mesh):
                pt = jnp.asarray(self.slots.page_table(wp))
                drafts = {
                    "tokens": jnp.asarray(bundle.tokens),
                    "parent_row": jnp.asarray(bundle.parent_row),
                    "depth": jnp.asarray(bundle.depth),
                    "mask": jnp.asarray(bundle.mask),
                    "n_draft": jnp.asarray(bundle.n_draft),
                }
                self.cache, self._dev_state, self._rng, packed = fn(
                    self.params, self.cache, pt, self._dev_state, self._rng,
                    drafts,
                )
        with self._kphase("verify"):
            # arealint: disable-next=PRF002 designed synchronous round: the spec path has no pipelined successor to overlap with, so this blocking pull IS the verify forward's device time (the spec twin of device_wait) and is what the "verify" kphase measures
            packed_np = np.asarray(packed)
        pending = {
            "packed": packed_np,
            "n_steps": B,
            "version": self._version,
            "was_active": active.copy(),
            "tasks": list(self._slot_task),
            "spec": True,  # one verify forward, not n_steps steps: the row ledger counts it apart
        }
        # acceptance accounting BEFORE _drain (it mutates slot ownership)
        emit_count = packed_np[2 * B]
        n_draft_total = int(bundle.n_draft.sum())
        n_accepted = 0
        source_tokens: dict[str, int] = {}
        for slot, task in enumerate(pending["tasks"]):
            if task is None or not active[slot]:
                continue
            if task is not self._slot_task[slot]:
                continue
            nd = int(bundle.n_draft[slot])
            acc = max(0, int(emit_count[slot]) - 1)
            if nd:
                n_accepted += acc
                src = bundle.sources[slot]
                source_tokens[src] = source_tokens.get(src, 0) + nd
                self._obs_spec.accepted_length.observe(acc)
                if task.timeline is not None:
                    task.timeline.mark(tl_mod.VERIFY, n_accepted=acc)
        self.stats["spec_rounds"] += 1
        self.stats["spec_draft_tokens"] += n_draft_total
        self.stats["spec_accepted_tokens"] += n_accepted
        self._obs_spec.rounds.inc()
        self._obs_spec.accepted_tokens.inc(n_accepted)
        for src, n in source_tokens.items():
            self._obs_spec.draft_tokens.labels(source=src).inc(n)
        credited = self._drain(pending)
        rolled = self._rollback_spec_pages()
        if rolled:
            self.stats["spec_rollback_pages"] += rolled
            self._obs_spec.rollback_pages.inc(rolled)
        return credited, True

    def _rollback_spec_pages(self) -> int:
        """Free speculation-allocated pages beyond each live slot's
        COMMITTED coverage (rows 0..pos hold written KV plus the pending
        token's row). Rejected drafts never wrote into these pages (the
        verify scatter routes non-accepted rows to trash), so this is the
        allocator-level rollback: after every round a slot owns exactly the
        pages its accepted tokens justify, and the pool audit
        (free + held + radix == total) holds mid-generation."""
        st = self._state
        psz = self.config.page_size
        freed = 0
        for slot in np.nonzero(st["active"])[0]:
            if self._slot_task[slot] is None:
                continue
            freed += self.slots.trim(slot, -(-(int(st["pos"][slot]) + 1) // psz))
        return freed

    def _drain(self, pending: dict | None) -> int:
        """Download one chunk's packed emissions (a single transfer) and
        credit tokens / finish tasks. Slots admitted after the chunk was
        dispatched are excluded via the was_active snapshot. Returns the
        credited token count (the kernel probe's per-step tok/s input)."""
        if pending is None:
            return 0
        with self._kphase("device_wait"):
            # the one device->host pull: blocks until the chunk's compute
            # finishes, so its span IS the visible device time of the pass
            t_pull = self._pace_clock()
            packed = self._pull(pending["packed"])
            t_back = self._pace_clock()
        self._pull_s += t_back - t_pull
        if "key" in pending:
            # two consecutive returns are one chunk's wall time apart
            self._pacer.pulled(t_back, pending["key"], laden=pending.get("laden", False))
        credited = 0
        with self._kphase("bookkeeping"):
            n_steps = pending["n_steps"]
            version = pending["version"]
            was_active = pending["was_active"]
            # a block model's chunk (``DecodePrograms._block_chunk``): a pass emits 0 to B tokens a slot, by block
            B = self.programs.block if pending.get("block") else 1
            rows = n_steps * B
            toks = packed[:rows]
            logps = packed[rows : 2 * rows].view(np.float32)
            if B > 1:
                passes, vers = packed[2 * rows : 3 * rows], packed[3 * rows : 4 * rows]
                emit_n = packed[4 * rows : 4 * rows + n_steps]  # [pass, slot]
                emit_count = emit_n.sum(0)
                # [pass, place, slot]: the places a slot's pass emitted, in sequence order
                took = (np.arange(B)[None, :, None] < emit_n[:, None, :]).reshape(rows, -1)
                tail = 4 * rows + n_steps
            else:
                emit_count = packed[2 * n_steps]
                tail = 2 * n_steps + 1
            active = packed[tail].astype(bool)
            pos = packed[tail + 1]
            if packed.shape[0] > tail + 2:  # a chunk's counts (a speculative round brings none)
                self._credit_counts(packed[tail + 2 :].reshape(-1))
            st = self._state
            now = time.monotonic()
            for slot, task in enumerate(pending["tasks"]):
                if task is None or not was_active[slot]:
                    continue
                if task is not self._slot_task[slot]:
                    continue  # slot turned over since dispatch; nothing to credit
                c = int(emit_count[slot])
                if c:
                    credited += c
                    if task.first_token_time is None:
                        task.first_token_time = now
                        if task.timeline is not None:
                            task.timeline.mark(tl_mod.FIRST_TOKEN)
                    if task.timeline is not None:
                        # per-chunk decode cadence; the timeline's event cap
                        # bounds long generations (durations stay exact)
                        task.timeline.mark(
                            tl_mod.DECODE_CHUNK, n_tokens=c, version=version
                        )
                    self._slot_progress[slot] = now  # watchdog: progress seen
                    # .tolist() converts in C — a genexpr of int()/float() costs
                    # ~S*n_steps Python calls per chunk on the serving hot loop
                    if B > 1:
                        at = took[:, slot]
                        task.out_tokens.extend(toks[at, slot].tolist())
                        task.out_logprobs.extend(logps[at, slot].tolist())
                        task.out_versions.extend(vers[at, slot].tolist())  # the weights of the pass that committed each
                        task.out_passes.extend(passes[at, slot].tolist())
                    else:
                        task.out_tokens.extend(toks[:c, slot].tolist())
                        task.out_logprobs.extend(logps[:c, slot].tolist())
                        task.out_versions.extend([version] * c)
                    self.stats["generated_tokens"] += c
                    self._obs.generated_tokens.inc(c)
                st["pos"][slot] = int(pos[slot])
                if B == 1:
                    st["ids"][slot] = int(toks[c - 1, slot]) if c else st["ids"][slot]
                st["remaining"][slot] -= c
                st["active"][slot] = bool(active[slot])
                if not active[slot]:
                    last = task.out_tokens[-1] if task.out_tokens else -1
                    g = task.req.gconfig
                    if (
                        not g.ignore_eos
                        and last in g.stop_token_ids
                        and len(task.out_tokens) >= g.min_new_tokens
                    ):
                        reason = StopReason.STOP.value
                    else:
                        reason = StopReason.LENGTH.value
                    self._finish(task, reason)
            self.stats["chunks"] += 1
            self._obs.chunks.inc()
            # the chunk's row ledger, from what this drain holds anyway: of the
            # rows x steps the program stepped for, ``credited`` were live,
            # ``spent`` ran under the device's mask for a request that had
            # ended (earlier in this chunk, or in the one before: the
            # dispatch's mask is a chunk stale), ``dropped`` emitted a token
            # for a request gone by now (preempted, turned over). A
            # speculative round is one step whose rows emit several tokens
            emitted = int(emit_count[was_active].sum())
            rows = int(was_active.sum()) * B  # a pass steps a block model's slot for B rows
            steps, spent = (1, 0) if pending.get("spec") else (n_steps, rows * n_steps - emitted)
            led = self._row_steps
            led["steps"] += steps
            led["rows"] += rows
            led["spent"] += spent
            led["dropped"] += emitted - credited
            self._obs.steps.inc(steps)
            self._obs.row_steps_spent.inc(spent)
        return credited

    def _pull(self, packed) -> np.ndarray:
        """A chunk's one device->host transfer: returns when the chunk's
        compute has ended."""
        return np.asarray(packed)

    def _loop_needed(self) -> bool:
        """Whether something waits that the loop serves at its top or at a
        pass's reap: the hold for the commit point ends for it at once."""
        return (
            self._shutdown.is_set()
            or self._paused.is_set()
            or self._held.is_set()
            or self._draining.is_set()
            or self._pending_weight_update is not None
            or self._radix_flush_req is not None
            or bool(self._abort_rids)
        )

    def _hold_for_commit(self, pending: dict | None) -> float:
        """With a chunk in flight, wait until the commit point inside it
        (inference/commit_point.py) so that what arrives until then is
        admitted into the next chunk, not the one after. Returns the seconds
        held; 0 without a chunk in flight or without an estimate of its time.

        The time is spent waiting for the device, so it goes under the
        ``device_wait`` phase. A submit wakes the wait and does not end it
        (one admission a pass); anything the loop must look at ends it."""
        if pending is None:
            self._pacer.reset()
            return 0.0
        at = self._pacer.commit_point(pending["key"])
        t0 = self._pace_clock()
        if at is None or at <= t0:
            return 0.0
        with self._kphase("device_wait"):
            while not self._loop_needed():
                left = at - self._pace_clock()
                if left <= 0:
                    break
                self._pace_wait(left)
                self._wakeup.clear()
        return self._pace_clock() - t0

    def _await_siblings(self) -> float:
        """A start from idle on a model with no prefix cache (a recurrent
        state, latent pages: slot_cache.py says which): the requests of a
        GRPO group arrive over some tens of milliseconds, the first of them
        wakes the loop, and a sibling that misses its group's admission can
        alias nothing afterwards, so it pays a prompt pass of its own (8
        clients' first groups of 4k-16k tokens on one v5e: 4-7 of 8 groups
        cut, 3.4-6.5 s of second prompt passes, another split every run:
        PERF.md section 6, PR 47). So the first admission waits until no
        request has arrived for a time that is small against the prompt pass
        it may save. Nothing decodes meanwhile, by the caller's condition,
        so no row waits on it but the queued ones. Returns the seconds
        waited."""
        t0 = seen_at = time.monotonic()
        seen = -1
        while not self._loop_needed():
            with self._queue.mutex:
                queued = [len(t.req.input_ids) for t in self._queue.queue]
            queued += [len(t.req.input_ids) for t in self._backlog]
            if not queued:
                break
            now = time.monotonic()
            if len(queued) != seen:
                seen, seen_at = len(queued), now
            gap = min(SIBLING_GAP_S, SIBLING_GAP_S_PER_TOKEN * max(queued))
            left = min(seen_at + gap, t0 + SIBLING_WAIT_S) - now
            if left <= 0:
                break
            self._wakeup.wait(left)
            self._wakeup.clear()
        return time.monotonic() - t0

    def _kphase(self, name: str):
        """Phase span on the current pass's kernel-probe timeline
        (observability/kernel_probe.py); a no-op null context outside a
        recorded pass (shutdown drain, direct calls from tests). Two
        monotonic-clock reads per span — never a device sync."""
        tl = self._ktl
        if tl is None:
            return contextlib.nullcontext()
        return tl.phase(name)

    def _abandon_kstep(self) -> None:
        """Discard the current pass's timeline (idle poll, pause, hold
        fence, torn-down cache): abandoned passes never reach the phase
        histograms, so every recorded step is a real chunk-work step."""
        if self._ktl is not None and self.kprobe is not None:
            self.kprobe.abandon_step(self._ktl)
        self._ktl = None

    def kernel_stats(self) -> dict:
        """Kernel-observatory summary for /statusz ``kernels`` (None-safe
        before initialize())."""
        if self.kprobe is None:
            return {}
        return self.kprobe.stats()

    def _run_pass(self, pending: dict | None, step_tl, span) -> tuple[dict | None, bool]:
        """One pass of the loop with the cache live: reap, admit, then either
        one speculative round or dispatch-then-drain. Returns the chunk now
        in flight and whether the loop has nothing to do until a wakeup.

        ``span`` is the pass's ``areal.decode.pass`` span (None for an idle
        poll); the phases inside are its children. It ends with the slots
        active after the pass, the tokens the pass credited, the row ledger of
        the chunk it drained (``LEDGER_KEYS``; ``spec=1`` on a speculative
        pass, whose ledger is its round's) and what its admission did:
        requests given a slot, prompt tokens handed to prefill programs,
        requests left waiting. (A pass that started with requests queued and
        admitted none of them, all expired, still leaves its span, with
        zeros.)"""
        self._pass_start = time.monotonic()
        t_pass = self._pace_clock()
        self._pull_s = 0.0
        ledger = dict(self._row_steps)  # before the pass's drain
        block_passes = self._block_passes
        # the chunk in flight has only just begun: commit the next one's
        # batch part-way through it, with everything that arrives until then
        held = self._hold_for_commit(pending)
        if (
            pending is None
            and self.slots.radix is None
            and not any(t is not None for t in self._slot_task)
        ):
            # from idle: the admission below is the only one that a group's
            # siblings can share
            with self._kphase("admission"):
                held += self._await_siblings()
        # lifecycle reaping BETWEEN chunks: cancellations, expired
        # deadlines (queued and decoding), per-slot watchdog — the
        # overload-safety half of interruptible generation. When a reap
        # fires, the in-flight chunk is drained first (tokens credited)
        # and None comes back; the fast path returns pending untouched.
        with self._kphase("admission"):
            in_flight = pending
            pending = self._reap_lifecycle(pending)
            if pending is not in_flight:
                self._pacer.reset()  # the device idles through the reap
            # admissions enqueue prefills + ONE packed state scatter; the
            # in-flight chunk (if any) ordered before them touches only
            # previously-active slots, so there is no dataflow hazard
            prefilled = self.stats["prefill_tokens"]
            rows = self._admit_pending()
            self._apply_slot_updates(rows)
            # what the admission left waiting: empty rows with demand
            queued = self._queue.qsize() + len(self._backlog)
        spec_on = self._spec_cfg is not None and self._drafter is not None
        if spec_on and self._freq_enabled:
            st = self._state
            # the in-round count updates the freq penalty needs are
            # incompatible with parallel verify scoring — fall back to
            # the sequential chunk path while any active slot uses it
            spec_on = not bool((st["freq_pen"] != 0.0)[st["active"]].any())
        drained = pending is not None
        if spec_on:
            # SYNCHRONOUS speculative pass: drain the pipelined chunk
            # first (covers the spec-off -> spec-on transition), then
            # draft + verify + accept in one round. A weight commit
            # always applies at the top of the pass, so draft and
            # verify run under ONE version — a commit landing "between
            # draft and verify" is impossible by construction, and
            # drafts are version-free host proposals anyway.
            tokens = self._drain(pending)
            n_spec, worked = self._spec_round()
            tokens += n_spec
            pending = None
        else:
            # speculatively dispatch the next chunk, then pay the previous
            # chunk's download while this one computes
            with self._kphase("dispatch"):
                dispatched = self._dispatch_chunk()
                if dispatched is not None:
                    dispatched["laden"] = bool(rows)  # this pass's prefills run before it: its interval is no chunk's time
            tokens = self._drain(pending)
            pending = dispatched
            worked = dispatched is not None
        self._pass_productive = bool(drained or worked or rows)
        if self._pass_productive:  # a bare poll says nothing of a pass's host work
            self._pacer.host_work(self._pace_clock() - t_pass - held - self._pull_s)
        if span is not None:
            span.set(
                active=int(self._state["active"].sum()),
                tokens=tokens,
                held_us=int(held * 1e6),
                **{k: self._row_steps[k] - ledger[k] for k in LEDGER_KEYS},  # of the chunk this pass drained
                **({"spec": 1} if spec_on else {}),
                # a block model: the slot-passes of the drained chunk that denoised and that committed
                **(
                    {"denoising": self._block_passes[0] - block_passes[0], "committing": self._block_passes[1] - block_passes[1]}
                    if self.programs.block > 1
                    else {}
                ),
                admitted=len(rows),  # requests given a slot
                prompt_tokens=self.stats["prefill_tokens"] - prefilled,
                queued=queued,
            )
        if step_tl is not None:
            # a pass that drained, dispatched, or admitted is a real
            # step; a bare poll (no slots, empty queue) is not
            if self._pass_productive:
                self._ktl = None
                self.kprobe.complete_step(step_tl, tokens=tokens)
            else:
                self._abandon_kstep()
        return pending, not worked and not any(
            t is not None for t in self._slot_task
        )

    def _loop(self) -> None:
        pending: dict | None = None
        while not self._shutdown.is_set():
            # arealint: disable-next=THR001 monotonic float heartbeat: torn reads are impossible for a GIL-protected float rebind and the wedge detector only compares against a multi-second threshold
            self._last_loop_ts = time.monotonic()
            # kernel observatory: one timeline per pass; idle/paused/held
            # passes abandon it, so recorded steps are always real chunk
            # work and the phase-sum identity holds on every record
            step_tl = (
                self.kprobe.begin_step() if self.kprobe is not None else None
            )
            self._ktl = step_tl
            self._apply_weight_update()
            self._service_radix_flush()
            # converge the prefix tree onto a shrunk autopilot cap
            self.slots.shrink_prefix_to_cap()
            if self._paused.is_set():
                self._abandon_kstep()
                self._drain(pending)
                pending = None
                self._abort_all()
                if self._draining.is_set():
                    # a draining replica leaves no queued request without a
                    # terminal — abort them now so callbacks fire (partial
                    # responses let callers resubmit elsewhere)
                    self._abort_queued()
                # release_memory waits on this: no chunk is in flight and
                # _abort_all (incl. KV parking) has completed
                self._pause_ack.set()
                self._wakeup.wait(timeout=0.05)
                self._wakeup.clear()
                continue
            if self._held.is_set():
                # commit fence (zero-pause weight sync): drain the in-flight
                # chunk, then idle with slots/KV/device state intact — no
                # aborts, no admissions. The pending staged commit applies at
                # the top of the next iteration; decoding resumes in place on
                # continue_generation and later tokens carry the new version.
                # Acks on _hold_ack, NOT _pause_ack: slots are still live
                # here, so the abort-pause contract does not hold.
                expiry = getattr(self.config, "hold_fence_timeout_s", 30.0)
                if (
                    expiry > 0
                    and time.monotonic() - getattr(self, "_hold_since", 0.0)
                    > expiry
                ):
                    # a lost /continue_generation must not wedge a replica
                    # that still answers /health ok — self-release
                    logger.warning(
                        f"hold fence exceeded {expiry:.0f}s without a "
                        "continue_generation; self-releasing (the commit, "
                        "if any, already applied between chunks)"
                    )
                    self._held.clear()
                    self._hold_ack.clear()
                    self._abandon_kstep()
                    continue
                # a hold-fence pass is abandoned even when it drains the
                # in-flight chunk: its wall time is fence stall, not a
                # decode step, and recording it would skew the phase means
                self._abandon_kstep()
                drained_chunk = pending is not None
                self._drain(pending)
                pending = None
                # a hold is legitimate idleness: keep the per-slot watchdog
                # baselines fresh so a long fence can't fire it on resume
                now_m = time.monotonic()
                for slot, t in enumerate(self._slot_task):
                    if t is not None:
                        self._slot_progress[slot] = now_m
                if not self._hold_marked:
                    # timeline: one FENCE_STALL event per hold window on
                    # every live request (the stall seconds accumulate
                    # below, pass by pass)
                    self._hold_marked = True
                    for t in self._slot_task:
                        if t is not None and t.timeline is not None:
                            t.timeline.mark(tl_mod.FENCE_STALL)
                self._hold_ack.set()
                # the stall window opens at the TOP of this pass
                # (_last_loop_ts): the staged-commit apply — the one H2D
                # under stage_target="host" — ran before this branch and is
                # fence stall, not decode. Only the pass that drained a real
                # in-flight chunk starts here instead (that chunk's compute
                # produced credited tokens, i.e. decode time).
                t_stall = (
                    time.monotonic() if drained_chunk else self._last_loop_ts
                )
                self._wakeup.wait(timeout=0.05)
                self._wakeup.clear()
                dt_stall = time.monotonic() - t_stall
                for t in self._slot_task:
                    if t is not None and t.timeline is not None:
                        t.timeline.fence_stall_s += dt_stall
                        if t.first_token_time is None:
                            # pre-first-token stall: outside TPOT's window
                            t.timeline.fence_stall_pre_first_s += dt_stall
                continue
            if self.cache is None:
                # memory released and not yet resumed: nothing to run on
                self._abandon_kstep()
                self._wakeup.wait(timeout=0.05)
                self._wakeup.clear()
                continue
            self._hold_marked = False  # next hold window marks afresh
            if not (
                pending is not None
                or self._backlog
                or not self._queue.empty()
                or any(t is not None for t in self._slot_task)
            ):
                # idle poll: nothing in flight, queued or decoding. Abandoned
                # before it runs, so it leaves no span and no step record
                self._abandon_kstep()
                step_tl = None
            self._pass_productive = False
            with (
                perf_tracer.trace_scope("areal.decode.pass", cpu=True)
                if step_tl is not None
                else contextlib.nullcontext()
            ) as span:
                pending, idle = self._run_pass(pending, step_tl, span)
            if self._pass_productive and span is not None:
                self._pass_watch.observe(span)
            if idle:
                self._wakeup.wait(timeout=0.05)
                self._wakeup.clear()
        self._ktl = None
        self._drain(pending)
        self._abort_all()
