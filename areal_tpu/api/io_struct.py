"""Request/response and metadata structs exchanged across the system.

Behavioral parity with reference areal/api/io_struct.py:25-376, with torch
tensors replaced by plain lists / numpy arrays (host-side control plane stays
framework-free; jax arrays only live inside engines).
"""

from __future__ import annotations

import dataclasses
import enum
import time
import uuid
from typing import Any


@dataclasses.dataclass
class GenerationHyperparameters:
    """Sampling controls (reference api/cli_args.py:100-240)."""

    n_samples: int = 1
    max_new_tokens: int = 16384
    min_new_tokens: int = 0
    max_tokens: int | None = None  # total budget incl. prompt; None = unlimited
    greedy: bool = False
    temperature: float = 1.0
    top_p: float = 1.0
    top_k: int = -1
    stop_token_ids: list[int] = dataclasses.field(default_factory=list)
    stop: list[str] = dataclasses.field(default_factory=list)
    frequency_penalty: float = 0.0
    # generate to the full token budget even when a stop token appears
    # (benchmark/profiling runs; reference ignore_eos semantics)
    ignore_eos: bool = False
    # detokenization control applied by workflows when rendering completions
    skip_special_tokens: bool = True
    # a block-diffusion model's generation (docs/serving.md "Block diffusion"); None = the model's default:
    # denoise passes a block (``block_length / denoising_steps`` positions committed a pass under the counted rules),
    # the rule (``sequential`` | ``low_confidence_static`` | ``low_confidence_dynamic``), the dynamic rule's threshold.
    # Every other model ignores them
    denoising_steps: int | None = None
    remasking_strategy: str | None = None
    confidence_threshold: float | None = None

    def new(self, **kwargs) -> "GenerationHyperparameters":
        return dataclasses.replace(self, **kwargs)


class StopReason(str, enum.Enum):
    STOP = "stop"  # EOS / stop token
    LENGTH = "length"  # max_new_tokens reached
    ABORT = "abort"  # interrupted (weight update in flight) — resumable
    TOOL_CALLS = "tool_calls"
    # request-lifecycle terminals (docs/request_lifecycle.md) — NOT
    # resumable: the client loop must not resubmit these
    DEADLINE = "deadline"  # deadline expired; partial output returned
    CANCEL = "cancelled"  # /abort_request (client gone / task failed)


@dataclasses.dataclass
class ModelRequest:
    """One generation request (reference io_struct.py ModelRequest)."""

    input_ids: list[int] = dataclasses.field(default_factory=list)
    gconfig: GenerationHyperparameters = dataclasses.field(
        default_factory=GenerationHyperparameters
    )
    rid: str = dataclasses.field(default_factory=lambda: uuid.uuid4().hex)
    metadata: dict[str, Any] = dataclasses.field(default_factory=dict)
    # vision: pre-extracted pixel patches [P, patch_dim] and the images'
    # (t, h, w) patch-grid shapes [n_images, 3] (drives the tower's 2-D rope)
    image_data: list[Any] | None = None
    image_grid_thw: list[Any] | None = None
    # absolute unix-epoch deadline (seconds). Propagated end-to-end as the
    # ``x-areal-deadline`` header; the decode loop reaps expired slots
    # between chunks and returns the partial output with
    # ``truncated_by="deadline"`` (docs/request_lifecycle.md).
    deadline: float | None = None


# the per-stage latency keys of the request-timeline breakdown, in the
# shape they travel: ModelResponse fields == /generate "timing" keys ==
# the client's cross-attempt accumulator == the proxy's areal_timing
# extension. One tuple so adding a stage is one edit, not five.
TIMING_FIELDS = (
    "queue_wait_s",
    "prefill_s",
    "decode_s",
    "fence_stall_s",
    "park_s",
)


@dataclasses.dataclass
class ModelResponse:
    """Generation result with per-token bookkeeping.

    ``output_versions[i]`` is the policy version that produced output token i —
    the key input to decoupled-PPO staleness correction (reference
    io_struct.py + remote_inf_engine.py:819-825).
    """

    input_tokens: list[int] = dataclasses.field(default_factory=list)
    output_tokens: list[int] = dataclasses.field(default_factory=list)
    output_logprobs: list[float] = dataclasses.field(default_factory=list)
    output_versions: list[int] = dataclasses.field(default_factory=list)
    # a block-diffusion model: the denoise pass of its block (0-based) that committed each output token, under whose
    # block state ``output_logprobs[i]`` and ``output_versions[i]`` were taken; empty for every other model
    output_denoise_pass: list[int] = dataclasses.field(default_factory=list)
    stop_reason: str = StopReason.STOP.value
    # lifecycle truncation flag: "" (normal), "deadline" (reaped at its
    # deadline between decode chunks), "watchdog" (no-progress abort), or
    # "cancelled" (/abort_request). Partial tokens/logprobs/versions are
    # still returned and stay per-token-version-consistent.
    truncated_by: str = ""
    latency: float = 0.0
    ttft: float = 0.0
    # request-timeline breakdown (observability/timeline.py): per-stage
    # latency attribution stamped by the engine at the terminal and summed
    # across abort/resume attempts by the client, so WorkflowExecutor /
    # trainer code can attribute rollout stalls without scraping metrics.
    # queue_wait + prefill + decode + fence_stall ≈ latency (park_s is the
    # abort-pause wait a resumed request carried; it overlaps queue_wait
    # of the resubmitted attempt and is informational).
    queue_wait_s: float = 0.0
    prefill_s: float = 0.0
    decode_s: float = 0.0
    fence_stall_s: float = 0.0
    park_s: float = 0.0
    rid: str = ""
    metadata: dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def input_len(self) -> int:
        return len(self.input_tokens)

    @property
    def output_len(self) -> int:
        return len(self.output_tokens)


@dataclasses.dataclass
class WeightUpdateMeta:
    """How trainer weights reach inference servers (reference io_struct.py).

    type:
    - "disk": trainer saves HF-format safetensors; servers reload from path.
    - "mem": host-staged device-to-device transfer over DCN — the TPU-native
      replacement for the reference's cross-job NCCL broadcast group
      (reference fsdp_engine.py:1047-1137). Weights stream as named bucketed
      chunks through a shared in-memory store / sidecar socket.
    """

    type: str = "disk"
    path: str | None = None
    with_version: bool = True
    alloc_mode: Any | None = None
    chunked_mem_mb: int = 128
    # mem-mode LoRA fast path: stream only the adapter leaves and let the
    # servers fold W += scale·(aN@bN − aOld@bOld) on device — ~25 MB instead
    # of the ~3 GB (1.5B) merged tree per update. The engine fills
    # ``lora_scale`` (= alpha/rank) when it builds the update.
    lora_only: bool = False
    lora_scale: float = 0.0
    # mem-mode wire format: "bf16" streams full-precision-ish leaves and
    # int8-serving servers re-quantize on apply; "q8" pre-quantizes the
    # dense projection leaves client-side (same per-out-channel transform
    # the server would run) — half the wire bytes AND no bf16-then-
    # requantize double rounding. Requires servers running
    # ServerConfig.quantization="int8".
    wire_format: str = "bf16"

    @classmethod
    def new_disk_update(cls, path: str) -> "WeightUpdateMeta":
        return cls(type="disk", path=path)


@dataclasses.dataclass
class SaveLoadMeta:
    path: str
    weight_format: str = "hf"  # "hf" (safetensors export) | "orbax" (sharded)
    with_optim: bool = False
    tokenizer: Any | None = None
    base_model_path: str | None = None


@dataclasses.dataclass
class FinetuneSpec:
    total_train_epochs: int
    dataset_size: int
    train_batch_size: int

    @property
    def steps_per_epoch(self) -> int:
        return max(1, self.dataset_size // self.train_batch_size)

    @property
    def total_train_steps(self) -> int:
        return self.total_train_epochs * self.steps_per_epoch


@dataclasses.dataclass
class StepInfo:
    epoch: int = 0
    epoch_step: int = 0
    global_step: int = 0
    steps_per_epoch: int = 0

    def next(self) -> "StepInfo":
        ep, es = self.epoch, self.epoch_step + 1
        if self.steps_per_epoch and es >= self.steps_per_epoch:
            ep, es = ep + 1, 0
        return StepInfo(
            epoch=ep,
            epoch_step=es,
            global_step=self.global_step + 1,
            steps_per_epoch=self.steps_per_epoch,
        )


@dataclasses.dataclass
class RolloutStat:
    submitted: int = 0
    accepted: int = 0
    running: int = 0
    rejected: int = 0


@dataclasses.dataclass
class ParamSpec:
    name: str
    shape: tuple[int, ...]
    dtype: str


@dataclasses.dataclass
class TimedResult:
    """Payload + timing wrapper from the async task runner (reference
    infra/async_task_runner.py TimedResult)."""

    data: Any
    task_id: str
    create_time: float = dataclasses.field(default_factory=time.monotonic)
