"""Hardware utilization & memory accounting: MFU, tok/s/chip, HBM ledger.

"Scalable Training of Language Models using JAX pjit and TPUv4" (PAPERS.md)
makes MFU the headline efficiency metric; this module supplies the two
inputs the trainer needs to report it as a standing number: the step's
model-FLOP content (from model dims — no profiler required) and the chip's
peak spec (from ``jax.devices()`` device_kind, overridable via
``TelemetryConfig.chip_peak_tflops`` for chips the table doesn't know).

It also builds the HBM ledger: an itemized account of where device memory
goes (params, optimizer state, KV page pool, radix cache, staged-update
buffers) against the device's reported limit
(``jax.local_devices()[i].memory_stats()`` where the backend supports it,
analytic byte-sums as the CPU fallback) with an OOM-headroom fraction.

Formulas (documented in docs/observability.md "Trainer observatory"):

- matmul params M = non-embedding params + the lm-head matmul (the input
  embedding is a lookup, not a matmul; the head multiplies even when tied)
- forward = 2·M FLOPs/token, backward = 4·M; gradient checkpointing adds
  one recomputed forward (+2·M); each extra no-grad forward pass in the
  step (logprob recompute, ref logprobs, critic values) adds 2·M
- MFU = step FLOPs / (window seconds × peak FLOPs/s × chips). The
  recorder reports it over the compute window (hardware efficiency) and
  over the full step (end-to-end utilization; the gap IS the bubble).
"""

from __future__ import annotations

from typing import Any

import numpy as np

# bf16 dense peak FLOPs/s and HBM bytes per chip, keyed by a lowercase
# substring of jax's device_kind. Order matters: first match wins, so the
# more specific generations sit above the bare-version fallbacks.
CHIP_SPECS: tuple[tuple[str, float, float], ...] = (
    ("v6e", 918e12, 32e9),
    ("v6 lite", 918e12, 32e9),
    ("v5p", 459e12, 95e9),
    ("v5e", 197e12, 16e9),
    ("v5 lite", 197e12, 16e9),
    ("v4", 275e12, 32e9),
    ("v3", 123e12, 32e9),
    ("v2", 46e12, 16e9),
)

# peak HBM bandwidth (bytes/s) per chip, same key scheme + match order as
# CHIP_SPECS
CHIP_MEMBW: tuple[tuple[str, float], ...] = (
    ("v6e", 1640e9),
    ("v6 lite", 1640e9),
    ("v5p", 2765e9),
    ("v5e", 819e9),
    ("v5 lite", 819e9),
    ("v4", 1228e9),
    ("v3", 900e9),
    ("v2", 700e9),
)


def chip_peak_flops(
    device: Any | None = None, override_tflops: float | None = None
) -> float | None:
    """Peak bf16 FLOPs/s of one chip. ``override_tflops`` (TelemetryConfig
    knob, in TFLOPs) wins; unknown kinds (CPU, future TPUs) return None —
    MFU is then simply not reported rather than fabricated."""
    if override_tflops is not None and override_tflops > 0:
        return float(override_tflops) * 1e12
    kind = _device_kind(device)
    if kind is None:
        return None
    for sub, flops, _hbm in CHIP_SPECS:
        if sub in kind:
            return flops
    return None


def chip_hbm_bytes(
    device: Any | None = None, override_gb: float | None = None
) -> float | None:
    """Per-chip HBM capacity; analytic-ledger denominator when the backend
    has no ``memory_stats()`` (CPU) and no override is configured."""
    if override_gb is not None and override_gb > 0:
        return float(override_gb) * 1e9
    kind = _device_kind(device)
    if kind is None:
        return None
    for sub, _flops, hbm in CHIP_SPECS:
        if sub in kind:
            return hbm
    return None


def chip_peak_membw(
    device: Any | None = None, override_gbps: float | None = None
) -> float | None:
    """Peak HBM bandwidth (bytes/s) of one chip. Unknown kinds return None
    rather than an invented bandwidth."""
    if override_gbps is not None and override_gbps > 0:
        return float(override_gbps) * 1e9
    kind = _device_kind(device)
    if kind is None:
        return None
    for sub, bw in CHIP_MEMBW:
        if sub in kind:
            return bw
    return None


def resolve_chip_peaks(
    device: Any | None = None,
) -> tuple[float | None, float | None, str]:
    """(peak FLOPs/s, peak bytes/s, source) of one chip. A TPU must resolve
    from the table above — an unknown TPU ``device_kind`` is an error, never
    a default and never a measured stand-in. A backend that is not a TPU has
    no peak to take a share of: ``(None, None, "none")``."""
    if device is None:
        import jax

        device = jax.local_devices()[0]
    flops, membw = chip_peak_flops(device), chip_peak_membw(device)
    if flops is not None and membw is not None:
        return flops, membw, "spec"
    if getattr(device, "platform", None) == "tpu":
        raise ValueError(
            f"unknown TPU device_kind {getattr(device, 'device_kind', None)!r}: "
            "add its published peaks to CHIP_SPECS / CHIP_MEMBW "
            "(observability/hw_accounting.py)"
        )
    return None, None, "none"


def _device_kind(device: Any | None) -> str | None:
    if device is None:
        import jax

        try:
            device = jax.local_devices()[0]
        except Exception:  # noqa: BLE001 — no backend yet: no spec
            return None
    kind = getattr(device, "device_kind", None)
    return kind.lower() if isinstance(kind, str) else None


# ---------------------------------------------------------------------------
# model-FLOP accounting from dims
# ---------------------------------------------------------------------------


def transformer_param_counts(mcfg) -> dict[str, int]:
    """Parameter counts from model dims (models/qwen.py ModelConfig):
    ``total``, ``embedding`` (input lookup table(s)), and ``matmul`` —
    the parameters that multiply per token (non-embedding + the lm head,
    which runs as a matmul even when weight-tied)."""
    h = mcfg.hidden_size
    L = mcfg.num_layers
    q_dim = mcfg.num_heads * mcfg.head_dim_
    kv_dim = mcfg.num_kv_heads * mcfg.head_dim_
    attn = h * q_dim + 2 * h * kv_dim + q_dim * h
    if getattr(mcfg, "num_experts", 0) > 0:
        inter = mcfg.moe_intermediate_size or mcfg.intermediate_size
        mlp = mcfg.num_experts * 3 * h * inter + h * mcfg.num_experts
        # per-token matmul work routes through top-k experts only
        mlp_active = mcfg.num_experts_per_tok * 3 * h * inter + h * mcfg.num_experts
    else:
        mlp = mlp_active = 3 * h * mcfg.intermediate_size
    norms = (2 * L + 1) * h
    embed = mcfg.vocab_size * h
    head = embed  # the lm-head matmul (shares the table when tied)
    total = L * (attn + mlp) + norms + embed
    if not mcfg.tie_word_embeddings:
        total += head
    matmul = L * (attn + mlp_active) + head
    return {"total": total, "embedding": embed, "matmul": matmul}


def train_step_flops(
    mcfg,
    n_tokens: float,
    n_extra_forwards: int = 0,
    remat: bool = False,
) -> float:
    """Model FLOPs of one optimizer step over ``n_tokens``: fwd (2M) + bwd
    (4M) [+ remat recompute 2M] + 2M per extra no-grad forward pass."""
    m = transformer_param_counts(mcfg)["matmul"]
    per_tok = (6 + (2 if remat else 0) + 2 * max(0, n_extra_forwards)) * m
    return float(per_tok) * float(n_tokens)


# ---------------------------------------------------------------------------
# HBM ledger
# ---------------------------------------------------------------------------


def step_transient_bytes(
    params_bytes: int, opt_state_bytes: int, donate: bool
) -> int:
    """Analytic peak of the optimizer step's *extra* HBM beyond the
    standing params/opt_state: one grads tree (params-sized) always; a
    donating step writes the updated params/opt_state into the donated
    input buffers, while an un-donated step holds BOTH generations live
    until the outputs materialize — the classic donate-or-double
    footgun arealint's DON family lints for."""
    transient = params_bytes  # grads
    if not donate:
        transient += params_bytes + opt_state_bytes
    return int(transient)


def tree_bytes(tree) -> int:
    """Total buffer bytes of a pytree of jax/numpy arrays (0 for None)."""
    if tree is None:
        return 0
    import jax

    total = 0
    for leaf in jax.tree.leaves(tree):
        nbytes = getattr(leaf, "nbytes", None)
        if nbytes is None and np.isscalar(leaf):
            nbytes = np.asarray(leaf).nbytes
        total += int(nbytes or 0)
    return total


def device_memory_stats(device: Any | None = None) -> dict | None:
    """The backend's own memory view (``bytes_in_use``/``bytes_limit``
    where available — TPU/GPU); None on CPU and older runtimes, which
    switches the ledger to the analytic fallback."""
    if device is None:
        import jax

        try:
            device = jax.local_devices()[0]
        except Exception:  # noqa: BLE001 — no backend: analytic ledger
            return None
    try:
        stats = device.memory_stats()
    except Exception:  # noqa: BLE001 — backend without the API
        return None
    if not stats or "bytes_in_use" not in stats:
        return None
    return dict(stats)


def build_hbm_ledger(
    components: dict[str, int],
    device: Any | None = None,
    override_hbm_gb: float | None = None,
    exclude_from_total: tuple[str, ...] = (),
) -> dict[str, Any]:
    """Itemized HBM account. ``components`` maps name -> bytes;
    ``exclude_from_total`` names entries that are *views into* another
    entry (the radix cache owns pages inside the KV pool) so the itemized
    total never double counts. Device-reported in_use/limit win when the
    backend exposes them; otherwise the ledger is analytic: in_use = the
    itemized sum, limit = the chip spec (or override) when known."""
    itemized = sum(
        v for k, v in components.items() if k not in exclude_from_total
    )
    ms = device_memory_stats(device)
    if ms is not None:
        in_use = int(ms["bytes_in_use"])
        limit = int(ms.get("bytes_limit") or 0) or None
        source = "device"
    else:
        in_use = itemized
        cap = chip_hbm_bytes(device, override_gb=override_hbm_gb)
        limit = int(cap) if cap else None
        source = "analytic"
    headroom = (
        max(0.0, 1.0 - in_use / limit) if limit else None
    )
    return {
        "components": dict(components),
        "itemized_bytes": itemized,
        "bytes_in_use": in_use,
        "bytes_limit": limit,
        "headroom_fraction": headroom,
        "source": source,
    }


def observe_hbm_ledger(ledger: dict[str, Any], obs=None) -> None:
    """Export one ledger onto the catalogued gauges (``areal_hbm_bytes``
    by component + the OOM-headroom fraction when the limit is known)."""
    if obs is None:
        from areal_tpu.observability import catalog as obs_catalog

        obs = obs_catalog.train_obs_metrics()
    for name, nbytes in ledger["components"].items():
        obs.hbm_bytes.labels(component=name).set(float(nbytes))
    obs.hbm_bytes.labels(component="in_use").set(float(ledger["bytes_in_use"]))
    if ledger["bytes_limit"]:
        obs.hbm_bytes.labels(component="limit").set(float(ledger["bytes_limit"]))
    if ledger["headroom_fraction"] is not None:
        obs.hbm_headroom.set(float(ledger["headroom_fraction"]))
