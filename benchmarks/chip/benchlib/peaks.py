"""Peaks of the chips the benchmark knows, and the operations and bytes an
algorithm needs, computed from shapes.

Source of the peaks: Google Cloud documentation, "TPU v5e" (per chip:
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s). Keyed by the
``device_kind`` JAX reports. A device that is not in the table is an error,
not a default.

The op and byte counts are those the mathematics needs (a matmul of
[m,k]x[k,n] is 2mkn operations; every weight and every live KV byte is read
once). Recomputation under remat is never counted.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "ops_int8": 393e12, "hbm_bytes_s": 819e9, "hbm_bytes": 16e9},
    "TPU v5e": {"flops_bf16": 197e12, "ops_int8": 393e12, "hbm_bytes_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks known for device kind {device_kind!r}; add it with its source")
    return PEAKS[device_kind]


def dims(cfg: dict) -> dict:
    hd = int(cfg.get("head_dim") or cfg["assumed"]["head_dim"])
    return {
        "D": int(cfg["hidden_size"]),
        "F": int(cfg["intermediate_size"]),
        "L": int(cfg["num_hidden_layers"]),
        "H": int(cfg["num_attention_heads"]),
        "KH": int(cfg["num_key_value_heads"]),
        "hd": hd,
        "V": int(cfg["vocab_size"]),
        "tied": bool(cfg["tie_word_embeddings"]),
    }


def matmul_params(cfg: dict) -> dict:
    """Weights that every token multiplies: per layer and the output head."""
    d = dims(cfg)
    q, kv = d["H"] * d["hd"], d["KH"] * d["hd"]
    layer = d["D"] * q + 2 * d["D"] * kv + q * d["D"] + 3 * d["D"] * d["F"]
    return {"layer": layer, "layers": layer * d["L"], "head": d["V"] * d["D"]}


def weight_bytes(cfg: dict, bytes_per: int = 2) -> int:
    """Bytes of every parameter (embedding counted once more if untied)."""
    d = dims(cfg)
    q, kv = d["H"] * d["hd"], d["KH"] * d["hd"]
    mp = matmul_params(cfg)
    small = d["L"] * (2 * d["D"] + q + 2 * kv) + d["D"]
    emb = d["V"] * d["D"] * (1 if d["tied"] else 2)
    return (mp["layers"] + small + emb) * bytes_per


def kv_bytes_per_token(cfg: dict, bytes_per: int = 2) -> int:
    d = dims(cfg)
    return 2 * d["L"] * d["KH"] * d["hd"] * bytes_per


def decode_attention_cost(cfg: dict, live_kv_tokens: float, kv_bytes_per: int = 2) -> tuple[float, float]:
    """(operations, bytes) of paged decode attention over all layers for ONE
    decode step whose active slots hold ``live_kv_tokens`` cached tokens in
    total: QK^T and PV are 2*hd operations each per (query head, cached
    token); K and V of every cached token are read once. Query and output
    vectors are left out (under 1% at these contexts)."""
    d = dims(cfg)
    ops = 4.0 * d["hd"] * d["H"] * d["L"] * live_kv_tokens
    byts = float(kv_bytes_per_token(cfg, kv_bytes_per)) * live_kv_tokens
    return ops, byts


def attention_ops_causal(cfg: dict, seq_lens) -> float:
    """Forward operations of causal self-attention over whole sequences:
    4*hd per (query head, visible key), n(n+1)/2 visible pairs a sequence."""
    d = dims(cfg)
    pairs = sum(n * (n + 1) / 2.0 for n in seq_lens)
    return 4.0 * d["hd"] * d["H"] * d["L"] * pairs


def prefill_cost(cfg: dict, seq_lens, bytes_per: int = 2) -> tuple[float, float]:
    """(operations, bytes) of one batched prefill of whole prompts: every
    token through every layer's matrices (no output head: the engine samples
    only the last position), plus causal attention; weights read once, the
    prompts' KV written once."""
    mp = matmul_params(cfg)
    n = float(sum(seq_lens))
    ops = 2.0 * mp["layers"] * n + attention_ops_causal(cfg, seq_lens)
    byts = mp["layers"] * bytes_per + kv_bytes_per_token(cfg, bytes_per) * n
    return ops, byts


def train_step_flops(cfg: dict, seq_lens) -> float:
    """Forward + backward operations of one training step over packed
    sequences, without recomputation: 6 per matrix weight per token (layers
    and the output head, whose logits every token needs for its logprob),
    and three times the causal attention forward."""
    mp = matmul_params(cfg)
    n = float(sum(seq_lens))
    return 6.0 * (mp["layers"] + mp["head"]) * n + 3.0 * attention_ops_causal(cfg, seq_lens)


def roofline(ops: float, byts: float, seconds: float, peak: dict) -> dict:
    """Share of the roofline: least time the chip could take over the time
    taken, and which bound it is."""
    t_ops, t_bytes = ops / peak["flops_bf16"], byts / peak["hbm_bytes_s"]
    least = max(t_ops, t_bytes)
    return {
        "pct": 100.0 * least / seconds if seconds > 0 else None,
        "bound": "compute" if t_ops >= t_bytes else "memory",
        "least_s": least,
    }
