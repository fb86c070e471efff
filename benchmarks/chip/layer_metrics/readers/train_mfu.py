"""Model FLOP/s utilization of training: forward+backward operations per
step without recomputation, times steps per second, over chips x peak.
An end-to-end utilization, never a kernel's roofline share."""
from benchlib import peaks


def read(metric: dict, facts: dict):
    rate = (facts.get("values") or {}).get("train_tok_s")
    if not rate:
        return None
    flops_step = peaks.train_step_flops(facts["cfg"], facts["seq_lens"])
    peak = peaks.peaks_for(facts["device_kind"])["flops_bf16"] * facts["chips"]
    return 100.0 * flops_step * (rate / facts["tokens_per_step"]) / peak
