from areal_tpu.models.qwen import (  # noqa: F401
    ModelConfig,
    init_params,
    forward,
    compute_logits,
    chunked_logprobs_entropy,
    param_partition_specs,
)


def family_of(model_cfg):
    """The module that implements ``model_cfg``'s model family: the one place
    where the serving stack picks between them (``models/qwen.py``: the
    Qwen2/3 and llama decoders and ``sdar_moe``, which generates by blocks; ``models/hybrid.py``: ``granitemoehybrid``,
    ``lfm2_moe``, ``olmo_hybrid``, ``deepseek_v3``, ``glm_moe_dsa`` and ``phi4flash``). Both have the entry points
    the decode engine calls (``param_partition_specs``, ``hf_name_map``,
    ``prefill_into_cache``, ``prefill_row_bytes``, ``forward_prefill_paged``,
    ``forward_decode_paged``, ``forward_verify_paged``, ``compute_logits``,
    ``quantize_params_int8``) and say what of them they do not implement for
    a model (``serving_limits``)."""
    from areal_tpu.models import hybrid, qwen

    return hybrid if isinstance(model_cfg, hybrid.HybridConfig) else qwen


def config_from_hf_dict(d: dict):
    """The model configuration of a published ``config.json``, of whichever
    family implements its ``model_type`` (an error if none does)."""
    from areal_tpu.models import hybrid, qwen

    if d.get("model_type") in hybrid.MODEL_TYPES:
        return hybrid.HybridConfig.from_hf_dict(d)
    return qwen.ModelConfig.from_hf_dict(d)


def config_from_hf_path(path: str):
    import json
    import os

    with open(os.path.join(path, "config.json")) as f:
        return config_from_hf_dict(json.load(f))
