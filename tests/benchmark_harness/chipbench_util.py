"""Shared by the benchmark-harness tests: loads ``benchmarks/chip/run.py``
under a name of its own and holds the tiny sizes of the CPU rehearsal (a
test-only override, never an option of the command).

Three tests run an engine (one traced rehearsal a cell kind, and the rollout
control), one the reference's training step twice; each sits in a file of
one or two tests, and no file here holds more than 8. xdist's ``loadfile``
scheduler hands out the files with most tests first: the heavy files run
last, when workers fall idle, and every seed file of 9 tests or more is
handed out exactly as without this directory, among them the suite's
timing-sensitive tests (``test_observability``'s telemetry race,
``test_scale_out``'s launcher race, ``test_weight_sync``'s pause timing)."""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmarks", "chip")


def load_run():
    """The harness's entry module (its import puts ``benchlib`` on sys.path)."""
    if "chipbench_run" not in sys.modules:
        spec = importlib.util.spec_from_file_location("chipbench_run", os.path.join(CHIP, "run.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules["chipbench_run"] = mod
        spec.loader.exec_module(mod)
    return sys.modules["chipbench_run"]


def bench():
    load_run()
    from benchlib import spec

    return spec.Bench(ROOT)


def tiny_model() -> dict:
    with open(os.path.join(ROOT, "examples", "smoke", "tiny_model", "config.json")) as f:
        cfg = json.load(f)
    cfg["assumed"] = {"head_dim": cfg["hidden_size"] // cfg["num_attention_heads"], "attention_bias": True}
    cfg["initializer_range"] = 0.02
    return cfg


TINY_MIX = {
    "generator": "grpo_groups",
    "group_size": 4,
    "shape_seed": 1,
    "prompt_len": {"dist": "loguniform", "lo": 8, "hi": 60},
    "output_len": {"dist": "lognormal", "median": 12, "sigma": 1.0, "lo": 2, "hi": 40},
    "max_total": 120,
    "first_wave_scale": "uniform01",
    "sampling": {"temperature": 1.0, "greedy": False, "ignore_eos": True},
}


# float32 on both sides: the trainer's step against the reference's
TRAIN_LIMITS = {"loss_abs": 1e-5, "grad_rel": 1e-3, "update_rel": 1e-2}


def rehearsal(kind: str, limit: float = 1e-5) -> dict:
    if kind == "rollout":
        return {
            "model": tiny_model(),
            "traffic": TINY_MIX,
            "params": {
                "dtype": "float32",
                "server": {"slots": 8, "max_seq_len": 128, "page_size": 16, "kv_hbm_gb": None, "decode_steps": 4, "attn_window_step": 128},
                "clients": 2,
                "warm_seconds": 0.2,
                "trace_seconds": 0.5,
                "ttft_grace_seconds": 0.3,
                "check": {"sample": 4, "limit_mean_abs_logprob": limit},
            },
        }
    return {
        "model": tiny_model(),
        "lengths_mix": TINY_MIX,
        "traffic": {"rows": 2, "row_len": 128, "min_len": 4},
        "params": {
            "dtype": "float32",
            "engine": {
                "lr": 1e-5, "lr_warmup_steps": 1, "weight_decay": 0.05, "beta1": 0.9, "beta2": 0.95, "eps": 1e-8,
                "gradient_clipping": 1.0, "max_tokens_per_mb": 100000, "bucket_step": 128, "logprob_chunk_size": 64,
            },
            "loss": {"eps_clip": 0.2, "behave_imp_weight_cap": 5.0},
            "warm_steps": 2,
            "trace_seconds": 0.5,
            "check": {
                "pad_multiple": 128,
                "keep": {"layers": [0, 1], "matrices": ["wq", "wo", "w_down"], "vectors": ["input_norm", "post_attn_norm", "bq", "bk", "bv"], "rows": 4096},
                "limits": TRAIN_LIMITS,
            },
        },
    }


CELL_KINDS = {"rollout-1.5b-grpo": "rollout", "rollout-7b-d14-grpo": "rollout", "train-1.5b-packed4k": "train"}


def rehearse(cell: str, trace: bool, tmp, control: bool = False) -> dict:
    """One rehearsal run of a cell through ``run.run_cell`` -> its result.
    ``tmp`` (the test's ``tmp_path``) holds the run's files."""
    reh = {**rehearsal(CELL_KINDS[cell]), "tmp": str(tmp)}
    return load_run().run_cell(bench(), cell, 2**31 + 17, 2.0, bool(trace), t0=time.monotonic(), rehearsal=reh, control=control)


def check_rehearsal(cell: str, trace: bool, res: dict) -> None:
    """What every rehearsal's result has to hold."""
    assert {"correct", "attempted", "failed", "metrics", "device", "rehearsal"} <= set(res)
    assert res["device"]["platform"] == "cpu"
    assert res["correct"] is True and res["attempted"] > 0 and res["failed"] == 0
    c = bench().cell(cell)
    # every end-to-end metric of the cell was read, whatever the result line carries
    assert all(res["rehearsal"]["values"][m["name"]] > 0 for m in c["end_to_end"])
    if not trace:
        assert set(res["metrics"]) == {m["name"] for m in c["end_to_end"]}
        assert "breakdown" not in res
    else:
        # device-trace metrics find no device plane on the CPU and are left out
        assert set(res["metrics"]) <= {m["name"] for m in c["per_layer"]}
        assert res["device"]["window_s"] > 0 and set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    json.loads(json.dumps(res))  # the result line serialises


def program_logprobs(cfg, params, ids):
    """log p(ids[t] | ids[:t]) from the program's own ``models/qwen.py`` forward in float32."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchlib import harness

    from areal_tpu.models import qwen

    mcfg = dataclasses.replace(harness.model_config(cfg, "float32"), attn_impl="xla")
    x = jnp.asarray(ids)[None]
    hidden = qwen.forward(params, mcfg, x, jnp.ones_like(x), jnp.arange(len(ids), dtype=jnp.int32)[None])
    lp = jax.nn.log_softmax(qwen.compute_logits(params, mcfg, hidden)[0], axis=-1)
    return np.asarray(lp[np.arange(len(ids) - 1), ids[1:]])
