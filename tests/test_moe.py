"""MoE + expert parallelism (SURVEY §2.4 EP; reference archon/moe stack):
routing correctness, capacity semantics, EP-sharded forward on the virtual
mesh, and a training step through the engine."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import qwen
from areal_tpu.models.moe import moe_ffn
from jax import set_mesh

MOE_CFG = qwen.ModelConfig(
    vocab_size=256,
    hidden_size=32,
    intermediate_size=64,
    num_layers=2,
    num_heads=4,
    num_kv_heads=2,
    dtype="float32",
    tie_word_embeddings=True,
    attention_bias=False,
    num_experts=4,
    num_experts_per_tok=2,
    moe_intermediate_size=48,
    capacity_factor=2.0,
)


def test_moe_param_shapes_and_specs():
    params = qwen.init_params(jax.random.PRNGKey(0), MOE_CFG)
    L = params["layers"]
    assert L["w_router"].shape == (2, 32, 4)
    assert L["we_gate"].shape == (2, 4, 32, 48)
    assert L["we_down"].shape == (2, 4, 48, 32)
    assert "w_gate" not in L
    specs = qwen.param_partition_specs(MOE_CFG)
    assert specs["layers"]["we_gate"] == jax.sharding.PartitionSpec(
        None, "expert", "fsdp", "model"
    )


def test_moe_ffn_matches_manual_routing():
    """With capacity ample and top-1 routing, moe_ffn == picking each
    token's argmax expert FFN."""
    cfg = qwen.ModelConfig(
        **{
            **MOE_CFG.__dict__,
            "num_experts_per_tok": 1,
            "norm_topk_prob": True,
            "capacity_factor": 4.0,
        }
    )
    params = qwen.init_params(jax.random.PRNGKey(1), cfg)
    layer = jax.tree.map(lambda x: x[0], params["layers"])
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(0, 1, (2, 8, 32)), jnp.float32)
    out, aux = moe_ffn(h, layer, cfg)
    assert out.shape == h.shape and np.isfinite(float(aux))

    logits = np.asarray(h) @ np.asarray(layer["w_router"])
    choice = logits.argmax(-1)
    want = np.zeros_like(np.asarray(h))
    for g in range(2):
        for t in range(8):
            e = choice[g, t]
            x = np.asarray(h)[g, t]
            ggate = x @ np.asarray(layer["we_gate"])[e]
            up = x @ np.asarray(layer["we_up"])[e]
            silu = ggate / (1 + np.exp(-ggate)) * up
            want[g, t] = silu @ np.asarray(layer["we_down"])[e]
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-4, atol=2e-4)


def test_moe_capacity_drops_tokens():
    """Tokens over an expert's capacity get zero FFN output (residual-only),
    never garbage."""
    cfg = qwen.ModelConfig(
        **{
            **MOE_CFG.__dict__,
            "num_experts": 2,
            "num_experts_per_tok": 1,
            "capacity_factor": 0.25,  # tiny: most tokens dropped
            "moe_dropless": False,  # capacity semantics under test
        }
    )
    params = qwen.init_params(jax.random.PRNGKey(2), cfg)
    layer = jax.tree.map(lambda x: x[0], params["layers"])
    h = jnp.ones((1, 16, 32), jnp.float32)
    out, _ = moe_ffn(h, layer, cfg)
    assert np.isfinite(np.asarray(out)).all()
    # identical tokens route identically -> capacity C=max(K, 0.25*1*16/2)=2
    # per expert; the rest must be exactly zero
    nonzero_rows = (np.abs(np.asarray(out)[0]).sum(-1) > 1e-9).sum()
    assert nonzero_rows <= 4, nonzero_rows


def test_moe_forward_ep_sharded():
    """Full model forward with experts sharded over the mesh expert axis."""
    from areal_tpu.api.config import MeshConfig
    from areal_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.make_mesh(MeshConfig(data=1, fsdp=2, seq=1, model=2, expert=2))
    params = qwen.init_params(jax.random.PRNGKey(3), MOE_CFG)
    specs = qwen.param_partition_specs(MOE_CFG)
    shardings = mesh_lib.param_sharding(mesh, specs)
    params = jax.tree.map(
        lambda x, s: jax.device_put(x, s), params, shardings
    )
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 256, (2, 16)), jnp.int32)
    seg = jnp.ones_like(ids)
    pos = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (2, 16))
    with set_mesh(mesh):
        hidden, aux = jax.jit(
            lambda p, i, s, o: qwen.forward(p, MOE_CFG, i, s, o, with_aux=True)
        )(params, ids, seg, pos)
    assert hidden.shape == (2, 16, 32)
    assert np.isfinite(np.asarray(hidden)).all()
    assert np.isfinite(float(aux))


@pytest.mark.slow  # tier-1 budget: heaviest tests ride -m slow (PR 4)
def test_moe_train_step():
    """One GRPO-style train step on the MoE model through the engine,
    including the router aux loss."""
    from areal_tpu.api.config import (
        MeshConfig,
        MicroBatchSpec,
        OptimizerConfig,
        TrainEngineConfig,
    )
    from areal_tpu.api.io_struct import FinetuneSpec
    from areal_tpu.engine.train_engine import JaxTrainEngine
    from tpu_testing import random_batch

    cfg = TrainEngineConfig(
        init_from_scratch=True,
        dtype="float32",
        param_dtype="float32",
        mesh=MeshConfig(data=1, fsdp=2, seq=1, model=2, expert=2),
        optimizer=OptimizerConfig(lr=1e-2, lr_scheduler_type="constant"),
        mb_spec=MicroBatchSpec(max_tokens_per_mb=4096),
        bucket_step=64,
    )
    eng = JaxTrainEngine(cfg, model_config=MOE_CFG)
    eng.initialize(FinetuneSpec(1, 64, 8))

    def loss_fn(outputs, b):
        lm = (b["label_valid"] & (b["loss_mask"] > 0)).astype(jnp.float32)
        nll = -(outputs["logprobs"] * lm).sum() / jnp.maximum(lm.sum(), 1)
        loss = nll + 0.01 * outputs["moe_aux"]
        return loss, {"nll": jax.lax.stop_gradient(nll), "moe_aux": outputs["moe_aux"]}

    def weight_fn(d):
        return float((np.asarray(d["loss_mask"]) > 0).sum())

    batch = random_batch(seed=3, vocab=256)
    losses = [eng.train_batch(batch, loss_fn, weight_fn)["nll"] for _ in range(6)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


@pytest.mark.parametrize(
    "form,dtype,tol",
    [("dense", "float32", 1e-6), ("routed", "float32", 1e-6), ("dense", "bfloat16", 3e-5), ("routed", "bfloat16", 3e-5)],
)
def test_dropless_token_conservation(form, dtype, tol, monkeypatch):
    """Dropless dispatch computes EVERY routed (token, k) assignment even
    under routing imbalance that would overflow any capacity buffer —
    output equals an explicit per-token loop over the top-k experts
    (reference parity target: archon/moe token-shuffle kernels compute all
    assignments, kernels.py:1-228). In both forms of ``moe.expert_ffn``
    (32 rows take the dense one; the routed one is what a train step's
    shard runs), and in bfloat16, where the gate and up projections come
    out rounded to the rows' type. The loop is float64 on the same weights
    and rows; the outputs are up to 1e-3 in size (weights N(0, 0.02)) and
    read 4e-10 off in float32, 7e-6 in bfloat16: the tolerances are 1e-6
    and 3e-5 of absolute error."""
    from areal_tpu.models import moe

    if form == "routed":
        monkeypatch.setattr(moe, "DENSE_ROWS", 0)
    assert moe.takes_dense_form(32, 4) == (form == "dense")
    cfg = qwen.ModelConfig(
        **{**MOE_CFG.__dict__, "moe_dropless": True, "norm_topk_prob": True, "dtype": dtype}
    )
    params = qwen.init_params(jax.random.PRNGKey(3), cfg)
    layer = jax.tree.map(lambda x: x[0], params["layers"])
    rng = np.random.default_rng(3)
    # near-identical tokens -> all route to the same experts (max imbalance)
    base = rng.normal(0, 1, 32)
    h = jnp.asarray(
        base[None, None, :] + 0.01 * rng.normal(0, 1, (2, 16, 32)), cfg.jax_dtype
    )
    out, aux = moe_ffn(h, layer, cfg)
    assert np.isfinite(float(aux)) and out.dtype == cfg.jax_dtype

    # explicit per-token reference
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    hn = np.asarray(h, np.float64)
    logits = hn @ np.asarray(layer["w_router"], np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    want = np.zeros_like(hn)
    for g in range(hn.shape[0]):
        for t in range(hn.shape[1]):
            top = np.argsort(-p[g, t])[:K]
            gates = p[g, t][top]
            gates = gates / gates.sum()
            for e, gate in zip(top, gates):
                x = hn[g, t]
                gg = x @ np.asarray(layer["we_gate"][e], np.float64)
                up = x @ np.asarray(layer["we_up"][e], np.float64)
                y = (gg / (1 + np.exp(-gg))) * up
                want[g, t] += gate * (y @ np.asarray(layer["we_down"][e], np.float64))
    np.testing.assert_allclose(np.asarray(out, np.float64), want, rtol=0, atol=tol)
    assert np.abs(want).max() > 5e-4
    # and every token got nonzero expert output (nothing dropped)
    assert (np.abs(np.asarray(out, np.float64)).sum(-1) > 1e-7).all()


def test_dropless_ep_sharded_matches_single_device():
    """EP over an expert=2 mesh produces the same output as no mesh."""
    from areal_tpu.api.config import MeshConfig
    from areal_tpu.parallel import mesh as mesh_lib

    cfg = qwen.ModelConfig(**{**MOE_CFG.__dict__, "moe_dropless": True})
    params = qwen.init_params(jax.random.PRNGKey(4), cfg)
    layer = jax.tree.map(lambda x: x[0], params["layers"])
    rng = np.random.default_rng(4)
    h = jnp.asarray(rng.normal(0, 1, (2, 16, 32)), jnp.float32)
    ref, _ = moe_ffn(h, layer, cfg)

    mesh = mesh_lib.make_mesh(
        MeshConfig(data=-1, fsdp=1, seq=2, model=1, expert=2)
    )
    with set_mesh(mesh):
        out, aux = jax.jit(lambda h, l: moe_ffn(h, l, cfg))(h, layer)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5)


def test_moe_hf_roundtrip(tmp_path):
    """MoE checkpoints round-trip through the HF layout (qwen2/3_moe: one
    tensor per (layer, expert) + mlp.gate router; stacked [L, E, ...] here)
    and the written config.json reconstructs the MoE ModelConfig — so a
    from-scratch MoE export is a self-contained, loadable artifact."""
    import jax.numpy as jnp

    from areal_tpu.models.hf import load_params_from_hf, save_params_to_hf

    params = qwen.init_params(jax.random.PRNGKey(0), MOE_CFG)
    path = str(tmp_path / "hf")
    save_params_to_hf(params, MOE_CFG, path, base_model_path="")
    cfg2 = qwen.ModelConfig.from_hf_path(path)
    assert cfg2.num_experts == MOE_CFG.num_experts
    assert cfg2.num_experts_per_tok == MOE_CFG.num_experts_per_tok
    assert cfg2.moe_intermediate_size == MOE_CFG.moe_intermediate_size
    cfg2 = qwen.ModelConfig(**{**cfg2.__dict__, "dtype": "float32"})
    loaded, _ = load_params_from_hf(path, cfg2, dtype=jnp.float32)
    for k in ("w_router", "we_gate", "we_up", "we_down", "wq", "input_norm"):
        np.testing.assert_allclose(
            np.asarray(loaded["layers"][k]),
            np.asarray(params["layers"][k]),
            rtol=1e-6,
        )
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(1, 250, (1, 16)).astype(np.int32))
    seg = jnp.ones((1, 16), jnp.int32)
    pos = jnp.arange(16, dtype=jnp.int32)[None]
    h1, _ = qwen.forward(params, MOE_CFG, ids, seg, pos, with_aux=True)
    h2, _ = qwen.forward(loaded, cfg2, ids, seg, pos, with_aux=True)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-5, atol=1e-6)


@pytest.mark.slow  # tier-1 budget: heaviest tests ride -m slow (PR 4)
def test_moe_serving_greedy_parity():
    """The decode engine serves MoE models (prefill + paged decode run the
    dropless dispatch) and the greedy stream matches a teacher-forced full
    forward — the same parity bar the dense serving path is held to."""
    from areal_tpu.api.config import MeshConfig, ServerConfig
    from areal_tpu.api.io_struct import GenerationHyperparameters, ModelRequest
    from areal_tpu.inference.decode_engine import DecodeEngine

    params = qwen.init_params(jax.random.PRNGKey(1), MOE_CFG)
    eng = DecodeEngine(
        ServerConfig(
            max_batch_size=8,
            max_seq_len=64,
            decode_steps_per_call=4,
            seed=0,
            mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
        ),
        params=params,
        model_cfg=MOE_CFG,
    )
    eng.initialize()
    eng.start()
    try:
        rng = np.random.default_rng(0)
        prompt = rng.integers(1, 250, 8).tolist()
        ids = list(prompt)
        for _ in range(8):
            # pad to a gmm-tile-friendly length (T*K must divide the
            # interpret tile); segment 0 masks the pads out of attention
            L = len(ids)
            Lp = -(-L // 8) * 8
            a = np.zeros((1, Lp), np.int32)
            a[0, :L] = ids
            seg = np.zeros((1, Lp), np.int32)
            seg[0, :L] = 1
            pos = np.zeros((1, Lp), np.int32)
            pos[0, :L] = np.arange(L)
            h = qwen.forward(params, MOE_CFG, a, seg, pos, with_aux=True)[0]
            logits = qwen.compute_logits(params, MOE_CFG, h)
            ids.append(int(np.argmax(np.asarray(logits)[0, L - 1])))
        want = ids[len(prompt):]
        resp = eng.generate_sync(
            ModelRequest(
                input_ids=prompt,
                gconfig=GenerationHyperparameters(max_new_tokens=8, greedy=True),
            ),
            timeout=240,
        )
        assert resp.output_tokens == want, (resp.output_tokens, want)
    finally:
        eng.stop()
