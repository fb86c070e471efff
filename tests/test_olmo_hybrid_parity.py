"""The ``olmo_hybrid`` family against published implementations of its parts
(the installed transformers 4.57.6 has no ``olmo_hybrid``, so no whole
checkpoint can be compared): the delta rule of the benchmark's reference
(token by token) and of the program (chunked, and the decode step) against
``torch_recurrent_gated_delta_rule`` and ``torch_chunk_gated_delta_rule`` of
``transformers``' ``qwen3_next``, on seeded inputs in float32; and the block
(an RMSNorm on each sublayer's OUTPUT, q/k norm over the whole projection, no
rotation) against ``Olmo3DecoderLayer``, once whole with an identity rotary
embedding and once with the mixer stubbed.

Tolerances: float32 on both sides. Outputs of order 0.1-1: 2e-5 against the
torch recurrence (measured 2e-7 to 2e-6), 1e-4 against torch's chunked form,
which inverts its triangular system in another order. A read after the write,
beta on the wrong side or a missing 1/sqrt(K) moves them by 1e-2 and more."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))
import chipbench_olmo_util as ou  # noqa: E402
from chipbench_util import load_run  # noqa: E402

B, T, H, K, V = 2, 150, 4, 24, 64


@pytest.fixture(scope="module")
def torch_rules():
    torch = pytest.importorskip("torch")
    nxt = pytest.importorskip("transformers.models.qwen3_next.modeling_qwen3_next")
    rng = np.random.default_rng(0)
    x = {
        "q": rng.normal(0, 1, (B, T, H, K)), "k": rng.normal(0, 1, (B, T, H, K)), "v": rng.normal(0, 1, (B, T, H, V)),
        "g": -rng.uniform(0, 1, (B, T, H)) * np.array([1e-3, 0.05, 0.5, 4.0]), "beta": 2 * rng.uniform(0, 1, (B, T, H)),
    }
    x = {k: v.astype(np.float32) for k, v in x.items()}
    t = {k: torch.tensor(v) for k, v in x.items()}
    with torch.no_grad():
        o_rec, s_rec = nxt.torch_recurrent_gated_delta_rule(t["q"], t["k"], t["v"], t["g"], t["beta"], None, True, use_qk_l2norm_in_kernel=True)
        o_chk, s_chk = nxt.torch_chunk_gated_delta_rule(t["q"], t["k"], t["v"], t["g"], t["beta"], 64, None, True, use_qk_l2norm_in_kernel=True)
    return x, (o_rec.numpy(), s_rec.numpy()), (o_chk.numpy(), s_chk.numpy())


def _normed(x):
    import jax
    import jax.numpy as jnp

    unit = lambda t: t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    return unit(jnp.asarray(x["q"])) * K**-0.5, unit(jnp.asarray(x["k"]))


def test_references_recurrence_is_the_torch_recurrence(torch_rules):
    load_run()
    import jax.numpy as jnp
    from benchlib import olmo_hybrid_reference as ref

    x, (o_rec, s_rec), _ = torch_rules
    q, k = _normed(x)
    for b in range(B):
        s, o = ref.delta_rule(q[b], k[b], jnp.asarray(x["v"][b]), jnp.asarray(x["g"][b]), jnp.asarray(x["beta"][b]))
        np.testing.assert_allclose(np.asarray(o), o_rec[b], atol=2e-5, rtol=0)
        np.testing.assert_allclose(np.asarray(s), s_rec[b], atol=2e-5, rtol=0)  # [H, K, V] on both sides
    assert np.abs(o_rec).max() > 0.3


def test_programs_scan_and_step_are_the_torch_rules(torch_rules):
    import jax.numpy as jnp

    from areal_tpu.models import hybrid

    x, (o_rec, s_rec), (o_chk, s_chk) = torch_rules
    q, k = _normed(x)
    v, g, beta = (jnp.asarray(x[n]) for n in ("v", "g", "beta"))
    s, o = hybrid.gdn_chunked_scan(q, k, v, g, beta, jnp.full((B,), T, jnp.int32))
    np.testing.assert_allclose(np.asarray(o), o_rec, atol=2e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(s), s_rec, atol=2e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(o), o_chk, atol=1e-4, rtol=0)
    np.testing.assert_allclose(np.asarray(s), s_chk, atol=1e-4, rtol=0)
    state = jnp.zeros((B, H, K, V))
    for t in range(40):
        state, o_t = hybrid.gdn_decode_step(state, q[:, t], k[:, t], v[:, t], jnp.exp(g[:, t]), beta[:, t], jnp.ones((B,), bool))
        np.testing.assert_allclose(np.asarray(o_t), o_rec[:, t], atol=2e-5, rtol=0)


def _olmo3_layer(torch, d=64, heads=2, f=96):
    from transformers.models.olmo3 import configuration_olmo3, modeling_olmo3

    cfg = configuration_olmo3.Olmo3Config(
        vocab_size=64, hidden_size=d, intermediate_size=f, num_hidden_layers=1, num_attention_heads=heads, num_key_value_heads=heads,
        rms_norm_eps=1e-6, attention_bias=False, layer_types=["full_attention"], attn_implementation="eager",
    )
    torch.manual_seed(0)
    layer = modeling_olmo3.Olmo3DecoderLayer(cfg, 0).eval().to(torch.float32)
    with torch.no_grad():  # norms start at 1: move them, or a norm in the wrong place would not show
        for name, p in layer.named_parameters():
            if "norm" in name:
                p.add_(0.2 * torch.randn_like(p))
    return cfg, layer


def _our_attention_layer(layer, d, heads):
    """The torch layer's weights as one layer of our ``attention`` stack."""
    import jax.numpy as jnp

    w = {n: jnp.asarray(p.detach().numpy()) for n, p in layer.named_parameters()}
    names = {
        "input_norm": "post_attention_layernorm.weight", "post_norm": "post_feedforward_layernorm.weight",
        "w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight", "w_down": "mlp.down_proj.weight",
        "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight", "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight",
        "q_norm": "self_attn.q_norm.weight", "k_norm": "self_attn.k_norm.weight",
    }
    return {ours: (w[theirs].T if w[theirs].ndim == 2 else w[theirs])[None] for ours, theirs in names.items()}


def test_attention_block_is_olmo3s_with_an_identity_rotary_embedding():
    """``Olmo3DecoderLayer`` whole, its rotary embedding handed cos = 1 and
    sin = 0 (no rotation: the assumption for a null ``rope_theta``), against
    one attention layer of the program and of the reference: the norms'
    places, the q/k norm over the whole projection, the softmax scale."""
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers.models.olmo3.modeling_olmo3")
    load_run()
    import jax.numpy as jnp
    from benchlib import olmo_hybrid_reference as ref

    from areal_tpu.models import hybrid

    d, heads, n = 64, 2, 19
    _, layer = _olmo3_layer(torch, d, heads)
    x = np.random.default_rng(1).normal(0, 1, (1, n, d)).astype(np.float32)
    causal = torch.full((n, n), float("-inf")).triu(1)[None, None]
    with torch.no_grad():
        want = layer(torch.tensor(x), attention_mask=causal, position_embeddings=(torch.ones(1, n, d // heads), torch.zeros(1, n, d // heads))).numpy()
    ours = _our_attention_layer(layer, d, heads)
    lp = {k: v[0] for k, v in ours.items()}
    got_ref = ref._attention_layer(jnp.asarray(x[0]), lp, heads=heads, kv_heads=heads, hd=d // heads, eps=1e-6)
    np.testing.assert_allclose(np.asarray(got_ref), want[0], atol=2e-5, rtol=0)
    mcfg = hybrid.HybridConfig(
        vocab_size=64, hidden_size=d, intermediate_size=96, layer_types=("attention",), num_heads=heads, num_kv_heads=heads,
        rms_norm_eps=1e-6, tie_word_embeddings=False, dtype="float32", model_type="olmo_hybrid", qk_norm=True, qk_norm_over="whole",
        norm_placement="post", fused_gate_up=False, kv_lane_pad=1,
    )
    # the embedding is the input itself (identity rows), the final norm the identity's RMSNorm taken back below
    params = {"embed": jnp.asarray(x[0]), "final_norm": jnp.ones((d,)), "lm_head": jnp.zeros((1, d)), "attention": ours}
    hidden, *_ = hybrid.forward_prefill(params, mcfg, jnp.arange(n)[None], jnp.ones((1, n), jnp.int32))
    unnormed = want[0] / np.sqrt((want[0] ** 2).mean(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.asarray(hidden[0]), unnormed, atol=2e-5, rtol=0)


def test_block_norm_order_with_the_mixer_stubbed(monkeypatch):
    """``Olmo3DecoderLayer`` with its mixer replaced by a fixed linear map,
    against a delta-rule layer of the program whose mixer is the same map:
    ``h = x + rmsnorm(mixer(x))``, ``out = h + rmsnorm(mlp(h))``."""
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers.models.olmo3.modeling_olmo3")
    import jax.numpy as jnp

    from areal_tpu.models import hybrid

    d, n = 64, 11
    _, layer = _olmo3_layer(torch, d, 2)
    rng = np.random.default_rng(2)
    mix = rng.normal(0, 0.3, (d, d)).astype(np.float32)

    class Stub(torch.nn.Module):
        def forward(self, hidden_states, **_):
            return hidden_states @ torch.tensor(mix), None

    layer.self_attn = Stub()
    x = rng.normal(0, 1, (1, n, d)).astype(np.float32)
    with torch.no_grad():
        want = layer(torch.tensor(x), position_embeddings=None).numpy()[0]
    mcfg = ou.model_config({**ou.tiny_model(("linear_attention",)), "hidden_size": d, "intermediate_size": 96})
    monkeypatch.setattr(hybrid, "gdn_prefill", lambda cfg, lyr, h, n_state, dtypes: (h @ jnp.asarray(mix), *(jnp.zeros((1, *s[2:]), t) for s, t in cfg.state_shapes(1).values())))
    w = {n_: jnp.asarray(p.detach().numpy()) for n_, p in layer.named_parameters()}
    stack = {
        "input_norm": w["post_attention_layernorm.weight"][None], "post_norm": w["post_feedforward_layernorm.weight"][None],
        "w_gate": w["mlp.gate_proj.weight"].T[None], "w_up": w["mlp.up_proj.weight"].T[None], "w_down": w["mlp.down_proj.weight"].T[None],
    }
    params = {"embed": jnp.asarray(x[0]), "final_norm": jnp.ones((d,)), "gdn": stack}
    hidden, *_ = hybrid.forward_prefill(params, mcfg, jnp.arange(n)[None], jnp.ones((1, n), jnp.int32))
    np.testing.assert_allclose(np.asarray(hidden[0]), want / np.sqrt((want**2).mean(-1, keepdims=True) + 1e-6), atol=2e-5, rtol=0)
