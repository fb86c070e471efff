"""The ``solar_open2`` family of ``models/hybrid.py`` at a tiny size in
float32 (two periods G K K K; 8 query heads over 1 KV head of 16; 8 kda heads
of 16 x 16; the router scoring 16 experts, top-4, of which a share is held),
against the benchmark's plain reference, whose delta rule runs token by token
and whose attention is the full causal softmax. The engine (group copy,
preemption, the counter) is in tests/test_solar_open2_engine.py.

Tolerances: float32 on both sides over 8 layers, the program's chunked scan
against the reference's token loop: logits agree to 3e-4 of a largest logit
near 3 (measured 3e-5), a slot's state to 1e-5 of its norm (measured 1e-6).
A decay applied along the wrong axis of the state, a sub-block referred to
the wrong token, a gate's sigmoid taken for a SiLU or a read after the write
instead of before it moves the logits by 1e-2 and more; a bfloat16 state
stands 2e-3 off and must fail the state test."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))
import chipbench_solar_open2_util as su  # noqa: E402
from chipbench_util import CHIP, load_run  # noqa: E402

load_run()
from benchlib import solar_open2_reference as ref  # noqa: E402
from benchlib import solar_open2_weights  # noqa: E402

from areal_tpu import models  # noqa: E402
from areal_tpu.inference import paged_kv  # noqa: E402
from areal_tpu.models import hybrid  # noqa: E402
from tests.family_harness import program_logits, through_the_cache  # noqa: E402
from areal_tpu.ops.kda_state_update import kda_state_update_stacked  # noqa: E402
from areal_tpu.ops.paged_attention_q8 import live_order  # noqa: E402

PSZ = 16
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(((a - b) ** 2).sum() / (b * b).sum()))


def _recurrence_inputs(L=150, H=3, K=16, V=16, seed=0, strong=False):
    """q, k L2-normalised, keys in overlapping pairs, log decays log-uniform
    in 0.001-5 a token; ``strong``: -5 a token in four channels (-320 over a
    chunk: ``k exp(G)`` against ``k exp(-G)`` would overflow float32 past
    -88) and beta at 1.98 on every third token."""
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)  # noqa: E731
    q = unit(rng.normal(size=(L, H, K))).astype(np.float32) * K**-0.5
    k = rng.normal(size=(L, H, K))
    k[1::2] = k[0::2] + 0.1 * rng.normal(size=k[0::2].shape)
    k = unit(k).astype(np.float32)
    v = rng.normal(size=(L, H, V)).astype(np.float32)
    a = -np.exp(rng.uniform(np.log(1e-3), np.log(5.0), size=(L, H, K))).astype(np.float32)
    beta = (2.0 / (1.0 + np.exp(-2.0 * rng.normal(size=(L, H))))).astype(np.float32)
    if strong:
        a[:, :, :4] = -5.0
        beta[::3] = 1.98
    return q, k, v, a, beta


def _by_steps(q, k, v, a, beta, n_state):
    """``kda_decode_step`` a token at a time over one slot (a ``lax.scan`` of the step itself)."""

    def step(s, x):
        q_t, k_t, v_t, a_t, b_t, live = x
        s, o = hybrid.kda_decode_step(s, q_t[None], k_t[None], v_t[None], jnp.exp(a_t)[None], b_t[None], live[None])
        return s, o[0]

    s0 = jnp.zeros((1, *q.shape[1:], v.shape[-1]))
    s, outs = jax.jit(lambda *x: jax.lax.scan(step, s0, x))(q, k, v, a, beta, jnp.arange(q.shape[0]) < n_state)
    return np.asarray(s[0]), np.asarray(outs)


@pytest.mark.parametrize("strong", [False, True], ids=["published-decays", "log-decay--320-a-chunk"])
@pytest.mark.parametrize("n_state", [150, 97, 0])
def test_step_chunked_scan_and_reference_are_one_recurrence(strong, n_state):
    """``kda_decode_step`` token by token, ``kda_chunked_scan`` (two chunks
    and a part; ``n_state`` inside a chunk, and 0: the state stands still)
    and the reference's ``delta_rule``: one state, one output."""
    q, k, v, a, beta = _recurrence_inputs(strong=strong)
    s_step, o_step = _by_steps(q, k, v, a, beta, n_state)
    with jax.default_matmul_precision("highest"):
        s_scan, o_scan = jax.jit(hybrid.kda_chunked_scan)(q, k, v, a, beta, n_state)
    real = (np.arange(q.shape[0]) < n_state).astype(np.float32)
    s_ref, o_ref = ref.delta_rule(q, k, v, a * real[:, None, None], beta * real[:, None])
    assert np.isfinite(np.asarray(o_scan)).all()
    np.testing.assert_allclose(s_scan, s_step, atol=5e-6, rtol=0)
    np.testing.assert_allclose(s_ref, s_step, atol=5e-6, rtol=0)
    np.testing.assert_allclose(np.asarray(o_scan)[:n_state], o_step[:n_state], atol=5e-6, rtol=0)
    np.testing.assert_allclose(np.asarray(o_ref)[:n_state], o_step[:n_state], atol=5e-6, rtol=0)
    if n_state:
        assert np.abs(o_step[:n_state]).max() > 0.3 and np.abs(s_step).max() > 0.1
    else:
        assert not s_step.any()


def test_the_scan_carries_its_state_between_blocks():
    """A prompt in two blocks, the second from the first's state, is the
    prompt in one (``kda_prefill`` walks a long prompt so)."""
    q, k, v, a, beta = _recurrence_inputs(L=200, strong=True)
    whole_s, whole_o = hybrid.kda_chunked_scan(q, k, v, a, beta, 170)
    s, o1 = hybrid.kda_chunked_scan(q[:128], k[:128], v[:128], a[:128], beta[:128], 170)
    s, o2 = hybrid.kda_chunked_scan(q[128:], k[128:], v[128:], a[128:], beta[128:], 170 - 128, s)
    np.testing.assert_allclose(s, whole_s, atol=2e-6, rtol=0)
    np.testing.assert_allclose(np.concatenate([o1, o2])[:170], np.asarray(whole_o)[:170], atol=2e-6, rtol=0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_state_kernel_under_interpret_matches_the_step(dtype):
    """The Pallas launch over the live list, in place: live slots advance as
    ``kda_decode_step`` advances them, the others and the other layer keep
    their state bit for bit and read zeros."""
    q, k, v, a, beta = (t[:5] for t in _recurrence_inputs(L=6, strong=True))
    state = jnp.asarray(np.random.default_rng(1).normal(size=(2, 5, 3, 16, 16)), dtype)
    active = jnp.asarray([True, False, True, True, False])
    new, o = kda_state_update_stacked(state, 1, q, k, v, np.exp(a), beta, *live_order(active), interpret=True)
    want_s, want_o = hybrid.kda_decode_step(state[1], q, k, v, np.exp(a), beta, active)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(new[1], np.float32), np.asarray(want_s, np.float32), atol=tol, rtol=0)
    np.testing.assert_array_equal(np.asarray(new[0], np.float32), np.asarray(state[0], np.float32))
    np.testing.assert_array_equal(np.asarray(new[1, 1], np.float32), np.asarray(state[1, 1], np.float32))
    live = np.asarray(active)
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(want_o)[live], atol=tol, rtol=0)
    assert not np.asarray(o)[~live].any()
    jaxpr = str(jax.make_jaxpr(lambda *x: kda_state_update_stacked(*x, interpret=True))(state, 1, q, k, v, np.exp(a), beta, *live_order(active)))
    assert "pjit" not in jaxpr.split("pallas_call", 1)[1].split("name=kda_state_update")[0]  # lax primitives only in the body


@pytest.mark.parametrize("n", [5, 150])
def test_full_forward_matches_reference(n):
    cfg = su.tiny_model()
    params = su.make_params(cfg, 11)
    ids = np.random.default_rng(n).integers(0, cfg["vocab_size"], n)
    want = ref.logits(params, cfg, ids)
    assert np.abs(program_logits(su.model_config(cfg), params, ids) - want).max() < 3e-4 and np.abs(want).max() > 0.3


def test_prefill_then_paged_decode_and_the_slot_state_match_the_reference():
    """37 prompt tokens padded to a bucket of 64, then 30 decode steps through
    the cache: every step's LOGITS are the reference's full forward's; the
    slot's kda state after the prefill (``n_state``: the prompt less its
    last token, the padding masked out) and after the steps is the
    reference's first kda layer's (the model's layer 1) after exactly those
    tokens; the other slots' rows stay zero; a bfloat16 state fails."""
    cfg = su.tiny_model()
    mcfg, params = su.model_config(cfg), su.make_params(cfg, 3)
    assert mcfg.layer_types == ("attention", "kda", "kda", "kda") * 2 and set(mcfg.ffns) == {"moe"}
    ids = np.random.default_rng(1).integers(0, cfg["vocab_size"], 67)
    want = ref.logits(params, cfg, ids)
    got, cache = through_the_cache(mcfg, params, ids, 37, 64, page_size=PSZ)
    assert np.abs(got - want[36:]).max() < 3e-4
    assert cache["kda"].shape == (6, 3, 8, 16, 16) and cache["conv"].shape == (6, 3, 3 * 3 * 128)
    view = hybrid.slot_state_view(mcfg, "kda", cache["kda"][0])
    assert rel(view[1], ref.first_layer_state(params, cfg, ids, pad_to=256)) < 1e-5
    assert not np.asarray(cache["kda"][:, 0]).any() and not np.asarray(cache["kda"][:, 2]).any()
    _, cache0 = through_the_cache(mcfg, params, ids[:37], 37, 64, page_size=PSZ)  # one step: the prompt's last token
    assert rel(cache0["kda"][0, 1], ref.first_layer_state(params, cfg, ids[:37], pad_to=256)) < 1e-5
    low = su.model_config(cfg, kda_state_dtype="bfloat16")
    _, cache_low = through_the_cache(low, params, ids, 37, 64, page_size=PSZ)
    assert cache_low["kda"].dtype == jnp.bfloat16
    assert rel(cache_low["kda"][0, 1].astype(jnp.float32), ref.first_layer_state(params, cfg, ids, pad_to=256)) > 1e-3


def test_gated_gqa_prompt_pass_in_both_forms():
    """The attention mixer's prompt pass as XLA computes it ([H, L, L]
    logits a row) and under the flash launch (interpreted): the same
    attention, padding rows aside; the shape rule takes the launch where the
    logits would not fit and leaves every shorter prompt on XLA's form."""
    cfg = su.tiny_model()
    mcfg = su.model_config({**cfg, "head_dim": 128, "linear_attn_config": {**cfg["linear_attn_config"]}})
    rng = np.random.default_rng(2)
    A, L, H, KH, hd = 2, 256, mcfg.num_heads, mcfg.num_kv_heads, 128
    q, k, v = (jnp.asarray(rng.normal(size=(A, L, n, hd)), jnp.float32) for n in (H, KH, KH))
    seg = jnp.asarray(np.arange(L)[None] < np.asarray([256, 200])[:, None], jnp.int32)
    got = np.asarray(hybrid.gqa_flash_attend(mcfg, q, k, v, seg, interpret=True))
    G = H // KH
    logits = jnp.einsum("atkgd,askd->akgts", q.reshape(A, L, KH, G, hd), k) * hd**-0.5
    causal = (np.arange(L)[:, None] >= np.arange(L)[None, :])[None, None, None]
    probs = jax.nn.softmax(jnp.where(causal, logits, -1e30), axis=-1)
    want = np.asarray(jnp.einsum("akgts,askd->atkgd", probs, v).reshape(A, L, H * hd))
    np.testing.assert_allclose(got[0], want[0], atol=2e-5, rtol=0)
    np.testing.assert_allclose(got[1, :200], want[1, :200], atol=2e-5, rtol=0)
    with open(os.path.join(CHIP, "configs", su.CONFIG + ".json")) as f:
        full = su.model_config(json.load(f), dtype="bfloat16")
    assert not hybrid.gqa_prefill_launch(full, 16384)  # off a TPU the XLA form stays
    on_tpu = pytest.MonkeyPatch()
    on_tpu.setattr(jax, "default_backend", lambda: "tpu")
    try:
        assert [hybrid.gqa_prefill_launch(full, n) for n in (1024, 2048, 4096, 16384)] == [False, True, True, True]
        assert not hybrid.gqa_prefill_launch(su.model_config(cfg), 16384)  # heads of 16: not the kernel's shape
    finally:
        on_tpu.undo()
    # the gate itself, through the whole layer: the program's prompt pass against the reference's (test above) holds it;
    # here, that a long prompt's rows go through it in blocks to the same result
    layer = {"wg": jnp.asarray(rng.normal(size=(64, H * 16)) * 0.1, jnp.float32)}
    tiny = su.model_config(cfg)
    attn, h = jnp.asarray(rng.normal(size=(1, 4096, H * 16)), jnp.float32), jnp.asarray(rng.normal(size=(1, 4096, 64)), jnp.float32)
    want = attn * jax.nn.sigmoid(h @ layer["wg"])
    np.testing.assert_allclose(hybrid._attn_gated(tiny, layer, attn, h), want, atol=1e-5, rtol=0)


def test_the_shares_of_16_ranks_add_up_to_the_uncut_layer():
    """The tiny model's 16 experts over 4 ranks of 4 AND over 16 ranks of 1
    (the cell: 320 over 16 ranks of 20): an expert layer with
    rank r's experts (router and bias whole, the shared expert on every
    rank), summed over the ranks with the shared expert counted ONCE, is the
    uncut reference's layer. The program's share and the reference's."""
    whole = su.tiny_model(held=16, experts=16, periods=1)
    params = su.make_params(whole, 17)
    lp = {k: v[0] for k, v in params["kda_moe"].items()}
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(5), (37, 64), jnp.float32)
    d = ref.dims(whole)
    kw = dict(eps=d["eps"], top_k=d["K"], norm_topk=d["norm_topk"], scale=d["scale"])
    uncut = np.asarray(ref._expert_ffn(x, lp, e0=0, **kw)[0] - x)
    for ranks in (4, 16):
        per = 16 // ranks
        by_program, by_reference, shared = np.zeros_like(uncut), np.zeros_like(uncut), None
        for rank in range(ranks):
            cfg_r = ref.share_of(whole, rank, ranks)
            assert (cfg_r["n_routed_experts"], cfg_r["assumed"]["router_experts"], cfg_r["assumed"]["expert_first"]) == (per, 16, per * rank)
            lp_r = {k: (v[per * rank : per * (rank + 1)] if k.startswith("we_") else v) for k, v in lp.items()}
            routed = np.asarray(ref._expert_ffn(x, lp_r, e0=per * rank, shared=False, **kw)[0] - x)
            with_shared = np.asarray(ref._expert_ffn(x, lp_r, e0=per * rank, **kw)[0] - x)
            shared = with_shared - routed if shared is None else shared
            by_reference += routed
            mcfg = su.model_config(cfg_r)
            assert (mcfg.num_experts, mcfg.router_width, mcfg.expert_first) == (per, 16, per * rank)
            out, load = hybrid._ffn(mcfg, "moe", lp_r, x)
            by_program += np.asarray(out - x) - shared
            assert load.shape == (16,) and int(load.sum()) == 37 * 4
        assert np.abs(uncut - shared).max() > 0.01 and np.abs(shared).max() > 0.01  # both parts are there to be lost
        np.testing.assert_allclose(by_reference + shared, uncut, atol=3e-6, rtol=0)
        np.testing.assert_allclose(by_program + shared, uncut, atol=3e-6, rtol=0)


def _catalog_config():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        return next(r for r in map(json.loads, f) if r["name"] == "Solar-Open2-250B")["config"]


def test_published_configuration_round_trips_and_what_is_not_implemented_is_refused():
    pub = _catalog_config()
    mcfg = models.config_from_hf_dict(pub)
    assert isinstance(mcfg, hybrid.HybridConfig) and mcfg.model_type == "solar_open2" and models.family_of(mcfg) is hybrid
    assert mcfg.layer_types == ("attention", "kda", "kda", "kda") * 12 and mcfg.ffns == ("moe",) * 48
    assert (mcfg.num_heads, mcfg.num_kv_heads, mcfg.head_dim_, mcfg.rope_theta, mcfg.attn_gate, mcfg.qk_norm) == (64, 8, 128, None, True, False)
    assert (mcfg.kda_n_heads, mcfg.kda_k_dim, mcfg.kda_v_dim, mcfg.kda_d_conv, mcfg.kda_rank, mcfg.kda_neg_eigval) == (64, 128, 128, 4, 128, True)
    assert (mcfg.num_experts, mcfg.router_width, mcfg.num_experts_per_tok, mcfg.moe_intermediate_size, mcfg.moe_shared_intermediate_size) == (320, 320, 8, 1280, 1280)
    assert (mcfg.router_score, mcfg.router_bias, mcfg.norm_topk_prob, mcfg.routed_scaling_factor) == ("sigmoid", True, True, 1.0)
    back = mcfg.to_hf_dict()
    unread = {"rope_theta", "max_position_embeddings"}  # no layer reads them (use_rope false)
    assert {k: back[k] for k in pub if k not in unread} == {k: v for k, v in pub.items() if k not in unread}
    assert models.config_from_hf_dict(back) == mcfg
    assert mcfg.state_shapes(2)["kda"] == ((36, 2, 64, 128, 128), jnp.dtype("float32")) and mcfg.state_shapes(2)["conv"][0] == (36, 2, 3 * 24576)
    assert mcfg.count_shapes["kda_updates"] == (36,) and mcfg.has_recurrent_state and hybrid.serving_limits(mcfg)["reason"] == "recurrent_state"
    assert "kda" in paged_kv.STATE_LEAVES and "kda_updates" in hybrid.COUNT_LEAVES
    names = hybrid.hf_name_map(models.config_from_hf_dict({**pub, "num_hidden_layers": 4, "gqa_layers": [0], "n_routed_experts": 2}))
    assert names["kda_moe/0/f_b"] == ("model.layers.1.self_attn.f_b_proj.weight", True) and names["attention_moe/0/wg"][0] == "model.layers.0.self_attn.g_proj.weight"
    assert names["kda_moe/2/we_down/1"][0] == "model.layers.3.mlp.experts.1.down_proj.weight"
    for bad in ({"use_rope": True}, {"kda_use_full_proj": True}, {"use_gqa_gate": False}, {"gqa_layers": [0, 99]}, {"n_group": 2},
                {"linear_attn_config": {**pub["linear_attn_config"], "num_kv_heads": 8}}):
        with pytest.raises(ValueError):
            models.config_from_hf_dict({**pub, **bad})


def test_parameter_counts_by_hand():
    """250.3 B / 14.7 B active as published (250B-A15B), and the cell's 3.90
    B: by hand from the widths, against the weights' own shapes."""
    pub = _catalog_config()
    D, E, Fe, V = 4096, 320, 1280, 196608
    expert = 3 * D * Fe  # 15.73 M
    moe_rest = D * E + E + 3 * D * Fe + 2 * D  # router, its bias, the shared expert, the block's two norms
    gqa = 2 * D * 8192 + 2 * D * 1024 + D * 8192 + moe_rest  # q, o, k, v, the gate: 126.1 M
    kda = 4 * D * 8192 + 2 * (D * 128 + 128 * 8192) + D * 64 + 3 * 4 * 8192 + 64 + 8192 + 128 + moe_rest  # 154.8 M
    assert (round(gqa / 1e6, 1), round(kda / 1e6, 1), round(expert / 1e6, 2)) == (126.1, 154.8, 15.73)
    total = 12 * gqa + 36 * kda + 48 * E * expert + 2 * V * D + D
    active = 12 * gqa + 36 * kda + 48 * 8 * expert + 2 * V * D + D
    assert (round(total / 1e9, 1), round(active / 1e9, 1)) == (250.3, 14.7)
    whole = {**pub, "assumed": {}}
    assert solar_open2_weights.count(whole) == total and solar_open2_weights.count(whole, active=True) == active
    with open(os.path.join(CHIP, "configs", su.CONFIG + ".json")) as f:
        cell = json.load(f)
    held = 2 * gqa + 6 * kda + 8 * 20 * expert + 2 * 24576 * D + D
    assert solar_open2_weights.count(cell) == held and round(held / 1e9, 2) == 3.90
    shapes = jax.eval_shape(lambda: hybrid.init_params(jax.random.PRNGKey(0), su.model_config(cell, dtype="bfloat16")))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == held  # the program's own leaves
    with pytest.raises(AssertionError):
        assert round((total + 48 * 3 * D * (10240 - 1280)) / 1e9, 1) == 250.3  # a shared expert of intermediate_size: 256B
