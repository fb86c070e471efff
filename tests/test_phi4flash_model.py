"""The ``phi4flash`` family in ``models/hybrid.py`` (a decoder-hybrid-decoder:
Mamba-1 layers beside window attention, ONE full-attention layer whose keys
and values the cross-attention layers read, gated memory units, differential
attention, LayerNorm with a bias) against the benchmark's plain reference
(``benchlib/phi4flash_reference.py``: every layer over every token, no cache,
the scan token by token), and its cache: the window layers' rings
(``paged_kv.RING_LEAVES``), the shared pages, the selective-scan state.

Tiny size of the published shape: 12 layers in the same five kinds, a window
of 8 tokens against pages of 4, so that a ring wraps after 8 tokens and a
window crosses page edges. float32 on both sides, logits of order 1:
tolerance 2e-5 (measured 1e-6 to 6e-6). A ring one token off, a key a window
too old or a memory unit reading another token's scan output moves a logit by
1e-2 and more."""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))
import chipbench_phi4flash_util as pu  # noqa: E402
from chipbench_util import CHIP, load_run  # noqa: E402

load_run()

from areal_tpu import models  # noqa: E402
from areal_tpu.inference import paged_kv  # noqa: E402
from areal_tpu.models import hybrid  # noqa: E402
from tests.family_harness import decode_step, fresh_cache, prefill_into_slot, program_logits, with_counts  # noqa: E402

TOL = 2e-5
W, PSZ = 8, 4


@pytest.fixture(scope="module")
def tiny():
    cfg = pu.tiny_model()
    return cfg, pu.model_config(cfg), pu.make_params(cfg, 11)


def _ids(n, seed=0, vocab=500):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


# -- the configuration ----------------------------------------------------------


def test_from_hf_dict_builds_the_layer_table_and_says_which_layers_a_pool_serves(tiny):
    _, mcfg, _ = tiny
    assert mcfg.layer_types == pu.KINDS and models.family_of(mcfg) is hybrid
    assert (mcfg.count("s6"), mcfg.count("swa"), mcfg.count("attention"), mcfg.count("gmu"), mcfg.count("cross")) == (4, 3, 1, 2, 2)
    assert mcfg.norm_kind == "layer" and mcfg.diff_attn and mcfg.attn_bias and mcfg.rope_theta is None and mcfg.sliding_window == W
    # ONE layer under the page table: pairs of K/V heads side by side, nothing padded
    assert mcfg.num_kv_layers == 1 and mcfg.kv_pools == {"k": (2, 16), "v": (2, 16)}
    groups = mcfg.kv_groups
    assert groups["full"] == {"pools": ("k", "v"), "writers": (7,), "readers": (7, 9, 11), "keeps": None}
    assert groups["window"] == {"pools": paged_kv.RING_LEAVES, "writers": (1, 3, 5), "readers": (1, 3, 5), "keeps": W}
    assert mcfg.ring_pages(PSZ) == 2 and mcfg.ring_pages(3) == 3
    assert mcfg.ring_shapes(5, PSZ) == {n: ((3, 2, 6, 2, PSZ, 16), jnp.dtype("float32")) for n in paged_kv.RING_LEAVES}
    assert mcfg.state_shapes(5) == {"ssm": ((4, 5, 4, 128), jnp.dtype("float32")), "conv": ((4, 5, 3 * 128), jnp.dtype("float32"))}
    assert mcfg.count_shapes == {
        "shared_kv_tokens_read": (1,), "window_tokens_read": (1,), "s6_updates": (1,), "attn_blocks_listed": (1,), "attn_blocks_fetched": (1,),
    }
    assert mcfg.has_recurrent_state and set(mcfg.count_shapes) <= set(hybrid.COUNT_LEAVES)
    assert set(mcfg.layer_types) <= set(hybrid.KINDS) and set(hybrid._MIXER_SCOPE) == set(hybrid.KINDS)


@pytest.mark.parametrize(
    "change, msg",
    [
        ({"mb_per_layer": 3}, "mb_per_layer"),
        ({"num_hidden_layers": 10}, "divisible by 4"),
        ({"sliding_window": None}, "sliding_window"),
        ({"mlp_bias": True}, "mlp_bias"),
        ({"num_key_value_heads": 8}, "pairs"),
        ({"hidden_act": "gelu"}, "hidden_act"),
    ],
)
def test_from_hf_dict_refuses_what_the_module_does_not_implement(change, msg):
    cfg = pu.tiny_model()
    hf = {**{k: v for k, v in cfg.items() if k != "assumed"}, **cfg["assumed"], **change}
    with pytest.raises(ValueError, match=msg):
        models.config_from_hf_dict(hf)


def test_config_round_trips_through_its_hf_dict(tiny):
    _, mcfg, _ = tiny
    again = hybrid.HybridConfig.from_hf_dict({**mcfg.to_hf_dict(), "dtype": "float32", "ssm_state_dtype": "float32", "conv_state_dtype": "float32"})
    assert again == mcfg


def test_published_widths_count_3852_million_parameters_in_the_programs_own_shapes():
    """The configuration file at every published width: 32 layers in the
    issue's table, and the program's stacks hold what the benchmark's weights
    make, leaf for leaf: 3,852.6 M parameters (the published 3.8B)."""
    from benchlib import phi4flash_weights

    with open(os.path.join(CHIP, "configs", pu.CONFIG + ".json")) as f:
        cfg = json.load(f)
    mcfg = pu.model_config(cfg, "bfloat16", **{})
    kinds = mcfg.layer_types
    assert len(kinds) == 32 and kinds[:18:2] == ("s6",) * 9 and kinds[1:17:2] == ("swa",) * 8 and kinds[17] == "attention"
    assert kinds[18::2] == ("gmu",) * 7 and kinds[19::2] == ("cross",) * 7
    assert (mcfg.s6_d_inner, mcfg.mamba_d_state, mcfg.s6_dt_rank, mcfg.head_dim_, mcfg.kv_pools) == (5120, 16, 160, 64, {"k": (10, 128), "v": (10, 128)})
    shapes = phi4flash_weights.shapes(cfg)
    sizes = hybrid._stack_sizes(mcfg)
    for stack, leaves in hybrid._layer_shapes(mcfg).items():
        assert {k: (sizes[stack], *v) for k, v in leaves.items()} == shapes[stack], stack
    assert phi4flash_weights.count(cfg) == 3_852_562_944
    # a cached token: 5,120 B in the one full layer; a slot: 26 MB of rings at pages of 128, 3.2 MB of state
    assert paged_kv.kv_token_bytes(mcfg.kv_pools, mcfg.num_kv_layers, 2) == 5120
    ring = mcfg.ring_shapes(1, 128)["ring_k"][0]
    assert 2 * 2 * np.prod(ring[:2] + ring[3:]) == 8 * 4 * 128 * 5120 == 20_971_520 and mcfg.ring_pages(128) == 4 <= -(-512 // 128) + 1
    # eight alternating pairs, two single layers, seven pairs: four scan bodies, not 32
    assert [(len(g), n, at, span) for g, n, at, span in hybrid._periods(mcfg)] == [(2, 8, 0, 16), (1, 1, 16, 1), (1, 1, 17, 1), (2, 7, 18, 14)]


def test_other_families_keep_one_scan_a_run_of_one_kind():
    from chipbench_hybrid_util import tiny_model as granite

    import chipbench_olmo_util as ou

    for mcfg in (ou.model_config(ou.tiny_model()), models.config_from_hf_dict({**granite(), "dtype": "float32"})):
        assert all(len(group) == 1 for group, *_ in hybrid._periods(mcfg))
        assert [g[0] for g, *_ in hybrid._periods(mcfg)] == hybrid._runs(mcfg)


def test_hf_name_map_names_every_leaf_once(tiny):
    _, mcfg, params = tiny
    names = hybrid.hf_name_map(mcfg)
    flat = {"/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path) for path, _ in jax.tree.flatten_with_path(params)[0]}
    tops = {n for n in names if "/" not in n}
    assert tops == {"embed", "final_norm", "final_norm_bias"}
    stacked = {n.split("/")[0] + "/" + n.split("/")[2] for n in names if "/" in n}
    assert stacked | tops == flat
    assert names["swa/2/wq"] == ("model.layers.5.attn.Wqkv.q.weight", True) and names["attention/0/wo_b"] == ("model.layers.7.attn.out_proj.bias", False)
    assert names["s6/3/A_log"] == ("model.layers.6.attn.A_log", True) and names["gmu/1/gmu_in"][0].startswith("model.layers.10.")
    assert len(set(v[0] for v in names.values())) == len(names)


# -- the forward against the reference --------------------------------------------


@pytest.mark.parametrize("n", [5, 8, 13, 30])
def test_full_forward_matches_the_plain_reference(tiny, n):
    """Below the window, at it, across it and with the window moved on by
    more than two of its lengths."""
    cfg, mcfg, params = tiny
    ids = _ids(n, seed=n)
    got = program_logits(mcfg, params, ids)
    want = pu.reference().token_logits(params, cfg, ids)
    assert np.abs(got - want).max() < TOL


@pytest.mark.parametrize("n", [3, 9, 26])
def test_last_token_prompt_pass_gives_the_full_passes_logits_at_that_token(tiny, n):
    """The architecture's own prompt pass: the self-decoder and layer 7's K
    and V over every token, its attention and the cross-decoder over the last
    token alone; and ``tail='none'`` leaves the same K, V and state behind."""
    _, mcfg, params = tiny
    ids = np.concatenate([_ids(n, seed=40 + n), np.zeros(4, np.int32)])  # padded to a bucket
    x, seg = jnp.asarray(ids)[None], (jnp.arange(n + 4) < n).astype(jnp.int32)[None]
    full, ks, vs, state = hybrid.forward_prefill(params, mcfg, x, seg)
    last, ks_l, vs_l, state_l = hybrid.forward_prefill(params, mcfg, x, seg, tail="last")
    none, ks_n, vs_n, state_n = hybrid.forward_prefill(params, mcfg, x, seg, tail="none")
    assert last.shape == (1, 1, 64) and none is None
    assert np.abs(np.asarray(hybrid.compute_logits(params, mcfg, last)[0, 0] - hybrid.compute_logits(params, mcfg, full)[0, n - 1])).max() < TOL
    for a, b in ((ks, ks_l), (vs, vs_l), (ks, ks_n), (vs, vs_n)):
        assert np.allclose(np.asarray(a)[:, :, :n], np.asarray(b)[:, :, :n], atol=1e-6)
    for other in (state_l, state_n):
        assert set(other) == {"ssm", "conv"} and all(np.allclose(np.asarray(state[k]), np.asarray(other[k]), atol=1e-6) for k in state)


@pytest.mark.parametrize("L", [5, 8, 21])
def test_window_attention_in_blocks_equals_the_dense_mask(tiny, L):
    _, mcfg, _ = tiny
    rng = jax.random.split(jax.random.PRNGKey(L), 3)
    q = jax.random.normal(rng[0], (2, L, 8, 8))
    k, v = (jax.random.normal(r, (2, L, 2, 16)) for r in rng[1:])
    t = jnp.arange(L)
    allowed = (t[None, :] <= t[:, None]) & (t[:, None] - t[None, :] < W)
    want = jnp.stack([hybrid.diff_attend(mcfg, q[i], k[i], v[i], allowed) for i in range(2)])
    assert np.abs(np.asarray(hybrid.swa_attend(mcfg, q, k, v) - want)).max() < 1e-5


def test_packed_queries_over_paired_rows_are_the_two_softmaxes(tiny):
    """``[q1 | 0]`` and ``[0 | q2]`` against rows ``[k1 | k2]`` under plain
    grouped-query paged attention give what ``diff_attend`` computes."""
    _, mcfg, _ = tiny
    rng = jax.random.split(jax.random.PRNGKey(3), 3)
    T = 11
    q = jax.random.normal(rng[0], (1, 8, 8))
    k, v = (jax.random.normal(r, (T, 2, 16)) for r in rng[1:])
    want = hybrid.diff_attend(mcfg, q, k, v, jnp.ones((1, T), bool))
    pages = lambda t: jnp.pad(t, ((0, 12 - T), (0, 0), (0, 0))).reshape(3, PSZ, 2, 16).transpose(2, 0, 1, 3)  # noqa: E731
    got = paged_kv.paged_attention_xla(hybrid._diff_pack_q(q), pages(k), pages(v), jnp.asarray([T]), jnp.asarray([[0, 1, 2]]), sm_scale=mcfg.sm_scale)
    assert np.abs(np.asarray(got - want)).max() < 1e-5


def test_the_scan_over_a_prompt_equals_the_decode_steps(tiny):
    _, mcfg, _ = tiny
    rng = jax.random.split(jax.random.PRNGKey(9), 5)
    L, C, N = 19, 128, 4
    c, d = jax.random.normal(rng[0], (2, L, C)), jax.nn.softplus(jax.random.normal(rng[1], (2, L, C)))
    b, cc = jax.random.normal(rng[2], (2, L, N)), jax.random.normal(rng[3], (2, L, N))
    a = -jnp.exp(jax.random.normal(rng[4], (N, C)))
    n_state = jnp.asarray([L, 7])
    s_fin, y = hybrid.s6_scan(c, d, b, cc, a, n_state)
    s, live = jnp.zeros((2, N, C)), jnp.asarray([True, True])
    for t in range(L):
        s_new, y_t = hybrid.s6_decode_step(s, c[:, t], d[:, t], b[:, t], cc[:, t], a, live)
        s = jnp.where((t < n_state)[:, None, None], s_new, s)
        if t < 7:
            assert np.abs(np.asarray(y_t - y[:, t])).max() < 1e-5
    assert np.abs(np.asarray(s - s_fin)).max() < 1e-5
    kept, _ = hybrid.s6_decode_step(s, c[:, 0], d[:, 0], b[:, 0], cc[:, 0], a, jnp.asarray([False, True]))
    assert np.array_equal(np.asarray(kept[0]), np.asarray(s[0])) and not np.array_equal(np.asarray(kept[1]), np.asarray(s[1]))


# -- through the cache --------------------------------------------------------------


@pytest.mark.parametrize("plen", [3, 7, 11, 19])
def test_prefill_then_decode_through_the_cache_matches_the_reference(tiny, plen):
    """A prompt below, just under, across and two windows past the window,
    then decoded to 44 tokens: the ring has wrapped five times by then and
    every read crossed a page edge; slot 1 holds nothing and stays so."""
    cfg, mcfg, params = tiny
    total = 44
    seqs = {0: _ids(total, seed=plen), 2: _ids(total, seed=100 + plen)}
    want = {s: pu.reference().token_logits(params, cfg, ids) for s, ids in seqs.items()}
    cache, table = fresh_cache(mcfg, 3, 12, PSZ)
    cache = prefill_into_slot(mcfg, params, cache, table, [(s, ids[:plen]) for s, ids in seqs.items()], -(-plen // PSZ) * PSZ, PSZ)
    step, table, active = decode_step(mcfg, PSZ, False), jnp.asarray(table), jnp.asarray([True, False, True])
    idle = {k: np.asarray(v) for k, v in cache.items()}
    for t in range(plen - 1, total):
        logits, cache = step(params, jnp.asarray([seqs[0][t], 0, seqs[2][t]]), jnp.asarray([t, 0, t]), cache, table, active)
        logits = np.asarray(logits)
        assert max(np.abs(logits[s] - want[s][t]).max() for s in seqs) < TOL, t
    for k in ("ssm", "conv"):  # a slot that is not active keeps its state bit for bit
        assert np.array_equal(idle[k][:, 1], np.asarray(cache[k])[:, 1])
    for k in paged_kv.RING_LEAVES:  # ... and its rings
        assert np.array_equal(idle[k][:, :, 1], np.asarray(cache[k])[:, :, 1])
    assert set(cache) == {"k", "v", "ssm", "conv", *paged_kv.RING_LEAVES}  # what rides with a step leaves with it


def test_an_active_slot_changes_nothing_of_its_neighbours_and_writes_one_row(tiny):
    _, mcfg, params = tiny
    cache, table = fresh_cache(mcfg, 3, 12, PSZ)
    rng = jax.random.split(jax.random.PRNGKey(5), len(cache))
    cache = {k: jax.random.normal(r, v.shape, v.dtype) for r, (k, v) in zip(rng, cache.items())}
    before = {k: np.asarray(v) for k, v in cache.items()}
    pos = 13  # ring position 5: page 1, row 1; the full layer's page 3, row 1
    table[[0, 2]] = 0  # as the engine leaves a slot that holds no request: its row of the table at the trash page
    _, after = decode_step(mcfg, PSZ, False)(params, jnp.asarray([7, 8, 9]), jnp.asarray([2, pos, 4]), cache, jnp.asarray(table), jnp.asarray([False, True, False]))
    after = {k: np.asarray(v) for k, v in after.items()}
    for k in paged_kv.RING_LEAVES:  # the block past the last slot takes the rows of the slots that are not live
        changed = np.argwhere((after[k] != before[k])[:, :, :3].any(axis=(0, 1, 5)))  # [slot block, page, row]
        assert changed.tolist() == [[1, 1, 1]], (k, changed)
    for k in ("k", "v"):
        changed = np.argwhere((after[k] != before[k])[:, :, 1:].any(axis=(0, 1, 4)))  # [page past the trash page, row]
        assert changed.tolist() == [[int(table[1, pos // PSZ]) - 1, pos % PSZ]]
    for k in ("ssm", "conv"):
        assert np.array_equal(after[k][:, [0, 2]], before[k][:, [0, 2]]) and not np.array_equal(after[k][:, 1], before[k][:, 1])


@pytest.mark.parametrize("plen", [5, 8, 21])
def test_a_ring_holds_exactly_the_window_after_a_prompt(tiny, plen):
    """Ring position r of a window layer holds the K of the LAST prompt token
    t with t % window == r; a padding row's ring goes to the block past the
    last slot, and the slots around keep theirs."""
    _, mcfg, params = tiny
    ids = _ids(plen, seed=plen)
    bucket = -(-plen // PSZ) * PSZ
    cache, table = fresh_cache(mcfg, 3, 12, PSZ)
    marked = {k: (v + 7.0 if k in paged_kv.RING_LEAVES else v) for k, v in cache.items()}
    out = prefill_into_slot(mcfg, params, marked, table, [(2, ids), (3, ids[:1])], bucket, PSZ)  # slot 3 does not exist: a padding row
    x = jnp.asarray(np.pad(ids, (0, bucket - plen)))[None]
    _, _, _, rows = hybrid.forward_prefill(
        params, mcfg, x, (jnp.arange(bucket) < plen).astype(jnp.int32)[None], n_state=jnp.asarray([plen - 1]),  # as the program: the last token is decode's
        sink=({"ssm": marked["ssm"], "conv": marked["conv"], "ring_k": jnp.zeros((3, 1, bucket, 2, 16))},
              lambda arr, j, new: {**arr, **{k: arr[k].at[j].set(v) if k == "ring_k" else arr[k] for k, v in new.items() if k in arr}}),
    )
    k_rows = np.asarray(rows["ring_k"])[:, 0]  # [window layer, position, pair, lanes]
    ring = np.asarray(out["ring_k"])[:, :, 2].reshape(3, 2, 2 * PSZ, 16)  # [layer, pair, ring position, lanes]
    for r in range(min(plen, W)):
        t = max(u for u in range(plen) if u % W == r)
        assert np.allclose(ring[:, :, r], k_rows[:, t], atol=1e-6), (r, t)
    assert np.all(np.asarray(out["ring_k"])[:, :, :2] == 7.0) and not np.all(np.asarray(out["ring_k"])[:, :, 3] == 7.0)


def test_copy_pages_gives_a_sibling_the_primarys_rings_and_state(tiny):
    _, mcfg, _ = tiny
    cache, _ = fresh_cache(mcfg, 3, 12, PSZ)
    rng = jax.random.split(jax.random.PRNGKey(8), len(cache))
    cache = {k: jax.random.normal(r, v.shape, v.dtype) for r, (k, v) in zip(rng, cache.items())}
    before = {k: np.asarray(v) for k, v in cache.items()}
    out = paged_kv.copy_pages(dict(cache), jnp.asarray([9]), jnp.asarray([4]), jnp.asarray([2]), jnp.asarray([0]))
    out = {k: np.asarray(v) for k, v in out.items()}
    for k in paged_kv.RING_LEAVES:
        assert np.array_equal(out[k][:, :, 2], before[k][:, :, 0]) and np.array_equal(out[k][:, :, [0, 1, 3]], before[k][:, :, [0, 1, 3]])
    for k in ("ssm", "conv"):
        assert np.array_equal(out[k][:, 2], before[k][:, 0]) and np.array_equal(out[k][:, :2], before[k][:, :2])
    for k in ("k", "v"):
        assert np.array_equal(out[k][:, :, 9], before[k][:, :, 4])


def test_a_chunks_counts_are_the_live_slots(tiny):
    _, mcfg, params = tiny
    cache, table = fresh_cache(mcfg, 3, 12, PSZ)
    cache = with_counts(mcfg, cache)
    _, out = decode_step(mcfg, PSZ, False)(params, jnp.asarray([1, 2, 3]), jnp.asarray([2, 30, 11]), cache, jnp.asarray(table), jnp.asarray([True, True, False]))
    assert int(out["shared_kv_tokens_read"][0]) == (3 + 31) * 3  # the full layer and two cross layers
    assert int(out["window_tokens_read"][0]) == (3 + W) * 3  # at most a window a live slot and window layer
    assert int(out["s6_updates"][0]) == 2 * 4


def test_the_kernel_path_in_interpret_mode_equals_the_gather_path(tiny, monkeypatch):
    """``paged_decode_attn`` over the rings and the shared pages and
    ``paged_kv_write`` into both, unchanged, under the Pallas interpreter."""
    import areal_tpu.ops.paged_attention_q8 as q8mod
    import areal_tpu.ops.paged_kv_write as kvw

    monkeypatch.setattr(q8mod, "paged_attention_stacked", functools.partial(q8mod.paged_attention_stacked, interpret=True))
    monkeypatch.setattr(kvw, "paged_kv_write", functools.partial(kvw.paged_kv_write, interpret=True))
    _, mcfg, params = tiny
    psz = 8  # the writer's tile is 8 rows
    S, maxp = 3, 4
    cache = paged_kv.init_paged_cache(mcfg, S * maxp + 1, psz, slots=S)
    rng = jax.random.split(jax.random.PRNGKey(6), len(cache))
    cache = {k: jax.random.normal(r, v.shape, v.dtype) for r, (k, v) in zip(rng, cache.items())}
    table = jnp.asarray(1 + np.arange(S * maxp, dtype=np.int32).reshape(S, maxp)).at[1].set(0)  # slot 1 ended: trash page
    args = (params, mcfg, jnp.asarray([4, 5, 6]), jnp.asarray([3, 9, 21]), cache, table)
    step = jax.jit(functools.partial(hybrid.forward_decode_paged, page_size=psz), static_argnums=1, static_argnames="use_kernel")  # the HIDDEN rows of both paths
    h_x, c_x = step(*args, active=jnp.asarray([True, False, True]), use_kernel=False)
    h_k, c_k = step(*args, active=jnp.asarray([True, False, True]), use_kernel=True)
    assert np.abs(np.asarray(h_x - h_k))[[0, 2]].max() < 1e-4
    for name in c_x:
        live = [0, 2]
        if name in paged_kv.RING_LEAVES:
            assert np.allclose(np.asarray(c_x[name])[:, :, live], np.asarray(c_k[name])[:, :, live], atol=1e-5), name
        elif name in ("ssm", "conv"):
            assert np.allclose(np.asarray(c_x[name])[:, live], np.asarray(c_k[name])[:, live], atol=1e-5), name
        else:
            assert np.allclose(np.asarray(c_x[name])[:, :, 1:], np.asarray(c_k[name])[:, :, 1:], atol=1e-5), name


def test_serving_limits_refuse_by_name(tiny):
    _, mcfg, _ = tiny
    limits = hybrid.serving_limits(mcfg)
    assert set(limits) == {"reason", "prefix_cache", "speculative", "int8_weights", "int8_pages", "sharded"} and limits["reason"] == "recurrent_state"
    assert "rings" in limits["int8_pages"] and "state" in limits["prefix_cache"]
    for refused in (hybrid.forward_prefill_paged, hybrid.forward_verify_paged):
        with pytest.raises(NotImplementedError, match="recurrent state"):
            refused()
    assert hybrid.prefill_attn_launch(mcfg, 16384) is False
