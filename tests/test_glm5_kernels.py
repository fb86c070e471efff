"""What ``ops/`` gained for a latent-attention layer with a learned index,
each launch under the interpreter against XLA's form of the same sum: the
index's scores over the pool of index keys, the latent launch under a
selection (the MASKED sparse read; selections that leave whole blocks of a
slot without a chosen token among them), a decode step's write of TWO pools
of different widths in one launch, and the touched-expert launch where an
expert goes through the ring in parts of its width. Compiled for the chip in
tests/test_tpu_compile.py; ``kernelcheck --compiled`` runs the grids there."""

import functools

import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.inference import paged_kv
from areal_tpu.ops import moe_touched_experts as mte
from areal_tpu.ops.paged_attention_q8 import live_order
from areal_tpu.tools import kernelcheck

INDEX_CASES = ["scores-bf16-layer0", "masked-bf16-layer2", "masked-bf16-last-tokens", "scores-f32-layer1", "masked-f32-layer1", "masked-f32-last-tokens"]
PARTS_CASES = ["f32-2-parts-three-touched", "f32-4-parts-all-touched", "f32-4-parts-none-touched"]


@pytest.mark.parametrize("case", INDEX_CASES)
def test_index_scores_and_the_masked_read_agree_with_the_gather_path(case):
    assert [c["case"] for c in kernelcheck.REGISTRY["paged_index_select"]()] == INDEX_CASES
    (result,) = kernelcheck.run_kernel("paged_index_select", case=case)
    assert result["ok"], result


@pytest.mark.parametrize("case", PARTS_CASES)
def test_an_expert_in_parts_of_its_width_is_the_same_expert(case):
    """Stacks in which every expert off the list and every other layer is
    NaN, the ring shrunk so that an expert takes 2 or 4 parts: the output is
    finite and the loop's."""
    assert [c["case"] for c in kernelcheck.REGISTRY["moe_touched_experts_parts"]()] == PARTS_CASES
    (result,) = kernelcheck.run_kernel("moe_touched_experts_parts", case=case)
    assert result["ok"], result


def test_width_parts_by_the_shapes():
    assert mte.width_parts(2048, 768, 2) == 1  # 18.9 MB twice over: the launch as it was (rollout-kanana-2-30b-a3b-ep8-grpo)
    assert mte.width_parts(2048, 1792, 2) == 1  # 44 MB
    assert mte.width_parts(6144, 2048, 2) == 4  # 151 MB whole; 37.7 MB in parts of 512
    assert 2 * 3 * 6144 * 512 * 2 <= mte._RING_BYTES < 2 * 3 * 6144 * 1024 * 2


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_two_pools_of_two_widths_written_by_one_launch_equal_the_scatters(dtype, monkeypatch):
    """A decode step's latent row (256 lanes) and index key (128 lanes) of
    one layer into two pools on the same page and row: the one launch over
    the live slots against the per-head scatters over every slot, bit for
    bit in every page but the trash page, no other layer touched."""
    import areal_tpu.ops.paged_kv_write as pkw

    monkeypatch.setattr(pkw, "paged_kv_write", functools.partial(pkw.paged_kv_write, interpret=True))
    rng = np.random.default_rng(3)
    S, L, N, psz = 6, 3, 14, 16
    cache = {
        "k": jnp.asarray(rng.standard_normal((L, 1, N, psz, 256)), dtype),
        "idx": jnp.asarray(rng.standard_normal((L, 1, N, psz, 128)), dtype),
    }
    rows = jnp.asarray(rng.standard_normal((S, 1, 256)), dtype)
    keys = jnp.asarray(rng.standard_normal((S, 1, 128)), dtype)
    live = np.array([True, False, True, True, False, True])
    page = jnp.asarray(np.where(live, 1 + rng.permutation(N - 1)[:S], 0), jnp.int32)
    off = jnp.asarray(rng.integers(0, psz, S), jnp.int32)
    by_kernel = paged_kv.write_decode_rows(dict(cache), jnp.int32(1), rows, None, page, off, live_order(jnp.asarray(live)), more={"idx": keys})
    by_scatter = paged_kv.write_decode_rows(dict(cache), jnp.int32(1), rows, None, page, off, None, more={"idx": keys})
    for name, new in (("k", rows), ("idx", keys)):
        a, b = np.asarray(by_kernel[name], np.float32), np.asarray(by_scatter[name], np.float32)
        assert np.array_equal(a[:, :, 1:], b[:, :, 1:])
        assert np.array_equal(a[[0, 2]], np.asarray(cache[name], np.float32)[[0, 2]])  # the other layers as they were
        for s in np.flatnonzero(live):
            assert np.array_equal(a[1, 0, int(page[s]), int(off[s])], np.asarray(new, np.float32)[s, 0])
        assert np.array_equal(a[1, 0, 0], np.asarray(cache[name], np.float32)[1, 0, 0])  # the kernel never touches the trash page


def test_grouped_matmuls_over_the_whole_stack_are_the_layers_own():
    """A prompt pass's rows through the grouped matmuls with the expert
    leaves handed over as their STACKS (``moe.Stacked``): the stack is
    ``layers x experts`` groups of which only this layer's have rows, and
    the result is what the layer's own three matrices give, for every layer
    of the stack (the kernel under the interpreter; 1,280 rows: past
    ``DENSE_ROWS``, so the routed form)."""
    import jax

    from areal_tpu.models import moe

    class Cfg:
        num_experts_per_tok, router_score, norm_topk_prob, router_norm_eps, routed_scaling_factor = 3, "sigmoid", True, 1e-20, 2.5

    L, E, D, F, T = 3, 4, 128, 256, 1280
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (T, D))
    stack = {
        "we_gate": jax.random.normal(ks[1], (L, E, D, F)) * D**-0.5,
        "we_up": jax.random.normal(ks[2], (L, E, D, F)) * D**-0.5,
        "we_down": jax.random.normal(ks[3], (L, E, F, D)) * F**-0.5,
    }
    router = {"w_router": jax.random.normal(ks[4], (D, 8)), "router_bias": 0.05 * jax.random.normal(ks[5], (8,))}
    assert not moe.takes_dense_form(T, E)
    over_stack = jax.jit(lambda x, w: moe.expert_ffn(x, w, Cfg, e0=2))
    for li in range(L):
        own, _, _, load = moe.expert_ffn(x, {k: v[li] for k, v in stack.items()} | router, Cfg, e0=2)
        got, _, _, load_s = over_stack(x, {k: moe.Stacked(v, jnp.int32(li)) for k, v in stack.items()} | router)
        assert float(jnp.abs(own).max()) > 1 and np.array_equal(np.asarray(load), np.asarray(load_s))
        np.testing.assert_allclose(np.asarray(got), np.asarray(own), atol=2e-6, rtol=0)
