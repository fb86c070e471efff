"""The latent page's two Pallas launches under the interpreter, each against
its XLA path: ``paged_latent_attn`` (ops/paged_latent_attention.py) by the
registered kernelcheck grid, and ``paged_kv_write`` on a pool of ONE row a
token (a latent model's ``k`` alone), bit for bit against the scatter; and
the touched-expert launch (ops/moe_touched_experts.py) against a loop over
experts, alone, through ``moe.expert_ffn`` and through a decode step.
``tests/test_tpu_compile.py -k kanana2`` compiles them for the described
chip; ``kernelcheck --compiled`` runs the grids there."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.inference import paged_kv
from areal_tpu.models import hybrid, moe
from areal_tpu.ops.paged_attention_q8 import live_order, paged_kernel_ok
from areal_tpu.tools import kernelcheck


def test_latent_attention_kernel_agrees_with_the_gather_path():
    assert "paged_latent_attention" in kernelcheck.REGISTRY
    results = kernelcheck.run_kernel("paged_latent_attention")
    assert len(results) == 4 and all(r["ok"] for r in results), results
    assert paged_kernel_ok(640, 128, False)  # the stored row is whole lane tiles: the compiled kernels serve it


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_one_latent_row_written_by_the_kernel_equals_the_scatter(dtype, monkeypatch):
    import functools

    import areal_tpu.ops.paged_kv_write as pkw

    monkeypatch.setattr(pkw, "paged_kv_write", functools.partial(pkw.paged_kv_write, interpret=True))
    L, S, N, psz, lanes = 3, 6, 9, 16, 256
    rng = np.random.default_rng(2)
    pool = jnp.asarray(rng.normal(size=(L, 1, N, psz, lanes)), dtype)
    rows = jnp.asarray(rng.normal(size=(S, 1, lanes)), dtype)
    live = np.array([True, False, True, True, False, True])
    page = jnp.asarray(np.where(live, 1 + rng.permutation(S), 0), jnp.int32)  # a page of its own a live slot
    off = jnp.asarray([0, 3, psz - 1, 7, 5, 8], jnp.int32)
    by_kernel = paged_kv.write_decode_rows({"k": pool}, jnp.int32(1), rows, None, page, off, live_order(jnp.asarray(live)))
    by_scatter = paged_kv.write_decode_rows({"k": pool}, jnp.int32(1), rows, None, page, off, None)
    assert set(by_kernel) == {"k"}
    got, want = np.asarray(by_kernel["k"], np.float32), np.asarray(by_scatter["k"], np.float32)
    assert np.array_equal(got[:, :, 1:], want[:, :, 1:])  # every page but the trash page, where the scatter sends an ended slot's row
    assert np.array_equal(got[:, :, 0], np.asarray(pool, np.float32)[:, :, 0])  # which the kernel leaves as it was
    changed = (got != np.asarray(pool, np.float32)).any(axis=-1)  # [L, 1, N, psz]
    assert changed.sum() == live.sum() and changed[1].sum() == live.sum()  # one row a live slot, in the named layer only


# ---------------------------------------------------------------------------
# the touched-expert launch
# ---------------------------------------------------------------------------

TOUCHED_CASES = ["f32-all-touched", "f32-one-touched", "f32-last-only", "f32-more-than-the-ring", "f32-none-touched", "bf16-three-touched"]


@pytest.mark.parametrize("case", TOUCHED_CASES)
def test_touched_expert_kernel_agrees_with_a_loop_over_the_listed_experts(case):
    """The registered grid, a case a test: stacks of 3 layers x 6 experts in
    which every expert off the list and every other layer is NaN. The output
    is finite and the loop's: nothing off the list was read."""
    assert [c["case"] for c in kernelcheck.REGISTRY["moe_touched_experts"]()] == TOUCHED_CASES
    (result,) = kernelcheck.run_kernel("moe_touched_experts", case=case)
    assert result["ok"], result


def test_touched_list_is_the_held_slice_compacted_in_order():
    load = jnp.asarray([9, 0, 3, 0, 0, 1, 0, 2, 0, 0, 4, 0], jnp.int32)
    ids, n = moe.touched_list(load, 4, 6)  # holds experts 4-9: rows on 5 and 7
    assert int(n) == 2 and np.asarray(ids)[:2].tolist() == [1, 3]
    ids, n = moe.touched_list(load, 0, 12)
    assert int(n) == 5 and np.asarray(ids)[:5].tolist() == [0, 2, 5, 7, 10]
    ids, n = moe.touched_list(jnp.zeros(12, jnp.int32), 2, 8)
    assert int(n) == 0
    ids, n = moe.touched_list(jnp.ones(12, jnp.int32), 2, 8)
    assert int(n) == 8 and np.asarray(ids).tolist() == list(range(8))


def _interpreted_touched(monkeypatch, seen=None):
    """The launch under the interpreter; ``seen`` collects the ``n_touched``
    each launch was given."""
    import areal_tpu.ops.moe_touched_experts as mte

    launch = functools.partial(mte.touched_expert_ffn, interpret=True)

    def recording(x, gate, wg, wu, wd, layer, touched, n_touched, **kw):
        jax.debug.callback(lambda n: seen.append(int(n)), n_touched)
        return launch(x, gate, wg, wu, wd, layer, touched, n_touched, **kw)

    monkeypatch.setattr(mte, "touched_expert_ffn", launch if seen is None else recording)


@pytest.mark.parametrize("case", ["every_expert", "one_expert", "last_id_only", "all_rows_dead", "held_from_4", "live_masks_an_experts_only_row"])
def test_expert_ffn_over_the_touched_experts_equals_the_dense_form(case, monkeypatch):
    """``moe.expert_ffn`` on the layer in the middle of a stack of three,
    both ways: XLA's every-expert form on the layer's slice, and the launch
    over the stack with every held expert that got no live row, and both
    other layers, set to NaN. The bias decides who is chosen (top-2 of 8);
    the launch's list comes from the same ``load`` the counter reads."""
    _interpreted_touched(monkeypatch)
    from areal_tpu import models

    cfg = models.qwen.ModelConfig(num_experts=8, num_experts_per_tok=2, norm_topk_prob=True)
    rng = np.random.default_rng(5)
    T, D, F, L = 24, 128, 256, 3
    e0, E_loc = (4, 4) if case == "held_from_4" else (0, 8)
    x = jnp.asarray(rng.normal(0, 1, (T, D)), jnp.float32)
    bias = np.zeros(8, np.float32)
    live = None
    if case == "one_expert":
        bias[[3, 6]] = 10.0  # every row chooses 3 and 6 ...
        e0, E_loc = 0, 6  # ... and 6 is held elsewhere
    elif case == "last_id_only":
        bias[[7]] = 10.0
        e0, E_loc = 6, 2  # the second choice falls on 0-5 or on 6: forced below
        bias[[0]] = 9.0
    elif case == "all_rows_dead":
        live = jnp.zeros(T, bool)
    layer = {"w_router": jnp.asarray(rng.normal(0, 0.3, (D, 8)), jnp.float32), "router_bias": jnp.asarray(bias)}
    stacks = {
        "we_gate": rng.normal(0, D**-0.5, (L, E_loc, D, F)).astype(np.float32),
        "we_up": rng.normal(0, D**-0.5, (L, E_loc, D, F)).astype(np.float32),
        "we_down": rng.normal(0, F**-0.5, (L, E_loc, F, D)).astype(np.float32),
    }
    if case == "live_masks_an_experts_only_row":
        _, _, chosen = moe.route(x, layer["w_router"], cfg, layer["router_bias"])
        chosen = np.asarray(chosen)
        rows_of = [np.flatnonzero((chosen == e).any(1)) for e in range(8)]
        e_few = min(range(8), key=lambda e: (len(rows_of[e]) == 0, len(rows_of[e])))
        live = jnp.asarray(~np.isin(np.arange(T), rows_of[e_few]))  # the rows that chose it hold no request
    sliced = {**layer, **{k: jnp.asarray(v[1]) for k, v in stacks.items()}}
    want, _, _, load = moe.expert_ffn(x, sliced, cfg, live=live, e0=e0)
    touched = np.asarray(load)[e0 : e0 + E_loc] > 0
    if case == "every_expert":
        assert touched.all()
    elif case == "one_expert":
        assert touched.tolist() == [False, False, False, True, False, False]
    elif case == "last_id_only":
        assert touched.tolist() == [False, True]
    elif case == "all_rows_dead":
        assert not touched.any()
    elif case == "live_masks_an_experts_only_row":
        assert not touched[e_few] and touched.sum() >= 5
    poisoned = {}
    for k, v in stacks.items():
        v = v.copy()
        v[[0, 2]] = np.nan
        v[1, ~touched] = np.nan
        poisoned[k] = jnp.asarray(v)
    stacked = {k: moe.Stacked(v, jnp.int32(1)) for k, v in poisoned.items()}
    got, _, _, load_t = moe.expert_ffn(x, {**layer, **stacked}, cfg, live=live, e0=e0)
    assert np.array_equal(np.asarray(load_t), np.asarray(load))
    assert np.isfinite(np.asarray(got)).all()
    if case == "all_rows_dead":
        assert not np.asarray(got).any()  # exactly 0
    else:
        assert np.abs(np.asarray(want)).max() > 0.05
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6, rtol=0)
    # XLA's form on the poisoned layer: gate 0 x NaN
    if not touched.all():
        dense_on_poison, _, _, _ = moe.expert_ffn(x, {**layer, **{k: v[1] for k, v in poisoned.items()}}, cfg, live=live, e0=e0)
        assert np.isnan(np.asarray(dense_on_poison)).any()


@pytest.mark.parametrize(
    "rows,k,router,local,want",
    [(64, 6, 128, 16, True), (128, 4, 32, 32, False), (256, 6, 128, 16, False), (1024, 6, 128, 16, False), (2048, 6, 128, 16, False), (8, 2, 8, 4, True)],
    ids=["cell7-decode", "cell5-decode", "cell7-prefill-256", "cell7-prefill-1024", "routed-rows", "tiny"],
)
def test_touched_form_is_chosen_by_a_full_batchs_assignments_an_expert(rows, k, router, local, want):
    """3 assignments an expert from a full batch (64 x 6 / 128) takes the
    touched form; 16 (128 x 4 / 32) and a prefill's 12-48 keep XLA's; rows
    past the dense form's are routed, never touched."""
    assert moe.takes_touched_form(rows, k, router, local) is want
    assert not want or moe.takes_dense_form(rows, local)


def test_decode_step_through_the_touched_experts_agrees_with_xlas_form(monkeypatch):
    """Two decode steps of the tiny latent model (experts 4-7 held of the 8
    its router scores, three expert layers), ``use_kernel=True`` with every
    launch interpreted on a backend that calls itself a TPU, against
    ``use_kernel=False``: the same hidden rows to float32's digits, the same
    load, ``moe_touched`` equal to the ``n_touched`` the launches were given,
    and ``moe_streamed`` equal to it where XLA's form reads all four a layer."""
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))
    import chipbench_kanana2_util as ku

    import areal_tpu.ops.paged_kv_write as pkw
    import areal_tpu.ops.paged_latent_attention as pla

    seen: list[int] = []
    _interpreted_touched(monkeypatch, seen)
    monkeypatch.setattr(pla, "paged_latent_attention_stacked", functools.partial(pla.paged_latent_attention_stacked, interpret=True))
    monkeypatch.setattr(pkw, "paged_kv_write", functools.partial(pkw.paged_kv_write, interpret=True))
    cfg = ku.tiny_model(held=4, first=4)
    mcfg, params = ku.model_config(cfg), ku.make_params(cfg, 3)
    S, PSZ, WP = 3, 8, 8
    assert moe.takes_touched_form(S, mcfg.num_experts_per_tok, mcfg.router_width, mcfg.num_experts)
    pt = np.zeros((S, WP), np.int32)
    pt[0], pt[1] = np.arange(1, WP + 1), np.arange(WP + 1, 2 * WP + 1)
    out = {}
    for use_kernel in (False, True):
        if use_kernel:
            monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        cache = paged_kv.init_paged_cache(mcfg, S * WP + 1, PSZ, slots=S)
        cache = {**cache, **{k: jnp.zeros(s, jnp.int32) for k, s in mcfg.count_shapes.items()}}
        # a jit of this test's own, traced under ITS patches (the spy, the backend's name): not the family harness's kept step
        step = jax.jit(functools.partial(hybrid.forward_decode_paged, page_size=PSZ, use_kernel=use_kernel), static_argnums=1)
        hidden = []
        for t in range(2):
            h, cache = step(params, mcfg, jnp.array([7, 9, 0]), jnp.array([t, t, 0]), cache, jnp.asarray(pt), active=jnp.array([True, True, False]))
            hidden.append(np.asarray(h)[:2])
        out[use_kernel] = (np.stack(hidden), {k: np.asarray(cache[k]) for k in mcfg.moe_count_shapes})
    jax.effects_barrier()
    (h_xla, c_xla), (h_krn, c_krn) = out[False], out[True]
    np.testing.assert_allclose(h_krn, h_xla, atol=2e-5, rtol=0)  # test_kanana2_model.py's tolerance
    assert np.array_equal(c_krn["moe_load"], c_xla["moe_load"]) and np.array_equal(c_krn["moe_touched"], c_xla["moe_touched"])
    assert len(seen) == 2 * 3 and sum(seen) == int(c_krn["moe_touched"].sum()) and 0 < sum(seen) < 2 * 3 * 4
    assert np.array_equal(c_krn["moe_streamed"], c_krn["moe_touched"])
    assert c_xla["moe_streamed"].tolist() == [2 * 4] * 3
