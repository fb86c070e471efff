"""The decode step with its KV rows written by ``ops/paged_kv_write.py``
(``use_kernel=True``: the program the chunk runs on the chip, both kernels in
interpret mode here) against the gather path with the per-head scatters:
40 greedy steps whose slots cross page boundaries must sample the same
tokens and leave the same pages, for the Qwen family (float pages and int8
pages with scales) and for the hybrid family (lane-padded heads, a recurrent
state beside the pages).

At most 8 tests here: xdist's ``loadfile`` hands whole files to workers."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))
import chipbench_hybrid_util as hu  # noqa: E402

import areal_tpu.ops.paged_attention_q8 as q8mod  # noqa: E402
import areal_tpu.ops.paged_kv_write as kvw  # noqa: E402
from areal_tpu.inference import paged_kv  # noqa: E402
from areal_tpu.models import hybrid, qwen  # noqa: E402
from areal_tpu.ops import ssm_state_update as ssu  # noqa: E402

S, PSZ, WP, STEPS = 4, 16, 4, 40
START = np.asarray([3, 9, 14, 20], np.int32)  # 40 steps on: every live slot crosses two or three pages
ENDED = 2  # its table row is the trash page: no kernel lists it, its output is not read
LIVE = [s for s in range(S) if s != ENDED]


@pytest.fixture(autouse=True)
def interpreted(monkeypatch):
    for mod, name in ((q8mod, "paged_attention_stacked"), (kvw, "paged_kv_write"), (ssu, "ssm_state_update_stacked")):
        monkeypatch.setattr(mod, name, functools.partial(getattr(mod, name), interpret=True))


def table() -> jnp.ndarray:
    pt = 1 + np.arange(S * WP, dtype=np.int32).reshape(S, WP)
    pt[ENDED] = 0
    return jnp.asarray(pt)


def greedy_twin(model, params, cfg, cache, **kw):
    """Tokens [STEPS, S] and the final cache of both paths."""
    out = {}
    for uk in (True, False):

        @jax.jit
        def step(cache, ids, pos, uk=uk):
            hid, cache = model.forward_decode_paged(params, cfg, ids, pos, cache, table(), page_size=PSZ, use_kernel=uk, **kw)
            return jnp.argmax(model.compute_logits(params, cfg, hid), -1).astype(jnp.int32), cache

        c, ids, toks = dict(cache), jnp.asarray([3, 5, 7, 9], jnp.int32), []
        for t in range(STEPS):
            ids, c = step(c, ids, jnp.asarray(START + t))
            toks.append(np.asarray(ids))
        out[uk] = (np.stack(toks), jax.tree.map(np.asarray, c))
    return out


def assert_twin(out, atol):
    np.testing.assert_array_equal(out[True][0][:, LIVE], out[False][0][:, LIVE])
    for name in ("k", "v"):
        got, want = out[True][1][name], out[False][1][name]
        # every page but the trash page: the gather path's scatters put the ended slot's rows there
        np.testing.assert_allclose(got[:, :, 1:].astype(np.float32), want[:, :, 1:].astype(np.float32), atol=atol)
        assert np.abs(want[:, :, 1:].astype(np.float32)).max() > 0


@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
def test_qwen_forty_greedy_steps_across_pages(quant):
    cfg = qwen.ModelConfig(
        vocab_size=256, hidden_size=128, intermediate_size=256, num_layers=2, num_heads=8, num_kv_heads=2,
        head_dim=16, dtype="float32", tie_word_embeddings=True,
    )
    params = qwen.init_params(jax.random.PRNGKey(0), cfg)
    cache = paged_kv.init_paged_cache(cfg, S * WP + 1, PSZ, quant=quant)
    out = greedy_twin(qwen, params, cfg, cache)
    # int8 pages: a scale that differs in its last bit moves a stored value by one step
    assert_twin(out, atol=1.01 if quant else 1e-4)
    if quant:
        np.testing.assert_allclose(out[True][1]["k_scale"][:, :, 1:], out[False][1]["k_scale"][:, :, 1:], rtol=1e-4)


def test_hybrid_forty_greedy_steps_across_pages():
    hu.load_run()
    from benchlib import hybrid_weights

    cfg = hu.tiny_model(("mamba", "attention", "mamba", "attention"))
    cfg.update(mamba_d_state=128)  # the state kernel's tile has the state dimension on the 128 lanes
    mcfg = hu.model_config(cfg)
    params = hybrid_weights.make_params(cfg, 5, jnp.float32)
    cache = paged_kv.init_paged_cache(mcfg, S * WP + 1, PSZ, slots=S)
    active = jnp.asarray([s != ENDED for s in range(S)])
    out = greedy_twin(hybrid, params, mcfg, cache, active=active)
    assert_twin(out, atol=1e-4)
    for name in (n for n in paged_kv.STATE_LEAVES if n in cache):  # this family's: ``ssm`` and ``conv``
        np.testing.assert_allclose(out[True][1][name], out[False][1][name], rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_no_tile_is_set_before_it_has_landed(monkeypatch, quant):
    """The writer under the TPU interpreter, which carries a copy out only
    when something waits for it (``dma_execution_mode="on_wait"``) and
    watches for races. A pool's reads share one semaphore, which counts
    bytes and not copies, so one wait does not say WHICH tile has landed: a
    body that sets a tile's row after one wait puts back a tile that had
    not come (this run then leaves other bits than the scatters: seen with
    such a body, PERF.md PR 29 review round). All reads are waited out
    before the first tile is touched."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental.pallas import tpu as pltpu

    params = pltpu.InterpretParams(detect_races=True, dma_execution_mode="on_wait")
    monkeypatch.setattr(kvw, "paged_kv_write", functools.partial(kvw.paged_kv_write, interpret=params))
    L, KH, N, psz, hd = 2, 2, 7, 128, 128
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    cache = {n: jax.random.normal(key, (L, KH, N, psz, hd), jnp.float32) for n, key in zip("kv", keys)}
    if quant:
        for n in "kv":
            cache[n], cache[f"{n}_scale"] = paged_kv.quantize_pages(cache[n], dtype=jnp.int8)
    else:
        cache = {n: x.astype(jnp.bfloat16) for n, x in cache.items()}
    k, v = (jax.random.normal(key, (6, KH, hd), jnp.bfloat16) for key in keys[2:])
    page = jnp.asarray([1, 2, 0, 3, 0, 4], jnp.int32)
    off = jnp.asarray([0, 37, 5, psz - 1, 5, 64], jnp.int32)
    got = paged_kv.write_decode_rows(cache, jnp.int32(1), k, v, page, off, q8mod.live_order(page != 0))
    want = paged_kv.write_decode_rows(cache, jnp.int32(1), k, v, page, off)
    for n in want:
        assert np.array_equal(np.asarray(got[n][:, :, 1:]).view(np.uint8), np.asarray(want[n][:, :, 1:]).view(np.uint8)), n
    assert not interpret_pallas_call.races.races_found
