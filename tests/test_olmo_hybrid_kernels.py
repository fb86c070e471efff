"""The delta-rule state kernel (``ops/gdn_state_update.py``: live slots only,
in place, two heads a tile) under the TPU interpreter on the CPU, against the
``jnp`` step of the same recurrence, and a whole decode step of the
``olmo_hybrid`` family on its three kernels (paged attention at query group
1, the KV writer, the state kernel) against the gather path. The compiled
kernel at the published sizes is in ``tests/test_tpu_compile.py``.

Tolerances: float32 on both sides with the two sums over the key dimension in
another order: 1e-5. A slot that is not live is compared bit for bit. With a
bfloat16 state (the precision tool's) both sides round the same float32
value: one bfloat16 ulp."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))
import chipbench_olmo_util as ou  # noqa: E402
from chipbench_util import load_run  # noqa: E402

from areal_tpu.inference import paged_kv  # noqa: E402
from areal_tpu.models import hybrid  # noqa: E402
from areal_tpu.ops import gdn_state_update as gsu  # noqa: E402
from areal_tpu.ops.paged_attention_q8 import live_order  # noqa: E402
from tests.family_harness import decode_step  # noqa: E402


@pytest.mark.parametrize("dtype,H,K,V", [("float32", 4, 24, 64), ("float32", 2, 16, 128), ("float32", 6, 8, 192), ("bfloat16", 4, 24, 64)])
def test_state_kernel_matches_the_jnp_step(dtype, H, K, V):
    """Packs of 2 heads a tile (V = 64 and 192) and of 1 (V = 128); beta up
    to 2 (``linear_allow_neg_eigval``: above 1 the write overshoots the
    read), decays from 0 to 1."""
    L, S = 3, 6
    p = gsu.head_pack(H, V)
    assert p == (1 if V == 128 else 2)
    ks = jax.random.split(jax.random.PRNGKey(H + V), 6)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    state = jax.random.normal(ks[0], (L, S, H, K, V), jnp.float32).astype(dtype)
    q, k = unit(jax.random.normal(ks[1], (S, H, K))) * K**-0.5, unit(jax.random.normal(ks[2], (S, H, K)))
    v = jax.random.normal(ks[3], (S, H, V))
    alpha, beta = jax.random.uniform(ks[4], (S, H)), 2 * jax.random.uniform(ks[5], (S, H))
    assert float(beta.max()) > 1.5
    packed = gsu.pack_state(state, p)
    assert np.array_equal(np.asarray(gsu.unpack_state(packed, p), np.float32), np.asarray(state, np.float32))
    new, o_all = hybrid.gdn_decode_step(state[1], q, k, v, alpha, beta, jnp.ones((S,), bool))
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == "float32" else dict(rtol=1e-2, atol=1e-2)
    for mask in ([1, 0, 1, 1, 0, 0], [0] * 6, [1] * 6, [0, 0, 0, 0, 1, 0]):
        active = jnp.asarray(mask, bool)
        out, o = gsu.gdn_state_update_stacked(packed, 1, q, k, v, alpha, beta, *live_order(active), interpret=True)
        assert out.dtype == packed.dtype and out.shape == packed.shape
        got = gsu.unpack_state(out, p)
        np.testing.assert_allclose(np.asarray(got[1][active], np.float32), np.asarray(new[active], np.float32), **tol)
        np.testing.assert_allclose(np.asarray(o[active]), np.asarray(o_all[active]), rtol=1e-4, atol=1e-5)
        assert not np.asarray(o[~active]).any()
        for layer in (0, 2):  # other layers, and the slots that are not live: untouched, bit for bit
            assert np.array_equal(np.asarray(out[layer], np.float32), np.asarray(packed[layer], np.float32))
        assert np.array_equal(np.asarray(out[1][~active], np.float32), np.asarray(packed[1][~active], np.float32))


def test_decode_step_with_the_kernels_matches_the_gather_path(monkeypatch):
    """One decode step of the tiny model (head_dim 128, query group 1) on the
    three kernels against the gather path: logits of the live slots, every
    state leaf, the ended slot bit for bit, and the update count."""
    import areal_tpu.ops.paged_attention_q8 as q8mod
    import areal_tpu.ops.paged_kv_write as kvw

    load_run()
    monkeypatch.setattr(q8mod, "paged_attention_stacked", functools.partial(q8mod.paged_attention_stacked, interpret=True))
    monkeypatch.setattr(gsu, "gdn_state_update_stacked", functools.partial(gsu.gdn_state_update_stacked, interpret=True))
    monkeypatch.setattr(kvw, "paged_kv_write", functools.partial(kvw.paged_kv_write, interpret=True))
    cfg = ou.tiny_model()
    cfg["assumed"]["head_dim"] = 128
    mcfg, params = ou.model_config(cfg), ou.make_params(cfg, 5)
    S, psz, wp = 4, 16, 2
    cache = paged_kv.init_paged_cache(mcfg, S * wp + 1, psz, slots=S)
    rng = jax.random.split(jax.random.PRNGKey(2), 4)
    cache["gdn"] = 0.1 * jax.random.normal(rng[0], cache["gdn"].shape)
    cache["conv"] = jax.random.normal(rng[1], cache["conv"].shape)
    cache["k"] = jax.random.normal(rng[2], cache["k"].shape)
    cache["v"] = jax.random.normal(rng[3], cache["v"].shape)
    pt = jnp.asarray(1 + np.arange(S * wp).reshape(S, wp), jnp.int32).at[2].set(0)  # slot 2 ended: trash page
    ids, pos = jnp.asarray([3, 5, 7, 9], jnp.int32), jnp.asarray([4, 9, 14, 19], jnp.int32)
    active = jnp.asarray([True, True, False, True])
    outs = {}
    for uk in (True, False):
        c = {**cache, "gdn_updates": jnp.zeros((6,), jnp.int32)}
        logits, new = decode_step(mcfg, psz, uk)(params, ids, pos, c, pt, active)
        outs[uk] = (np.asarray(logits), jax.tree.map(np.asarray, new))
    live = np.asarray(active)
    np.testing.assert_allclose(outs[True][0][live], outs[False][0][live], rtol=1e-4, atol=1e-5)
    for name in ("gdn", "conv"):
        np.testing.assert_allclose(outs[True][1][name], outs[False][1][name], rtol=1e-5, atol=1e-5)
        for uk in (True, False):
            assert np.array_equal(outs[uk][1][name][:, 2], np.asarray(cache[name][:, 2]))  # the ended slot, bit for bit
            assert outs[uk][1]["gdn_updates"].tolist() == [3] * 6  # live slots only, every delta-rule layer
