"""The hybrid family's kernel path in interpret mode on the CPU: the
recurrent-state kernel (``ops/ssm_state_update.py``: live slots only, in
place) against the masked XLA form of the same recurrence, and a whole decode
step with both of the family's kernels (paged attention over lane-padded
heads with the configuration's softmax scale, the state kernel) against the
gather path. The compiled kernels at the published sizes are in
``tests/test_tpu_compile.py``.

Tolerances: float32 on both sides with the sum over the state dimension in
another order: 1e-5 relative. A slot that is not live is compared bit for
bit. With a bfloat16 state (the output check's control) both sides round the
same float32 value, so they agree to one bfloat16 ulp."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))
import chipbench_hybrid_util as hu  # noqa: E402

from areal_tpu.inference import paged_kv  # noqa: E402
from areal_tpu.models import hybrid  # noqa: E402
from areal_tpu.ops import ssm_state_update as ssu  # noqa: E402
from areal_tpu.ops.paged_attention_q8 import live_order  # noqa: E402


@pytest.mark.parametrize("dtype,groups", [("float32", 1), ("float32", 2), ("bfloat16", 1)])
def test_state_kernel_matches_the_masked_recurrence(dtype, groups):
    L, S, H, P, N = 3, 6, 4, 8, 128
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    ssm = jax.random.normal(ks[0], (L, S, H, P, N), jnp.float32).astype(dtype)
    x = jax.random.normal(ks[1], (S, H, P))
    b, c = jax.random.normal(ks[2], (S, groups, N)), jax.random.normal(ks[3], (S, groups, N))
    dt, a = jax.nn.softplus(jax.random.normal(ks[4], (S, H))), -jnp.exp(jax.random.normal(ks[5], (H,)))
    bh, ch = jnp.repeat(b, H // groups, 1), jnp.repeat(c, H // groups, 1)
    new = ssm[1].astype(jnp.float32) * jnp.exp(dt * a)[..., None, None] + (dt[..., None] * x)[..., None] * bh[:, :, None, :]
    y_all = jnp.sum(new * ch[:, :, None, :], -1)
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == "float32" else dict(rtol=1e-2, atol=1e-2)
    for mask in ([1, 0, 1, 1, 0, 0], [0] * 6, [1] * 6, [0, 0, 0, 0, 1, 0]):
        active = jnp.asarray(mask, bool)
        out, y = ssu.ssm_state_update_stacked(ssm, 1, x, b, c, dt, a, *live_order(active), interpret=True)
        assert out.dtype == ssm.dtype
        np.testing.assert_allclose(np.asarray(out[1][active], np.float32), np.asarray(new[active]), **tol)
        np.testing.assert_allclose(np.asarray(y[active]), np.asarray(y_all[active]), rtol=1e-4, atol=1e-4)
        assert not np.asarray(y[~active]).any()
        for layer in (0, 2):  # other layers, and the slots that are not live: untouched
            assert np.array_equal(np.asarray(out[layer], np.float32), np.asarray(ssm[layer], np.float32))
        assert np.array_equal(np.asarray(out[1][~active], np.float32), np.asarray(ssm[1][~active], np.float32))


def test_decode_step_with_both_kernels_matches_the_gather_path(monkeypatch):
    import areal_tpu.ops.paged_attention_q8 as q8mod
    import areal_tpu.ops.paged_kv_write as kvw

    hu.load_run()
    from benchlib import hybrid_weights

    monkeypatch.setattr(q8mod, "paged_attention_stacked", functools.partial(q8mod.paged_attention_stacked, interpret=True))
    monkeypatch.setattr(ssu, "ssm_state_update_stacked", functools.partial(ssu.ssm_state_update_stacked, interpret=True))
    monkeypatch.setattr(kvw, "paged_kv_write", functools.partial(kvw.paged_kv_write, interpret=True))
    cfg = hu.tiny_model(("mamba", "attention", "mamba"))
    cfg.update(mamba_d_state=128)  # the kernel's tile has the state dimension on the 128 lanes
    mcfg = hu.model_config(cfg)
    params = hybrid_weights.make_params(cfg, 5, jnp.float32)
    S, psz, wp = 4, 16, 2
    cache = paged_kv.init_paged_cache(mcfg, S * wp + 1, psz, slots=S)
    rng = jax.random.split(jax.random.PRNGKey(2), 4)
    cache["ssm"] = jax.random.normal(rng[0], cache["ssm"].shape)
    cache["conv"] = jax.random.normal(rng[1], cache["conv"].shape)
    cache["k"] = jax.random.normal(rng[2], cache["k"].shape).at[..., 16:].set(0)
    cache["v"] = jax.random.normal(rng[3], cache["v"].shape).at[..., 16:].set(0)
    pt = jnp.asarray(1 + np.arange(S * wp).reshape(S, wp), jnp.int32).at[2].set(0)  # slot 2 ended: trash page
    ids, pos = jnp.asarray([3, 5, 7, 9], jnp.int32), jnp.asarray([4, 9, 14, 19], jnp.int32)
    active = jnp.asarray([True, True, False, True])
    outs = {}
    for uk in (True, False):
        hid, new = hybrid.forward_decode_paged(params, mcfg, ids, pos, dict(cache), pt, page_size=psz, active=active, use_kernel=uk)
        outs[uk] = (np.asarray(hybrid.compute_logits(params, mcfg, hid)), jax.tree.map(np.asarray, new))
    live = np.asarray(active)
    np.testing.assert_allclose(outs[True][0][live], outs[False][0][live], rtol=1e-4, atol=1e-5)
    for k in ("ssm", "conv"):
        np.testing.assert_allclose(outs[True][1][k], outs[False][1][k], rtol=1e-5, atol=1e-5)
        for uk in (True, False):
            assert np.array_equal(outs[uk][1][k][:, 2], np.asarray(cache[k][:, 2]))  # the ended slot, bit for bit
