"""Ring attention (context parallelism) tests — reference CP equivalence
(megatron packed context parallel) at unit scale."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.api.config import MeshConfig
from areal_tpu.models import qwen
from areal_tpu.parallel.mesh import make_mesh
from areal_tpu.parallel.ring_attention import ring_attention, zigzag_indices
from jax import set_mesh

from tpu_testing import TINY_QWEN2


def _ref_attention(q, k, v, seg, col):
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    mask = (
        (seg[:, :, None] == seg[:, None, :])
        & (seg[:, :, None] != 0)
        & (col[:, :, None] >= col[:, None, :])
    )[:, None]
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)


def _qkv(B=2, L=64, H=4, d=16, seed=0, packed=True):
    rng = np.random.default_rng(seed)
    q, k, v = (
        jnp.asarray(rng.normal(0, 1, (B, L, H, d)), jnp.float32) for _ in range(3)
    )
    if packed:
        seg = np.ones((B, L), np.int32)
        seg[0, L // 2 :] = 2  # two packed segments in row 0
        seg[1, L - 8 :] = 0  # padding tail in row 1
    else:
        seg = np.ones((B, L), np.int32)
    col = np.broadcast_to(np.arange(L, dtype=np.int32), (B, L)).copy()
    return q, k, v, jnp.asarray(seg), jnp.asarray(col)


@pytest.mark.multi_device
@pytest.mark.parametrize("sp", [2, 4, 8])
def test_ring_matches_reference(sp):
    q, k, v, seg, col = _qkv()
    ref = _ref_attention(q, k, v, seg, col)
    mesh = make_mesh(MeshConfig(data=1, fsdp=8 // sp, seq=sp, model=1))
    with set_mesh(mesh):
        out = jax.jit(lambda *a: ring_attention(*a))(q, k, v, seg, col)
    valid = np.asarray(seg) != 0  # padded queries have no defined output
    np.testing.assert_allclose(
        np.asarray(ref)[valid], np.asarray(out)[valid], atol=1e-5
    )


@pytest.mark.multi_device
def test_ring_zigzag_layout():
    """The 2-chunk-per-rank causal load-balance permutation must not change
    the result (explicit col indices make layout-independence exact)."""
    q, k, v, seg, col = _qkv(packed=False)
    ref = _ref_attention(q, k, v, seg, col)
    sp = 4
    perm = zigzag_indices(q.shape[1], sp)
    inv = np.argsort(perm)
    mesh = make_mesh(MeshConfig(data=1, fsdp=8 // sp, seq=sp, model=1))
    with set_mesh(mesh):
        out_p = jax.jit(lambda *a: ring_attention(*a))(
            q[:, perm], k[:, perm], v[:, perm], seg[:, perm], col[:, perm]
        )
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(out_p)[:, inv], atol=1e-5
    )


@pytest.mark.multi_device
def test_model_forward_ring_matches_xla():
    cfg_x = qwen.ModelConfig(**{**TINY_QWEN2.__dict__, "num_heads": 8})
    cfg_r = qwen.ModelConfig(**{**cfg_x.__dict__, "attn_impl": "ring"})
    params = qwen.init_params(jax.random.PRNGKey(0), cfg_x)
    rng = np.random.default_rng(0)
    G, L = 2, 64
    ids = jnp.asarray(rng.integers(1, 250, (G, L)), jnp.int32)
    seg = jnp.ones((G, L), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (G, L))

    ref = qwen.forward(params, cfg_x, ids, seg, pos)
    mesh = make_mesh(MeshConfig(data=1, fsdp=1, seq=4, model=2))
    with set_mesh(mesh):
        out = jax.jit(lambda p, i, s, po: qwen.forward(p, cfg_r, i, s, po))(
            params, ids, seg, pos
        )
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-4)


@pytest.mark.multi_device
def test_ring_gradients_flow():
    cfg_r = qwen.ModelConfig(
        **{**TINY_QWEN2.__dict__, "num_heads": 8, "attn_impl": "ring"}
    )
    params = qwen.init_params(jax.random.PRNGKey(1), cfg_r)
    rng = np.random.default_rng(1)
    G, L = 2, 32
    ids = jnp.asarray(rng.integers(1, 250, (G, L)), jnp.int32)
    seg = jnp.ones((G, L), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (G, L))

    def loss(p):
        h = qwen.forward(p, cfg_r, ids, seg, pos)
        return jnp.square(h.astype(jnp.float32)).mean()

    mesh = make_mesh(MeshConfig(data=1, fsdp=2, seq=4, model=1))
    with set_mesh(mesh):
        g = jax.jit(jax.grad(loss))(params)
    norms = [float(jnp.linalg.norm(x)) for x in jax.tree_util.tree_leaves(g)]
    assert all(np.isfinite(n) for n in norms)
    assert sum(norms) > 0
