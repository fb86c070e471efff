"""Ulysses sequence parallelism tests (reference
tests/test_fsdp_ulysses_forward.py / tests/torchrun/run_ulysses*.py role):
seq-mesh forward must match the single-device result, and the compiled HLO
must reshard via all-to-all (not all-gather of the full activation)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.api.config import MeshConfig
from areal_tpu.models import qwen
from areal_tpu.parallel.mesh import make_mesh
from jax import set_mesh

from tpu_testing import TINY_QWEN2


def _inputs(G=2, L=32, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(1, 250, (G, L)).astype(np.int32)
    seg = np.ones((G, L), np.int32)
    pos = np.broadcast_to(np.arange(L, dtype=np.int32), (G, L)).copy()
    return jnp.asarray(ids), jnp.asarray(seg), jnp.asarray(pos)


@pytest.fixture(scope="module")
def params():
    # 8 heads so seq=4 (> kv_heads=2) exercises GQA head replication
    cfg = qwen.ModelConfig(**{**TINY_QWEN2.__dict__, "num_heads": 8})
    return cfg, qwen.init_params(jax.random.PRNGKey(0), cfg)


@pytest.mark.multi_device
@pytest.mark.parametrize("mesh_cfg", [
    MeshConfig(data=1, fsdp=1, seq=4, model=2),
    MeshConfig(data=1, fsdp=2, seq=4, model=1),
    MeshConfig(data=1, fsdp=1, seq=8, model=1),
])
def test_seq_parallel_matches_single_device(params, mesh_cfg):
    cfg, p = params
    ids, seg, pos = _inputs()
    ref = qwen.forward(p, cfg, ids, seg, pos)

    mesh = make_mesh(mesh_cfg)
    with set_mesh(mesh):
        out = jax.jit(lambda p, i, s, po: qwen.forward(p, cfg, i, s, po))(
            p, ids, seg, pos
        )
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-4)


@pytest.mark.multi_device
def test_ulysses_uses_all_to_all(params):
    """The seq<->head reshard must compile to all-to-all collectives."""
    cfg, p = params
    ids, seg, pos = _inputs()
    mesh = make_mesh(MeshConfig(data=1, fsdp=1, seq=8, model=1))
    with set_mesh(mesh):
        lowered = jax.jit(
            lambda p, i, s, po: qwen.forward(p, cfg, i, s, po)
        ).lower(p, ids, seg, pos)
        hlo = lowered.compile().as_text()
    assert "all-to-all" in hlo, "Ulysses reshard did not lower to all-to-all"


@pytest.mark.multi_device
def test_seq_parallel_grads_match(params):
    cfg, p = params
    ids, seg, pos = _inputs()

    def loss(p):
        h = qwen.forward(p, cfg, ids, seg, pos)
        return jnp.square(h.astype(jnp.float32)).mean()

    g_ref = jax.grad(loss)(p)
    mesh = make_mesh(MeshConfig(data=1, fsdp=1, seq=4, model=2))
    with set_mesh(mesh):
        g_sp = jax.jit(jax.grad(loss))(p)
    flat_ref = jax.tree_util.tree_leaves(g_ref)
    flat_sp = jax.tree_util.tree_leaves(jax.tree.map(np.asarray, g_sp))
    for a, b in zip(flat_ref, flat_sp):
        np.testing.assert_allclose(np.asarray(a), b, atol=3e-4)
