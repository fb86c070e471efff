"""GPipe pipeline parallelism (parallel/pipeline.py) on a virtual mesh:
forward and gradient parity against the plain layers scan. PP on TPU is
deliberately NOT the train engine's default (GSPMD sharding covers the
reference's PP use cases within a pod — SURVEY §7.1); this pins that the
mechanism itself is correct for the cases that want stage partitioning."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from areal_tpu.parallel.pipeline import gpipe

L, D, B, M, S = 8, 16, 4, 6, 4  # layers, width, batch, microbatches, stages


def _layer_fn(x, layer):
    w, b = layer
    return jnp.tanh(x @ w + b)


@pytest.fixture(scope="module")
def setup():
    rng = np.random.default_rng(0)
    ws = jnp.asarray(rng.normal(0, 0.5, (L, D, D)).astype(np.float32))
    bs = jnp.asarray(rng.normal(0, 0.1, (L, D)).astype(np.float32))
    x = jnp.asarray(rng.normal(0, 1, (M, B, D)).astype(np.float32))
    devs = jax.devices()[:S]
    mesh = Mesh(np.array(devs).reshape(S), ("stage",))
    return ws, bs, x, mesh


def _reference(ws, bs, x):
    def body(carry, layer):
        return _layer_fn(carry, layer), None

    def per_micro(xm):
        y, _ = jax.lax.scan(body, xm, (ws, bs))
        return y

    return jax.vmap(per_micro)(x)


def _pipelined(ws, bs, x, mesh):
    fn = gpipe(_layer_fn, n_stages=S, n_microbatches=M, axis_name="stage")
    mapped = shard_map(
        fn,
        mesh=mesh,
        in_specs=((P("stage"), P("stage")), P()),
        out_specs=P(),
        check_vma=False,
    )
    return mapped((ws, bs), x)


def test_forward_parity(setup):
    ws, bs, x, mesh = setup
    want = _reference(ws, bs, x)
    got = _pipelined(ws, bs, x, mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_grad_parity(setup):
    """jax.grad differentiates through the fill-drain schedule's collectives
    — the backward pipeline comes from AD, not hand-written schedule code."""
    ws, bs, x, mesh = setup

    def loss_ref(ws, bs):
        return jnp.mean(_reference(ws, bs, x) ** 2)

    def loss_pipe(ws, bs):
        return jnp.mean(_pipelined(ws, bs, x, mesh) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1))(ws, bs)
    g_pipe = jax.grad(loss_pipe, argnums=(0, 1))(ws, bs)
    for a, b in zip(g_ref, g_pipe):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-5)


def test_uneven_microbatches_and_stages(setup):
    """M not a multiple of S and a 2-stage split both schedule correctly."""
    ws, bs, x, mesh_full = setup
    devs = jax.devices()[:2]
    mesh = Mesh(np.array(devs).reshape(2), ("stage",))
    fn = gpipe(_layer_fn, n_stages=2, n_microbatches=M, axis_name="stage")
    got = shard_map(
        fn,
        mesh=mesh,
        in_specs=((P("stage"), P("stage")), P()),
        out_specs=P(),
        check_vma=False,
    )((ws, bs), x)
    want = _reference(ws, bs, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
