"""The range test behind the flash kernels' tile skip (ISSUE 33) against a
brute-force "any equal pair": it never skips a live tile; the host's
counter counts what the kernels' own predicate runs."""

from types import SimpleNamespace

import numpy as np
import pytest

from areal_tpu.engine.train_engine import JaxTrainEngine
from areal_tpu.observability.catalog import train_obs_metrics
from areal_tpu.observability.metrics import Registry
from areal_tpu.ops import attention, flash_kernels
from areal_tpu.tools.flash_attn_probe import lay_out


def _any_equal_pair(seg, block_q, block_k):
    """[G, nq, nk] bool by brute force: the tile holds a (query, key) pair
    of one segment with key <= query."""
    G, L = seg.shape
    pair = (seg[:, :, None] == seg[:, None, :]) & (np.arange(L)[:, None] >= np.arange(L)[None, :])
    return pair.reshape(G, L // block_q, block_q, L // block_k, block_k).any(axis=(2, 4))


def _random_ids(seed, G=3, L=1024):
    """Runs of random length with random ids: unsorted, repeated, zeros."""
    rng = np.random.default_rng(seed)
    seg = np.zeros((G, L), np.int32)
    for g in range(G):
        at = 0
        while at < L:
            n = int(rng.integers(1, 300))
            seg[g, at : at + n] = rng.integers(0, 6)
            at += n
    return seg


@pytest.mark.parametrize("edges", [(128, 128), (256, 128), (128, 512)])
def test_range_test_never_skips_a_live_tile(edges):
    for seed in range(5):
        seg = _random_ids(seed)
        live = flash_kernels.live_tiles(seg, *edges)
        assert not (_any_equal_pair(seg, *edges) & ~live).any(), (seed, edges)
        # what the kernels are handed is the counted verdict, and a skipped
        # step holds the block of a tile that runs
        for outer, tiles in (("q", live), ("k", live.transpose(0, 2, 1))):
            run, block = (np.asarray(t).reshape(tiles.shape) for t in flash_kernels.skip_operands(seg, *edges, outer=outer))
            assert np.array_equal(run != 0, tiles)
            inner = np.broadcast_to(np.arange(tiles.shape[2]), tiles.shape)
            assert np.array_equal(block[tiles], inner[tiles])
            held = np.take_along_axis(tiles, block, axis=2) | np.take_along_axis(np.roll(tiles, -1, axis=1), block, axis=2)
            assert held.all(), (seed, edges, outer)
    # ids as pack_grid writes them (1, 2, ... then a tail of 0): the range test is exact
    for segments in ("cell", "300,500,90"):
        seg = lay_out(segments, 4096, seed=3)
        assert np.array_equal(flash_kernels.live_tiles(seg, 512, 512), _any_equal_pair(seg, 512, 512))


@pytest.mark.parametrize("segments,skips", [("cell", True), ("one", False), ("300,500,90", True)])
def test_counter_counts_the_tiles_that_hold_a_pair(segments, skips, monkeypatch):
    """``areal_train_attn_tiles_{run,causal}_total`` from a step's grids:
    the brute-force count at ``flash_tiles``' edges, run = causal on rows
    of one sequence, nothing where the flash kernel does not run."""
    seg = lay_out(segments, 4096, seed=11)
    tiles = attention.flash_tiles(4096, 128)
    obs = train_obs_metrics(Registry())
    engine = SimpleNamespace(model_cfg=SimpleNamespace(attn_impl="pallas", head_dim_=128), _obs=obs)
    grids = [SimpleNamespace(data={"segment_ids": seg})]

    def counted():
        return {
            kernel: (int(obs.attn_tiles_run.labels(kernel=kernel).get()), int(obs.attn_tiles_causal.labels(kernel=kernel).get()))
            for kernel in tiles._fields
        }

    JaxTrainEngine._count_attn_tiles(engine, grids)  # on the CPU the step runs XLA attention
    assert counted() == {kernel: (0, 0) for kernel in tiles._fields}
    monkeypatch.setattr(attention.jax, "default_backend", lambda: "tpu")
    JaxTrainEngine._count_attn_tiles(engine, grids)
    want = {}
    for kernel, edge in tiles._asdict().items():
        n = 4096 // edge
        want[kernel] = (int(_any_equal_pair(seg, edge, edge).sum()), seg.shape[0] * n * (n + 1) // 2)
        assert (want[kernel][0] < want[kernel][1]) == skips, (kernel, want)
    assert counted() == want


def test_model_builds_the_mask_once_and_trains_through_the_kernels(monkeypatch):
    """``qwen.forward`` on the flash path (forced here: a CPU says ``xla``)
    hands every layer of its scan, under ``jax.checkpoint``, one
    ``FlashMask`` built before it; hidden states and parameter gradients
    agree with the XLA path on a row whose ``dq`` tiles are partly skipped."""
    import functools

    import jax
    import jax.numpy as jnp

    from areal_tpu.models import qwen

    cfg = qwen.ModelConfig(
        vocab_size=64, hidden_size=256, intermediate_size=128, num_layers=2, num_heads=2, num_kv_heads=1,
        head_dim=128, dtype="float32", attn_impl="pallas", remat=True,
    )
    L = 1024
    params = qwen.init_params(jax.random.PRNGKey(0), cfg)
    seg = np.concatenate([np.full(512, 1), np.full(300, 2), np.zeros(212)]).astype(np.int32)[None]
    pos = np.concatenate([np.arange(512), np.arange(300), np.zeros(212)]).astype(np.int32)[None]
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, L), 0, 64)
    assert attention.flash_tile_counts(seg, attention.flash_block_sizes(attention.flash_tiles(L, 128)))["dq"] == (2, 3)

    def loss(params):
        hidden = qwen.forward(params, cfg, ids, jnp.asarray(seg), jnp.asarray(pos))
        return (jnp.where(jnp.asarray(seg != 0)[..., None], hidden, 0) ** 2).mean()

    want, want_grads = jax.value_and_grad(loss)(params)
    built = []
    flash_mask = attention.flash_mask
    monkeypatch.setattr(attention, "flash_mask", lambda *a, **k: built.append(a) or flash_mask(*a, **k))
    monkeypatch.setattr(attention, "flash_train", functools.partial(attention.flash_train, interpret=True))
    monkeypatch.setattr(attention.jax, "default_backend", lambda: "tpu")
    got, got_grads = jax.value_and_grad(loss)(params)
    assert len(built) == 1, "the skip tables belong outside the scan over layers"
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-3)
