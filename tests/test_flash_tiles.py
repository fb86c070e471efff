"""Tiles of the train step's flash kernels (ops/attention.py, ISSUE 27):
chosen from the row length and head_dim alone, valid for the kernels of
``ops/flash_kernels.py``, shared by ``flash_train`` and ``flash_fwd_pallas``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.ops import attention, flash_kernels
from areal_tpu.tools.kernelcheck import _packed_mask


@pytest.mark.parametrize("L", [128, 1024, 4096, 4224, 4608, 8192, 32768])
def test_tiles_divide_the_row_and_build_block_sizes(L, monkeypatch):
    for head_dim in (128, 256):
        tiles = attention.flash_tiles(L, head_dim)
        cap = attention.FLASH_TILE_EDGES[0] * 128 // head_dim
        for edge in tiles:
            assert L % edge == 0 and 128 <= edge <= cap, (L, head_dim, tiles)
        # the kernels want (query, key) edges, multiples of 128 that divide L
        bs = attention.flash_block_sizes(tiles)
        assert bs._fields == tiles._fields == ("fwd", "dkv", "dq")
        for kernel, (block_q, block_k) in bs._asdict().items():
            assert block_q == block_k == getattr(tiles, kernel)
            flash_kernels._check_blocks(kernel, L, block_q, block_k)
    # a default-configured row (bucket_step 512) never gets the library's 128
    if L % 512 == 0:
        assert min(attention.flash_tiles(L, 128)) >= 512
    # a length that is only a multiple of 128 gets tiles of 128 at worst
    if L % 256:
        assert set(attention.flash_tiles(L, 128)) == {128}
    # the kernel is chosen where the probe saw it win: from 512 at tiles of
    # 512 and up, from 4096 whatever the tiles (and never off a TPU)
    assert attention.resolve_impl("pallas", L, 128) == "xla"
    monkeypatch.setattr(attention.jax, "default_backend", lambda: "tpu")
    wins = L >= 4096 or (L >= 512 and L % 512 == 0)
    assert attention.resolve_impl("pallas", L, 128) == ("pallas" if wins else "xla")
    assert attention.resolve_impl("pallas", L, 64) == "xla"


def test_flash_fwd_pallas_at_its_default_tiles_matches_xla():
    """L=1024 at head 128 takes one 1024 x 1024 tile by default; the segment
    boundary (at 300) and the padded tail (from 900) fall inside it."""
    G, L, H, d = 1, 1024, 2, 128
    assert attention.flash_tiles(L, d).fwd == 1024
    seg = np.zeros((G, L), np.int32)
    seg[:, :300], seg[:, 300:900] = 1, 2
    q, k, v = (
        jax.random.normal(jax.random.PRNGKey(i), (G, L, H, d), jnp.float32)
        for i in range(3)
    )
    got = attention.flash_fwd_pallas(q, k, v, jnp.asarray(seg), interpret=True)
    want = attention.sdpa_xla(q, k, v, jnp.asarray(_packed_mask(seg)), d)
    live = (seg != 0)[:, :, None, None]  # a padded row's output is unspecified
    np.testing.assert_allclose(
        np.where(live, got, 0), np.where(live, want, 0), atol=2e-4
    )
    # and an explicit smaller tile gives the same rows
    tiled = attention.flash_fwd_pallas(
        q, k, v, jnp.asarray(seg), blk_q=256, blk_k=128, interpret=True
    )
    np.testing.assert_allclose(np.where(live, tiled, 0), np.where(live, got, 0), atol=2e-4)
