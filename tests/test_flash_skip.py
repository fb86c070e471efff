"""The training flash kernels skip the tiles of a packed row that hold no
same-segment pair (ops/flash_kernels.py, ISSUE 33): output and gradients
EQUAL those of the same kernels with the predicate forced true, and agree
with ``sdpa_xla``. CPU, interpret mode, rows of 512 in tiles of 128."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.ops import attention, flash_kernels
from areal_tpu.tools.flash_attn_probe import CELL_LENGTHS
from areal_tpu.tools.kernelcheck import _out_and_grads, _packed_mask
from areal_tpu.utils import datapack

L, D, EDGE = 512, 128, 128
BLOCKS = attention.FlashBlocks((EDGE, EDGE), (EDGE, EDGE), (EDGE, EDGE))


def _row(*runs):
    """One row from (id, length) runs."""
    return np.concatenate([np.full(n, sid, np.int32) for sid, n in runs])


def _cell_scaled_down():
    """``grpo-packed-4k``'s 13 sequences at an eighth of their lengths,
    packed first-fit-decreasing into rows of 512 as the trainer packs."""
    lens = [n // 8 for n in CELL_LENGTHS]
    lens[0] += 3 * L - sum(lens)
    rows = datapack.ffd_allocate(lens, L, min_groups=1)
    return np.stack(
        [np.pad(_row(*((j + 1, lens[i]) for j, i in enumerate(row))), (0, L - sum(lens[i] for i in row))) for row in rows]
    )


LAYOUTS = {
    "cell_scaled_down": _cell_scaled_down,
    "one_sequence_a_row": lambda: np.ones((1, L), np.int32),
    "zero_padded_tail": lambda: _row((1, 200), (2, 150), (0, 162))[None],
    "unsorted_ids": lambda: _row((5, 100), (9, 156), (2, 200), (7, 56))[None],
    "boundary_on_a_tile_edge": lambda: _row((1, 128), (2, 256), (3, 128))[None],
    "two_rows_two_layouts": lambda: np.stack([_row((1, 300), (2, 212)), _row((1, 60), (2, 70), (3, 382))]),
}


def _inputs(seg, heads=1):
    shape = (*seg.shape, heads, D)
    return [jax.random.normal(jax.random.PRNGKey(i), shape, jnp.float32) for i in range(4)]


def _live_rows(attn, seg):
    # a padded row (segment 0) carries no loss in the trainer
    keep = jnp.asarray(seg != 0)[:, :, None, None]
    return lambda q, k, v: jnp.where(keep, attn(q, k, v), 0)


def _every_tile(monkeypatch):
    """Force the predicate true: ranges that overlap whatever the ids."""
    calls = []

    def ranges(segment_ids, edge):
        lo, hi = tile_ranges(segment_ids, edge)
        calls.append(edge)
        return lo * 0, hi * 0 + np.iinfo(np.int32).max

    tile_ranges = flash_kernels.tile_ranges
    monkeypatch.setattr(flash_kernels, "tile_ranges", ranges)
    return calls


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_skipping_kernels_equal_the_unskipped_and_agree_with_xla(layout, monkeypatch):
    seg = LAYOUTS[layout]()
    q, k, v, w = _inputs(seg)

    def train(q, k, v):
        # the skip tables are built here, from the ranges as they are (or as
        # patched below), and handed to the kernels
        mask = attention.flash_mask(jnp.asarray(seg), D, BLOCKS)
        return attention.flash_train(q, k, v, mask, interpret=True)

    got = np.asarray(_out_and_grads(_live_rows(train, seg), q, k, v, w))
    want = _out_and_grads(
        _live_rows(lambda q, k, v: attention.sdpa_xla(q, k, v, jnp.asarray(_packed_mask(seg)), D), seg), q, k, v, w
    )
    np.testing.assert_allclose(got, want, atol=2e-4)
    counts = attention.flash_tile_counts(seg, BLOCKS)
    calls = _every_tile(monkeypatch)
    unskipped = np.asarray(_out_and_grads(_live_rows(train, seg), q, k, v, w))
    assert calls, "the forced predicate was never asked"
    # array_equal up to the sign of zero: -0.0 == 0.0
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, unskipped):
        assert np.array_equal(a, b), (layout, name, np.abs(a - b).max())
    # and the layout is one the skip engages on, or a row of one sequence
    run, causal = counts["fwd"]
    assert run == causal if layout == "one_sequence_a_row" else run < causal, counts


@pytest.mark.parametrize("layout", ["cell_scaled_down", "zero_padded_tail"])
def test_forward_only_kernel_skips_the_same_tiles(layout, monkeypatch):
    """``flash_fwd_pallas`` takes the range test through the same helper;
    its ``seg_q != 0`` rule leaves a padded row's output unspecified."""
    seg = LAYOUTS[layout]()
    q, k, v, _ = _inputs(seg, heads=2)
    live = (seg != 0)[:, :, None, None]

    def fwd():
        return np.where(live, attention.flash_fwd_pallas(q, k, v, jnp.asarray(seg), EDGE, EDGE, interpret=True), 0)

    got = fwd()
    want = attention.sdpa_xla(q, k, v, jnp.asarray(_packed_mask(seg)), D)
    np.testing.assert_allclose(got, np.where(live, want, 0), atol=2e-4)
    assert _every_tile(monkeypatch) is not None and np.array_equal(got, fwd())
