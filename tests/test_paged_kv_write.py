"""``ops/paged_kv_write.py`` in interpret mode on the CPU: a decode step's KV
rows written by one launch over the live slots, held BIT FOR BIT to the
per-head XLA scatters it replaces on the chip (``paged_kv.write_decode_rows``
without a live list), over the whole pool. The compiled kernel at the cells'
shapes is in ``tests/test_tpu_compile.py``; on the chip
``kernelcheck --compiled --kernel paged_kv_write`` holds it to the same
scatters (PERF.md, PR 29).

At most 8 tests here: xdist's ``loadfile`` hands whole files to workers."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import areal_tpu.ops.paged_kv_write as kvw
from areal_tpu.inference import paged_kv
from areal_tpu.ops.paged_attention_q8 import live_order

L, PSZ, HD = 3, 128, 128


@pytest.fixture(autouse=True)
def interpreted(monkeypatch):
    monkeypatch.setattr(kvw, "paged_kv_write", functools.partial(kvw.paged_kv_write, interpret=True))


# ONE jit for the launch and one for the scatters (the layer is an argument): called eagerly, a Pallas launch is
# traced, lowered and compiled anew at every call
WRITE = jax.jit(paged_kv.write_decode_rows)


def make_cache(kh: int, n_pages: int, quant: bool, seed: int = 0) -> dict:
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    cache = {n: jax.random.normal(k, (L, kh, n_pages, PSZ, HD), jnp.float32) for n, k in zip("kv", ks)}
    if not quant:
        return {n: x.astype(jnp.bfloat16) for n, x in cache.items()}
    out = {}
    for n, x in cache.items():
        out[n], out[f"{n}_scale"] = paged_kv.quantize_pages(x, dtype=jnp.int8)
    return out


def rows(kh: int, slots: int, seed: int):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    return (jax.random.normal(k, (slots, kh, HD), jnp.bfloat16) for k in ks)


def bits(x) -> np.ndarray:
    return np.asarray(x).view(np.uint8)


def assert_same_pool(got: dict, want: dict, first_page: int = 0):
    assert sorted(got) == sorted(want)
    for n in want:
        assert got[n].dtype == want[n].dtype and got[n].shape == want[n].shape
        assert np.array_equal(bits(got[n][:, :, first_page:]), bits(want[n][:, :, first_page:])), n


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("kh", [2, 4, 8])
def test_writer_leaves_the_bits_the_scatters_leave(kh, quant):
    """The three cells' KV head counts, bf16 and int8 pages with their
    lane-major scales; row 0, an odd row and the last row of a page; two
    slots that share a page id only at the trash page, neither written (the
    trash page and every page no live slot names stay as they were); the
    first and the last layer of the stacked pool."""
    cache = make_cache(kh, 7, quant)
    table_head = jnp.asarray([1, 2, 0, 3, 0, 4], jnp.int32)  # slots 2 and 4 ended: trash page 0
    page = jnp.asarray([1, 2, 0, 3, 0, 4], jnp.int32)
    off = jnp.asarray([0, 37, 5, PSZ - 1, 5, 64], jnp.int32)
    live = live_order(table_head != 0)
    assert int(live[1]) == 4
    for layer in (0, L - 1):
        k, v = rows(kh, 6, seed=layer + 1)
        got = WRITE(cache, jnp.int32(layer), k, v, page, off, live)
        want = WRITE(cache, jnp.int32(layer), k, v, page, off)
        assert_same_pool(got, want, first_page=1)  # the scatters put the ended slots' rows in the trash page
        for n in cache:
            assert np.array_equal(bits(got[n][:, :, 0]), bits(cache[n][:, :, 0])), f"{n}: trash page written"
            assert np.array_equal(bits(got[n][:, :, 5:]), bits(cache[n][:, :, 5:])), f"{n}: a page nobody names"
            other = [i for i in range(L) if i != layer]
            assert np.array_equal(bits(got[n][jnp.asarray(other)]), bits(cache[n][jnp.asarray(other)])), f"{n}: another layer"
        # and the rows are there: what the gather path reads back
        kq = got["k"][layer, :, page[3], off[3]]
        if quant:
            kq = paged_kv.dequantize_kv(kq, got["k_scale"][layer, :, page[3], 0, off[3]][:, None], jnp.float32)
            np.testing.assert_allclose(np.asarray(kq), np.asarray(k[3], np.float32), atol=0.05)
        else:
            assert np.array_equal(bits(kq), bits(k[3]))


def test_last_row_of_a_page_then_the_next_pages_first_row():
    """Two steps of one slot across a page boundary, beside a neighbour
    whose tile the first slot's write must not touch."""
    cache = make_cache(2, 6, quant=False)
    table = np.asarray([[1, 2], [3, 4]], np.int32)
    got, want = cache, cache
    live = live_order(jnp.asarray([True, True]))
    for step, pos in enumerate(([PSZ - 1, 8], [PSZ, 9])):
        pos = np.asarray(pos)
        page, off = jnp.asarray(table[np.arange(2), pos // PSZ]), jnp.asarray(pos % PSZ, jnp.int32)
        k, v = rows(2, 2, seed=10 + step)
        got = WRITE(got, jnp.int32(1), k, v, page, off, live)
        want = WRITE(want, jnp.int32(1), k, v, page, off)
    assert_same_pool(got, want)
    assert not np.array_equal(bits(got["k"][1, :, 2, 0]), bits(cache["k"][1, :, 2, 0]))  # the next page's row 0


def test_no_live_slot_returns_the_pool_as_it_is():
    cache = make_cache(2, 4, quant=True)
    k, v = rows(2, 3, seed=3)
    page, off = jnp.asarray([1, 2, 3], jnp.int32), jnp.asarray([0, 1, 2], jnp.int32)
    got = WRITE(cache, jnp.int32(0), k, v, page, off, live_order(jnp.zeros(3, bool)))
    assert_same_pool(got, cache)
