"""The latent page's two Pallas launches under the interpreter, each against
its XLA path: ``paged_latent_attn`` (ops/paged_latent_attention.py) by the
registered kernelcheck grid, and ``paged_kv_write`` on a pool of ONE row a
token (a latent model's ``k`` alone), bit for bit against the scatter.
``tests/test_tpu_compile.py -k kanana2`` compiles both for the described
chip; ``kernelcheck --compiled`` runs the grid there."""

import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.inference import paged_kv
from areal_tpu.ops.paged_attention_q8 import live_order, paged_kernel_ok
from areal_tpu.tools import kernelcheck


def test_latent_attention_kernel_agrees_with_the_gather_path():
    assert "paged_latent_attention" in kernelcheck.REGISTRY
    results = kernelcheck.run_kernel("paged_latent_attention")
    assert len(results) == 3 and all(r["ok"] for r in results), results
    assert paged_kernel_ok(640, 128, False)  # the stored row is whole lane tiles: the compiled kernels serve it


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_one_latent_row_written_by_the_kernel_equals_the_scatter(dtype, monkeypatch):
    import functools

    import areal_tpu.ops.paged_kv_write as pkw

    monkeypatch.setattr(pkw, "paged_kv_write", functools.partial(pkw.paged_kv_write, interpret=True))
    L, S, N, psz, lanes = 3, 6, 9, 16, 256
    rng = np.random.default_rng(2)
    pool = jnp.asarray(rng.normal(size=(L, 1, N, psz, lanes)), dtype)
    rows = jnp.asarray(rng.normal(size=(S, 1, lanes)), dtype)
    live = np.array([True, False, True, True, False, True])
    page = jnp.asarray(np.where(live, 1 + rng.permutation(S), 0), jnp.int32)  # a page of its own a live slot
    off = jnp.asarray([0, 3, psz - 1, 7, 5, 8], jnp.int32)
    by_kernel = paged_kv.write_decode_rows({"k": pool}, jnp.int32(1), rows, None, page, off, live_order(jnp.asarray(live)))
    by_scatter = paged_kv.write_decode_rows({"k": pool}, jnp.int32(1), rows, None, page, off, None)
    assert set(by_kernel) == {"k"}
    got, want = np.asarray(by_kernel["k"], np.float32), np.asarray(by_scatter["k"], np.float32)
    assert np.array_equal(got[:, :, 1:], want[:, :, 1:])  # every page but the trash page, where the scatter sends an ended slot's row
    assert np.array_equal(got[:, :, 0], np.asarray(pool, np.float32)[:, :, 0])  # which the kernel leaves as it was
    changed = (got != np.asarray(pool, np.float32)).any(axis=-1)  # [L, 1, N, psz]
    assert changed.sum() == live.sum() and changed[1].sum() == live.sum()  # one row a live slot, in the named layer only
