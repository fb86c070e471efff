"""The sampler's draw over the partition of ``ops/vocab_block_stats.py``.

``_inverse_cdf_at`` (inference/decode_programs.py) is held to a float64 NumPy
inverse CDF of the flat row, with its block statistics summed both ways: by
the ``vocab_block_stats`` launch (and the chosen block read by
``vocab_block_pick``) under the Pallas interpreter and by the same partition
in ``jnp``. The rows are the cells' vocabularies (a partial last
block in all but 65,536) at both ends of the temperatures a request may ask
for. Then the launch's traced size, as tests/test_paged_decode_budget.py holds
``paged_decode_attn``'s: every sampling program traces it once a variant, and
a jitted ``jnp`` call inside a kernel body is a trace of its own (PERF.md,
PR 45). Counts and values, never times.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.inference import decode_programs
from areal_tpu.ops import vocab_block_stats as vbs
from tests.test_paged_decode_budget import count

# the benchmark's cells: Qwen2.5-1.5B, Qwen2.5-7B, Granite / Olmo, LFM2, one chip's share of Kanana-2's and
# GLM-5's, Phi-4-mini-flash
VOCABS = (151936, 152064, 100352, 65536, 19360, 200064)
N_U = 32  # uniforms a row is drawn at: a sweep, both ends in


@pytest.fixture(scope="module")
def draws():
    """(vocab, by kernel) -> the jitted draw, traced once a module (the first
    test to ask traces it under its own patch of the launch)."""
    return {}


def draw_fn(draws, monkeypatch, vocab: int, kernel: bool):
    # off a TPU the launch runs under the interpreter, as every ops/ kernel's CPU test has it
    for launch in ("vocab_block_stats", "vocab_block_pick"):
        monkeypatch.setattr(vbs, launch, functools.partial(getattr(vbs, launch), interpret=True))
    key = (vocab, kernel)
    if key not in draws:
        draws[key] = jax.jit(functools.partial(decode_programs._inverse_cdf_at, use_kernel=kernel))
    return draws[key]


def row_of(kind: str, vocab: int, rng) -> np.ndarray:
    width = vbs.block_width(vocab)
    tail = (-(-vocab // width) - 1) * width  # the last block's first column
    x = rng.normal(0.0, 2.0, vocab).astype(np.float32)
    if kind == "holes":  # what a mask leaves: whole blocks, single columns and the row's last columns at -1e30
        x[width : 3 * width] = -1e30
        x[rng.integers(0, vocab, vocab // 7)] = -1e30
        x[-5:] = -1e30
    elif kind == "dominant":  # one logit holds all but 1e-9 of the mass, on a block's last column
        x[2 * width - 1] = 40.0
    elif kind == "tail":  # all of the mass in the (partial) last block
        x[:tail] = -1e30
    return x


def uniforms() -> np.ndarray:
    u = np.linspace(0.0, 1.0, N_U, endpoint=False, dtype=np.float32)
    u[-1] = np.float32(1.0) - np.float32(2.0**-24)  # the largest a float32 uniform takes
    return u


@pytest.mark.parametrize("temperature", [1e-6, 10.0])
@pytest.mark.parametrize("kind", ["plain", "holes", "dominant", "tail"])
@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "jnp"])
@pytest.mark.parametrize("vocab", VOCABS)
def test_the_draw_is_the_flat_inverse_cdf(draws, monkeypatch, vocab, kernel, kind, temperature):
    rng = np.random.default_rng(vocab % 9973 + len(kind))
    scaled = row_of(kind, vocab, rng) / np.float32(temperature)  # as ``_sample_step`` hands it over
    u = uniforms()
    ids, logp, lse = draw_fn(draws, monkeypatch, vocab, kernel)(jnp.tile(scaled, (N_U, 1)), jnp.asarray(u)[:, None])
    ids, logp = np.asarray(ids), np.asarray(logp, np.float64)

    x = scaled.astype(np.float64)
    p = np.exp(x - x.max())
    cdf = np.cumsum(p) / p.sum()
    want = np.searchsorted(cdf, u.astype(np.float64), side="right")
    assert ((0 <= ids) & (ids < vocab)).all()
    assert (p[ids] > 0).all(), "a token without mass was drawn"
    # the reference's token, or one whose stretch of the CDF ends or begins within float32 of the uniform
    below = np.where(ids > 0, cdf[np.maximum(ids - 1, 0)], 0.0)
    near = (below - 2e-6 <= u) & (u <= cdf[ids] + 2e-6)
    assert ((ids == want) | near).all(), (ids[(ids != want) & ~near], want[(ids != want) & ~near])
    assert (ids == want).mean() >= 0.9
    np.testing.assert_allclose(logp, np.log(p[ids] / p.sum()), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(lse, np.float64)[:, 0], np.log(p.sum()) + x.max(), rtol=1e-6)


@pytest.mark.parametrize("vocab", VOCABS + (5, 300, 2048, 2049))
def test_the_launch_sums_the_blocks_the_jnp_form_sums(vocab):
    rng = np.random.default_rng(vocab)
    x = rng.normal(0.0, 3.0, (8, vocab)).astype(np.float32)
    x[1] = -1e30
    x[2, : vocab // 2] = -np.inf  # whole blocks without a finite logit
    got = jax.jit(functools.partial(vbs.vocab_block_stats, interpret=True))(x)
    ref = jax.jit(vbs.vocab_block_stats_xla)(x)
    nb = -(-vocab // vbs.block_width(vocab))
    assert nb <= vbs.LANES
    for a, b in zip(got, ref, strict=True):
        assert a.shape == (8, vbs.LANES) and a.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    assert np.isneginf(np.asarray(got[0])[:, nb:]).all() and not np.asarray(got[1])[:, nb:].any()
    # a block's own maximum counts 1 in its sum
    assert (np.asarray(got[1])[0, :nb] >= 1.0).all()


@pytest.mark.parametrize("slots,vocab", [(128, 151936), (64, 19360), (5, 4500), (12, 300)])
def test_the_pick_launch_reads_each_rows_own_block(slots, vocab):
    """Rows that fill whole tiles of 8 and rows that do not; the partial
    last block compared over the columns the row has."""
    rng = np.random.default_rng(slots)
    width = vbs.block_width(vocab)
    nb = -(-vocab // width)
    x = rng.normal(size=(slots, vocab)).astype(np.float32)
    block = rng.integers(0, nb, slots).astype(np.int32)
    block[:2] = nb - 1, 0
    got = np.asarray(jax.jit(functools.partial(vbs.vocab_block_pick, interpret=True))(x, block))
    ref = np.asarray(jax.jit(vbs.vocab_block_pick_xla)(x, block))
    assert got.shape == ref.shape == (slots, width)
    for s in range(slots):
        held = min(width, vocab - block[s] * width)
        np.testing.assert_array_equal(got[s, :held], x[s, block[s] * width :][:held])
        np.testing.assert_array_equal(ref[s, :held], got[s, :held])


@pytest.mark.parametrize("slots,vocab", [(128, 151936), (64, 200064), (64, 19360)])
def test_the_launches_hold_their_equation_budget(slots, vocab):
    """Both launches as the draw makes them: no jitted function inside a
    kernel or an index map, and the kernels no larger than pinned."""

    def both(x, block):
        return vbs.vocab_block_stats(x), vbs.vocab_block_pick(x, block)

    args = jax.ShapeDtypeStruct((slots, vocab), jnp.float32), jax.ShapeDtypeStruct((slots,), jnp.int32)
    jaxpr = jax.make_jaxpr(both)(*args).jaxpr
    launches = {e.params["name"]: e for e in jaxpr.eqns if e.primitive.name == "pallas_call"}
    assert set(launches) == {"vocab_block_stats", "vocab_block_pick"}
    pinned = {"vocab_block_stats": 40, "vocab_block_pick": 8}  # this PR's hold 34 and 4
    for name, e in launches.items():
        traced = [e.params["jaxpr"], *(m.index_map_jaxpr.jaxpr for m in e.params["grid_mapping"].block_mappings)]
        assert sum(count(j, "jit") + count(j, "pjit") for j in traced) == 0
        n = count(e.params["jaxpr"])
        assert n <= pinned[name], f"{n} equations in {name}'s kernel, over the {pinned[name]} pinned"
    text = jax.jit(both).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") >= 2 and "vocab_block_stats" in text and "vocab_block_pick" in text
