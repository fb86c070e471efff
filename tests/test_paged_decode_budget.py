"""The traced size of ``paged_decode_attn``'s launch, held under a budget.

A start-up traces and lowers the decode chunk program once, and in it one
launch a site (a run or a period of layers: 1-4 a model); jax's tracing and
lowering is time no compile cache saves (PERF.md, section 5, "Set-up
decomposed"). PR 44's kernel, which fetched a shared block once as this one
does, was refused for the 0.4 s it added to every (site x KV head) of that
trace: 48.5 s of set-up at olmo's 4 sites x 30 heads. So the launch's
equations are counted here, through nested jaxprs, at the five cells' shapes,
against the launch that fetched every block a slot (PR 43's, the figures
beside each): at most 2.5 times as many (ISSUE 45's budget; this PR's launch
holds 1.24 to 1.58 times), the per-head code (its two matmuls) in two shapes
and no more, and no jitted function called inside the kernel, which is what
the time followed on the chip's host (equations alone did not: 1.5 times the
parent's cost 21.6 s of set-up at olmo's shape while the body used jnp's
operators). Counts, never times.
"""

import jax
import jax.numpy as jnp
import pytest

from areal_tpu.ops.paged_attention_q8 import paged_attention_stacked

# cell's shape -> (slots, KV heads, query rows a head, pages a row, query dtype, softmax scale),
# equations of the launch at PR 43 (one item a slot and block), and a ceiling a dozen over this PR's own 2,122 / 978 /
# 890 / 1,232 / 1,154 (fewer is welcome); both with the work list made inside the call
SHAPES = {
    "olmo-hybrid-7b": ((64, 30, 1, 32, jnp.bfloat16, None), 1712, 2135),
    "qwen2.5-7b": ((64, 4, 7, 32, jnp.bfloat16, None), 646, 990),
    "qwen2.5-1.5b": ((128, 2, 6, 32, jnp.bfloat16, None), 564, 900),
    "phi-4-mini-flash": ((64, 10, 4, 160, jnp.float32, 0.125), 881, 1245),
    "granite-h-micro": ((64, 8, 4, 32, jnp.bfloat16, None), 810, 1165),
}
PSZ = HD = 128


def inner_jaxprs(eqn):
    for v in eqn.params.values():
        for x in v if isinstance(v, (list, tuple)) else [v]:
            j = getattr(x, "jaxpr", x)
            if hasattr(j, "eqns"):
                yield j


def count(jaxpr, primitive=None) -> int:
    return sum(
        (primitive is None or e.primitive.name == primitive) + sum(count(j, primitive) for j in inner_jaxprs(e))
        for e in jaxpr.eqns
    )


def launch(shape):
    S, KH, G, wp, q_dtype, sm_scale = shape
    sds = jax.ShapeDtypeStruct
    pages = sds((2, KH, 65, PSZ, HD), jnp.bfloat16)
    args = [sds((S, KH * G, HD), q_dtype), pages, pages, sds((), jnp.int32), sds((S,), jnp.int32), sds((S, wp), jnp.int32)]

    def fn(q, k, v, li, lengths, table):
        return paged_attention_stacked(q, k, v, li, lengths, table, pages_per_compute_block=4, sm_scale=sm_scale)

    return fn, args


@pytest.mark.parametrize("cell", SHAPES)
def test_the_launch_holds_its_equation_budget(cell):
    shape, parent, ceiling = SHAPES[cell]
    fn, args = launch(shape)
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    n = count(jaxpr)
    assert n <= 2.5 * parent, f"{n} equations, over 2.5 x the {parent} of the launch that fetched a block a slot"
    assert n <= ceiling, f"{n} equations: the launch has grown past what this PR pinned ({ceiling})"
    # the per-head code in two shapes (an item of one slot, an item of stacked readers): QK and PV a head in each
    assert count(jaxpr, "dot_general") == 2 * 2 * shape[1] + 2  # and the work list's two small products
    assert count(jaxpr, "pallas_call") == 1
    # no jitted jnp function inside the kernel (``jnp.where``, an operator of a traced value): each call of one is
    # a trace of its own, a millisecond and more of a start-up's host time, and the per-head code made a dozen a head
    kernel = next(e for e in jaxpr.eqns if e.primitive.name == "pallas_call").params["jaxpr"]
    assert count(kernel, "jit") + count(kernel, "pjit") == 0


def test_the_launch_lowers_for_a_tpu_from_the_cpu():
    """At olmo's shape, the widest: what jax's lowering refuses it refuses
    here, before any chip (the chip's compiler is tests/test_tpu_compile.py's)."""
    fn, args = launch(SHAPES["olmo-hybrid-7b"][0])
    text = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text and "paged_decode_attn" in text


# ``paged_latent_attn`` since PR 54 (a shared block of latent rows fetched once, its readers' query rows stacked a
# pass, in passes of two sizes): cell's shape -> (slots, heads, pages a row, with a selection), equations of the
# launch at PR 53 (one item a (slot, block), a body of jnp operators: 20 jitted calls inside the kernel) and of its
# kernel alone, and a ceiling a dozen over this PR's own 689 / 689 / 229, all with the work list made inside the call.
# What a launch SITE traces is the kernel (491 against 335: under ISSUE 54's 1.6 x); the list is made once a step
# (``shared_decode_schedule``: 198 equations where ``decode_schedule`` is 34), so the whole call is held to 2 x
LATENT_SHAPES = {
    "xing4.0-29b-a4b": ((64, 32, 160, False), (369, 335), 700),
    "kanana-2-30b-a3b": ((64, 32, 32, False), (369, 335), 700),
    "glm-5": ((64, 64, 160, True), (379, 344), 240),
}


def latent_launch(shape):
    from areal_tpu.ops.paged_latent_attention import paged_latent_attention_stacked

    S, H, wp, selected = shape
    sds = jax.ShapeDtypeStruct
    args = [sds((S, H, 640), jnp.bfloat16), sds((2, 1, 65, PSZ, 640), jnp.bfloat16), sds((), jnp.int32), sds((S,), jnp.int32), sds((S, wp), jnp.int32)]
    if selected:
        args.append(sds((S, wp * PSZ), jnp.bool_))

    def fn(q, pool, li, lengths, table, select=None):
        return paged_latent_attention_stacked(
            q, pool, li, lengths, table, value_lanes=512, pages_per_compute_block=4, sm_scale=0.07, select=select
        )

    return fn, args


@pytest.mark.parametrize("cell", LATENT_SHAPES)
def test_the_latent_launch_holds_its_equation_budget(cell):
    shape, (parent, parent_kernel), ceiling = LATENT_SHAPES[cell]
    fn, args = latent_launch(shape)
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    n = count(jaxpr)
    assert n <= 2 * parent, f"{n} equations, over 2 x the {parent} of the launch that fetched a block a slot"
    assert n <= ceiling, f"{n} equations: the launch has grown past what PR 54 pinned ({ceiling})"
    # QK and PV in three shapes (an item of one slot, the two sizes of a pass of stacked readers) and the work list's
    # two small products; under a selection the launch walks no shared item
    assert count(jaxpr, "dot_general") == (2 if shape[3] else 8)
    assert count(jaxpr, "pallas_call") == 1
    kernel = next(e for e in jaxpr.eqns if e.primitive.name == "pallas_call").params["jaxpr"]
    assert count(kernel) <= 1.6 * parent_kernel, f"{count(kernel)} equations a launch site, over 1.6 x the parent kernel's {parent_kernel}"
    assert count(kernel, "jit") + count(kernel, "pjit") == 0  # ``jax.lax`` primitives only in the body


@pytest.mark.parametrize("cell", LATENT_SHAPES)
def test_the_latent_launch_lowers_for_a_tpu_from_the_cpu(cell):
    fn, args = latent_launch(LATENT_SHAPES[cell][0])
    text = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text and "paged_latent_attn" in text


# ``kda_prompt_scan`` (PR 48) at the published sizes, a block of 1,024 tokens of 64 heads of 128 x 128: the XLA form
# it stands in for is 577 equations a launch site (``hybrid.kda_chunked_scan``); the launch's walk over the 16 key
# offsets is ONE traced body (written out 16 times, with 4 heads a grid step, it was 4,239)
KDA_LAUNCH_CEILING = 1200


def kda_launch():
    from areal_tpu.ops.kda_prompt_scan import kda_prompt_scan

    sds, f32 = jax.ShapeDtypeStruct, jnp.float32
    return kda_prompt_scan, [sds((1024, 64, 128), f32)] * 4 + [sds((1024, 64), f32), sds((), jnp.int32), sds((64, 128, 128), f32)]


def test_the_kda_prompt_scan_holds_its_equation_budget():
    from areal_tpu.models import hybrid
    from areal_tpu.ops.kda_prompt_scan import HEADS_PER_STEP

    fn, args = kda_launch()
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    n = count(jaxpr)
    assert n <= KDA_LAUNCH_CEILING, f"{n} equations a launch site: the launch has grown past what PR 48 pinned (1,132)"
    assert n <= 2.5 * count(jax.make_jaxpr(hybrid.kda_chunked_scan)(*args).jaxpr)
    assert count(jaxpr, "pallas_call") == 1
    # a head's chunk: three products for the earlier sub-blocks, two a doubling of the inverse, the writes' two, the
    # stacked read of the state, ``B w`` and the state's update
    assert count(jaxpr, "dot_general") == 12 * HEADS_PER_STEP
    kernel = next(e for e in jaxpr.eqns[0].params["jaxpr"].eqns if e.primitive.name == "pallas_call").params["jaxpr"]
    assert count(kernel, "jit") + count(kernel, "pjit") == 0  # ``jax.lax`` primitives only in the body
    assert count(kernel, "scan") == 1 and count(kernel, "exp") < 20 * HEADS_PER_STEP  # the 16 offsets are one traced body


def test_the_kda_prompt_scan_lowers_for_a_tpu_from_the_cpu():
    fn, args = kda_launch()
    text = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text and "kda_prompt_scan" in text
