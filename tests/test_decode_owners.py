"""The decode engine's two lower owners stand alone: ``DecodePrograms`` builds
and lowers its programs with no engine, thread, request or page ledger, and
neither it nor ``SlotCache`` reaches back up (docs/serving.md).

The arrows point one way: ``decode_engine`` -> ``decode_programs`` -> models
and ops, ``decode_engine`` -> ``slot_cache`` -> ``paged_kv``."""

import ast
import collections
import inspect

import jax
import jax.numpy as jnp
import pytest

from areal_tpu import models
from areal_tpu.api.config import MeshConfig, ServerConfig
from areal_tpu.inference import decode_programs, paged_kv, slot_cache
from areal_tpu.inference.decode_programs import DecodePrograms
from areal_tpu.parallel import mesh as mesh_lib

from tpu_testing import TINY_QWEN2

# what ``tests/tpu_testing.py tiny_decode_engine`` serves with
CFG = dict(
    max_batch_size=4, max_seq_len=512, page_size=16, decode_steps_per_call=4, attn_window_step=512,
    mesh=MeshConfig(data=1, fsdp=1, seq=1, model=1),
)
# the warm set of that configuration as ``DecodeEngine.precompile()`` built it
# before the programs had a module of their own (PR 41): 1 window x 4
# (capped, greedy) chunks, 3 scatter + 3 clamp sizes, 3 page-copy sizes, 2
# prompt buckets x 4 group sizes
WARMED = {"chunk": 4, "upd": 3, "clamp": 3, "pagecopy": 3, "prefill": 8}


def _programs(**kw) -> tuple[DecodePrograms, tuple]:
    """``DecodePrograms`` of the tiny model and the abstract arguments its
    programs are lowered from: no weights, no cache and no state exist."""
    cfg = ServerConfig(**{**CFG, **kw})
    mcfg = TINY_QWEN2
    model = models.family_of(mcfg)
    mesh = mesh_lib.make_mesh(cfg.mesh, devices=jax.devices()[:1])
    S = cfg.max_batch_size
    params_s = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0), mcfg))
    cache_s = jax.eval_shape(
        lambda: paged_kv.init_paged_cache(mcfg, S * (cfg.max_seq_len // cfg.page_size) + 1, cfg.page_size, slots=S)
    )
    state_s = jax.eval_shape(lambda: {k: jnp.asarray(v) for k, v in decode_programs.slot_state(S).items()})
    if cfg.enable_frequency_penalty:
        state_s["freq_counts"] = jax.ShapeDtypeStruct((S, mcfg.vocab_size), jnp.uint16)
    rng_s = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    return DecodePrograms(model, mcfg, cfg, mesh), (params_s, cache_s, state_s, rng_s)


def test_programs_alone_lower_and_report_the_set_a_start_up_warms():
    progs, shapes = _programs()
    keys = progs.warm_keys()
    assert len(keys) == len(set(keys)) == 21
    assert collections.Counter(k[0] for k in keys) == WARMED
    # hot loop first: an out-of-budget start-up costs admission stalls, never decode stalls
    assert [k[0] for k in keys] == ["chunk"] * 4 + ["upd", "clamp"] * 3 + ["pagecopy"] * 3 + ["prefill"] * 8
    assert progs.keys() == set()  # naming a program builds nothing
    first = {kind: next(k for k in keys if k[0] == kind) for kind in ("chunk", "prefill", "upd", "clamp")}
    with jax.set_mesh(progs.mesh):
        for kind, module in (("chunk", "jit_chunk"), ("prefill", "jit_prefill"), ("upd", "jit_apply"), ("clamp", "jit_clamp")):
            text = progs.lower(first[kind], *shapes).as_text()
            assert f"module @{module} " in text, text[:200]
    assert progs.keys() == set(first.values())
    with pytest.raises(KeyError):
        progs.lower(("spec", 4, 4, False, True, False), *shapes)  # no start-up warms a speculative program


@pytest.mark.parametrize(
    "kw,warmed",
    [
        (dict(enable_frequency_penalty=True), {**WARMED, "chunk": 8}),
        (dict(max_batch_size=2), {**WARMED, "upd": 2, "clamp": 2, "pagecopy": 1}),
        (dict(max_seq_len=1024, attn_window_step=256), {**WARMED, "chunk": 16, "prefill": 16}),
    ],
    ids=["penalised", "two-slots", "four-windows"],
)
def test_the_warm_set_follows_the_shape_fields(kw, warmed):
    progs, _ = _programs(**kw)
    assert collections.Counter(k[0] for k in progs.warm_keys()) == warmed
    narrowed = progs.warm_keys(prompt_buckets=[256])
    assert {k[2] for k in narrowed if k[0] == "prefill"} == {256}


def _named(module) -> set[str]:
    """Every dotted module an ``import`` of ``module``'s source names, every
    name it imports from one, and every attribute it reads."""
    out = set()
    for node in ast.walk(ast.parse(inspect.getsource(module))):
        if isinstance(node, ast.Import):
            out.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            out.add(node.module or "")
            out.update(f"{node.module}.{a.name}" for a in node.names)
            out.update(a.name for a in node.names)
        elif isinstance(node, ast.Attribute):
            out.add(node.attr)
        elif isinstance(node, ast.Name):
            out.add(node.id)
    return out


@pytest.mark.parametrize(
    "module,never",
    [
        (decode_programs, ("decode_engine", "slot_cache", "RadixPrefixCache", "PagePool", "_Task", "io_struct")),
        (slot_cache, ("decode_engine", "decode_programs", "io_struct", "timeline", "ModelRequest", "jit")),
    ],
    ids=["programs", "slots"],
)
def test_the_arrows_point_one_way(module, never):
    """Read from each module's own source: ``areal_tpu/inference/__init__.py``
    imports the engine for everyone, so ``sys.modules`` says nothing."""
    named = _named(module)
    for word in never:
        assert not any(word == n or n.endswith("." + word) or ("." + word + ".") in n for n in named), word
