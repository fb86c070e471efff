"""No program of a benchmark cell may be refused by the program store
(``utils/compile_cache.py``): a refused program is traced and lowered again at
every start, and only a WARNING on the chip says so (cells 10-12 were refused
from the day they were added until PR 56 read it). The programs that were
refused, traced here (never lowered) as a TPU would trace them, at the cells'
published widths and longest shapes, and held to the store's own rule
(``compile_cache.constants_not_in_the_key``): the next table somebody's traced
code makes on the host fails this test, not a warm start."""

import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))
from chipbench_util import CHIP, load_run  # noqa: E402

from areal_tpu.utils import compile_cache  # noqa: E402

# family -> (the module of tests/benchmark_harness that builds the configuration as the cell does, the configuration's file,
# what its depth is cut by: one period of the layer pattern, as tests/test_tpu_compile.py cuts it)
FAMILIES = {
    "xing4_0": ("chipbench_xing4_util", "xing4.0-29b-a4b-ep4-d10", dict(num_hidden_layers=4)),  # the YaRN table, once a call site
    "solar_open2": ("chipbench_solar_open2_util", "solar-open2-250b-ep16-d8", dict(num_hidden_layers=4, gqa_layers=[0])),  # the flash launch's tile tables
    "cohere2_moe": ("chipbench_cohere2_moe_util", "command-a-plus-ep16-d4", {}),
}
SLOTS, CONTEXT, PSZ, STEPS, BUCKET = 64, 20480, 128, 32, 16384  # the long-context cells' server and longest bucket


def _host(consts) -> int:
    return sum(int(np.size(c)) for c in consts if not isinstance(c, jax.Array))


def _held_to_the_rule(traced, host_elements_at_least: int):
    consts = traced.jaxpr.consts
    assert compile_cache.constants_not_in_the_key(consts) is None, [(type(c).__name__, np.shape(c)) for c in consts]
    assert not any(isinstance(c, jax.Array) and np.size(c) > 1 for c in consts)  # nothing from outside the trace
    # the tables ARE there at these shapes (else this test sees another path than the chip runs)
    assert _host(consts) >= host_elements_at_least, [(type(c).__name__, np.shape(c)) for c in consts]


@pytest.mark.parametrize("family", FAMILIES)
def test_a_served_familys_decode_chunk_and_longest_prefill_pass_the_stores_rule(monkeypatch, family):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from areal_tpu import models
    from areal_tpu.api.config import MeshConfig, PrefixCacheConfig, ServerConfig
    from areal_tpu.inference import paged_kv
    from areal_tpu.inference.decode_programs import DecodePrograms, slot_state
    from areal_tpu.parallel import mesh as mesh_lib

    util, config, cut = FAMILIES[family]
    load_run()
    with open(os.path.join(CHIP, "configs", config + ".json")) as f:
        mcfg = importlib.import_module(util).model_config({**json.load(f), **cut}, "bfloat16")
    scfg = ServerConfig(
        dtype="bfloat16", max_batch_size=SLOTS, max_seq_len=CONTEXT, page_size=PSZ, decode_steps_per_call=STEPS, attn_window_step=CONTEXT, seed=0,
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1), prefix_cache=PrefixCacheConfig(enabled=True),
    )
    mesh = mesh_lib.make_mesh(scfg.mesh, devices=jax.devices()[:1])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the kernels' launches are traced, as on the chip
    model = models.family_of(mcfg)
    progs = DecodePrograms(model, mcfg, scfg, mesh, store=compile_cache.ProgramStore(None))
    assert progs.use_kernel
    placed = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=NamedSharding(mesh, P())), tree)  # noqa: E731
    shapes = (
        placed(jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0), mcfg))),
        placed(jax.eval_shape(lambda: paged_kv.init_paged_cache(mcfg, 1024, PSZ, slots=SLOTS))),
        placed(jax.eval_shape(lambda: jax.tree.map(jnp.asarray, slot_state(SLOTS)))),
        placed(jax.eval_shape(lambda: jax.random.PRNGKey(0))),
    )
    chunk = ("chunk", STEPS, CONTEXT // PSZ, False, False, False)
    prefill = ("prefill", 1, BUCKET, False)
    assert chunk in progs.warm_keys()
    with jax.set_mesh(mesh):
        for key, at_least in ((chunk, 32 if family == "xing4_0" else 0), (prefill, 32)):
            fn, args = progs._abstract_call(key, *shapes)
            assert fn._described is not None  # the other way to be refused: no description
            _held_to_the_rule(fn._fn.trace(*args), at_least)


def test_the_qwen_train_steps_flash_tables_pass_the_stores_rule(monkeypatch):
    """The fused train step's traced form at the train cell's grid (3 rows of
    4,096 tokens): the flash kernels' tile tables, 112 elements, are numpy the
    traced code made (PERF.md, PR 52 (g))."""
    import dataclasses

    from areal_tpu.models import qwen
    from tpu_testing import TINY_QWEN2

    cfg = dataclasses.replace(TINY_QWEN2, hidden_size=256, num_heads=2, num_kv_heads=1, attn_impl="pallas", dtype="bfloat16")
    assert cfg.head_dim_ == 128
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    params = jax.eval_shape(lambda: qwen.init_params(jax.random.PRNGKey(0), cfg))
    grid = jax.ShapeDtypeStruct((3, 4096), jnp.int32)

    def step(params, ids, seg, pos):
        loss = lambda p: qwen.forward(p, cfg, ids, seg, pos).astype(jnp.float32).mean()  # noqa: E731
        return jax.value_and_grad(loss)(params)

    _held_to_the_rule(jax.jit(step).trace(params, grid, grid, grid), 100)
