"""A GRPO group through ``DecodeEngine`` on the kernel path (the CPU's
interpreter): siblings hold the first sample's prompt pages
(``SlotCache.alias``), the decode steps' attention launches fetch such a
block once, and the two counters that say so reach ``/metrics`` and
``/statusz``.
"""

import functools
import os

import pytest

from areal_tpu.api.io_struct import GenerationHyperparameters, ModelRequest
from areal_tpu.observability.metrics import get_registry

from tpu_testing import tiny_decode_engine

LISTED, FETCHED = "areal_decode_attn_blocks_listed_total", "areal_decode_attn_blocks_fetched_total"
PROMPT = [(7 * i + 3) % 256 for i in range(75)]  # 4 full pages of 16 and a part: 2 shared blocks of 2 pages
NEW = [3, 5, 8, 8, 12, 12, 16, 40]  # tokens each sample generates: all but one have ended after 16


def interpreted(monkeypatch):
    import areal_tpu.ops.paged_attention_q8 as q8mod
    import areal_tpu.ops.paged_kv_write as kvw

    for mod, name in ((q8mod, "paged_attention_stacked"), (kvw, "paged_kv_write")):
        monkeypatch.setattr(mod, name, functools.partial(getattr(mod, name), interpret=True))


def run_group(eng):
    """The 8 samples admitted in one round, then chunk by chunk to the end:
    ([output tokens of each], [(live slots at the chunk's start, blocks
    listed, blocks fetched) of each chunk])."""
    results, chunks = {}, []
    for j, n in enumerate(NEW):
        g = GenerationHyperparameters(max_new_tokens=n, greedy=True, ignore_eos=True)
        eng.submit(ModelRequest(input_ids=list(PROMPT), gconfig=g), functools.partial(results.__setitem__, j))
    eng._apply_slot_updates(eng._admit_pending())
    for _ in range(40):
        live = sum(t is not None for t in eng._slot_task)
        if not live:
            break
        before = eng._attn_blocks
        eng._drain(eng._dispatch_chunk())
        chunks.append((live, eng._attn_blocks[0] - before[0], eng._attn_blocks[1] - before[1]))
    assert len(results) == len(NEW)
    return [results[j].output_tokens for j in range(len(NEW))], chunks


def test_a_group_of_8_reads_its_prompt_blocks_once(monkeypatch):
    """Greedy tokens are those of the gather path (what the engine gave
    before); while siblings live the launches fetch fewer blocks than the
    rows list, and exactly the listed ones once one sample is left."""
    plain = tiny_decode_engine(max_batch_size=8, max_seq_len=256, attn_window_step=128)
    want, idle = run_group(plain)
    assert all(c[1:] == (0, 0) for c in idle) and plain.decode_attention_status() is None  # counted on the kernel path
    interpreted(monkeypatch)
    eng = tiny_decode_engine(max_batch_size=8, max_seq_len=256, attn_window_step=128)
    eng.programs.use_kernel = True
    got, chunks = run_group(eng)
    assert got == want and [len(t) for t in got] == NEW
    assert eng.stats["prefix_shared"] == 7
    shared = [c for c in chunks if c[0] > 1]
    alone = [c for c in chunks if c[0] == 1]
    assert shared and alone
    for live, listed, fetched in shared:
        assert 0 < fetched < listed, (live, listed, fetched)
    for _, listed, fetched in alone:
        assert 0 < fetched == listed
    status = eng.decode_attention_status()
    assert status["blocks_listed"] == sum(c[1] for c in chunks) and status["blocks_fetched"] == sum(c[2] for c in chunks)
    assert 0 < status["fetched_share"] < 1
    text = get_registry().render_prometheus()
    assert LISTED in text and FETCHED in text


def test_statusz_and_the_docs_name_both_counters():
    import inspect

    from areal_tpu.inference import server

    assert "decode_attention_status" in inspect.getsource(server)
    doc = open(os.path.join(os.path.dirname(__file__), "..", "docs", "observability.md")).read()
    assert LISTED in doc and FETCHED in doc and "decode_attention" in doc
