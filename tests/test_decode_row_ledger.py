"""The row ledger of a drained chunk (``DecodeEngine._drain``): steps, rows,
live, spent and dropped row-steps on the pass's ``areal.decode.pass`` span, in
the two counters beside ``areal_decode_chunks_total`` and in ``/statusz``
``row_steps`` (docs/observability.md "Spans and scopes").

The numbers pin what the loop does today: a pass dispatches the next chunk
before it drains the last one, so the dispatch reads a host mask that is one
chunk stale, and a request that ends inside chunk k is stepped for, under the
device's mask, to the end of chunk k AND through all of chunk k + 1."""

import threading
import time

import numpy as np
import pytest

from areal_tpu.api.config import PerfTracerConfig
from areal_tpu.api.io_struct import GenerationHyperparameters, ModelRequest, StopReason
from areal_tpu.inference.decode_engine import LEDGER_KEYS
from areal_tpu.observability import catalog
from areal_tpu.utils import perf_tracer

from tpu_testing import tiny_decode_engine

STEPS, SLOTS = 8, 4
ARGS = {"active", "tokens", "held_us", "cpu_us", *LEDGER_KEYS, "admitted", "prompt_tokens", "queued"}


@pytest.fixture()
def record(monkeypatch):
    """The process's tracer for this test alone (a worker's record holds
    every engine it ran before)."""
    monkeypatch.setattr(perf_tracer, "_TRACER", perf_tracer.PerfTracer(PerfTracerConfig()))
    return lambda: [e.args for e in perf_tracer.get_tracer().record().entries if e.name == "areal.decode.pass"]


def _req(rid: str, n: int, prompt_len: int = 12) -> ModelRequest:
    ids = np.random.default_rng(len(rid) + n).integers(1, 200, prompt_len).tolist()
    return ModelRequest(rid=rid, input_ids=ids, gconfig=GenerationHyperparameters(max_new_tokens=n, greedy=True, ignore_eos=True))


def _serve(eng, reqs, chunks: int = 0) -> dict:
    """Every request queued before the loop starts (one admission for all),
    served to its end, and the loop left to drain the chunk it dispatched
    before the last end was known (``chunks`` drained in all): stopped
    earlier, the loop drains that one outside any pass."""
    got, done = {}, threading.Event()

    def cb(resp):
        got[resp.rid] = resp
        if len(got) == len(reqs):
            done.set()

    for r in reqs:
        eng.submit(r, cb)
    eng.start()
    try:
        assert done.wait(300), sorted(got)
        deadline = time.monotonic() + 60
        while eng.stats["chunks"] < chunks:
            assert time.monotonic() < deadline, eng.stats
            time.sleep(0.005)
    finally:
        eng.stop()
    return got


def _counters() -> dict:
    m = catalog.engine_metrics()
    return {"steps": m.steps.get(), "spent": m.row_steps_spent.get(), "tokens": m.generated_tokens.get(), "chunks": m.chunks.get()}


def test_every_pass_carries_its_chunks_ledger_and_the_sums_are_the_counters(record):
    eng = tiny_decode_engine(max_batch_size=SLOTS, decode_steps_per_call=STEPS)
    before = _counters()
    reqs = [_req("three", 3, 10), _req("eight", 8, 11), _req("thirteen", 13, 12)]
    got = _serve(eng, reqs, chunks=3)
    assert {r: len(got[r].output_tokens) for r in got} == {"three": 3, "eight": 8, "thirteen": 13}
    passes = record()
    assert all(set(p) == ARGS for p in passes), [sorted(p) for p in passes]
    assert all(p["tokens"] + p["spent"] + p["dropped"] == p["rows"] * p["steps"] for p in passes)
    # pass 1 admits the three and dispatches chunk 1; pass k + 1 dispatches chunk k + 1, THEN drains chunk k
    assert [(p["admitted"], p["prompt_tokens"], p["queued"]) for p in passes] == [(3, 33, 0)] + [(0, 0, 0)] * 3
    assert [(p["rows"], p["steps"], p["tokens"], p["spent"], p["dropped"]) for p in passes] == [
        (0, 0, 0, 0, 0),
        # chunk 1: 3 + 8 + 8 tokens; "three" ended at step 3 and is stepped for under the mask to step 8
        (3, STEPS, 19, 5, 0),
        # chunk 2 was dispatched before chunk 1's ends were drained: every step of it for "three" and for
        # "eight" (which emitted at all 8 steps of chunk 1), and the 3 steps after "thirteen"'s last 5
        (3, STEPS, 5, 8 + 8 + 3, 0),
        # and chunk 3 before "thirteen"'s end inside chunk 2 was: a whole chunk for no token
        (1, STEPS, 0, 8, 0),
    ]
    delta = {k: v - before[k] for k, v in _counters().items()}
    assert delta == {"steps": 3 * STEPS, "spent": 32, "tokens": 24, "chunks": 3}
    assert delta["steps"] == sum(p["steps"] for p in passes) and delta["spent"] == sum(p["spent"] for p in passes)
    assert delta["tokens"] == sum(p["tokens"] for p in passes) == eng.stats["generated_tokens"]
    assert eng.row_steps_status() == {"slots": SLOTS, "steps": 3 * STEPS, "live": 24, "spent": 32, "dropped": 0}


def test_a_request_gone_by_the_drain_is_dropped_not_spent(record):
    """Chunk 1 computes 8 tokens for each of two requests; before it is
    drained one of them is aborted the way pool pressure aborts (``_preempt``
    from ``_ensure_pages``, at the top of the next dispatch): its 8 tokens go
    with it, and nothing of it is stepped for afterwards."""
    eng = tiny_decode_engine(max_batch_size=SLOTS, decode_steps_per_call=STEPS)
    ensure, calls = eng._ensure_pages, []

    def preempt_once(ahead=None):
        calls.append(ahead)
        if len(calls) == 2:  # pass 2's dispatch: chunk 1 is in flight
            (slot,) = [s for s, t in enumerate(eng._slot_task) if t is not None and t.req.rid == "gone"]
            eng._apply_slot_updates([eng._preempt(slot)])
        ensure(ahead)

    eng._ensure_pages = preempt_once
    before = _counters()
    got = _serve(eng, [_req("stays", 20), _req("gone", 20, 14)], chunks=4)
    assert got["gone"].stop_reason == StopReason.ABORT.value and got["gone"].output_tokens == []
    assert len(got["stays"].output_tokens) == 20
    passes = record()
    assert all(p["tokens"] + p["spent"] + p["dropped"] == p["rows"] * p["steps"] for p in passes)
    assert [(p["rows"], p["tokens"], p["spent"], p["dropped"]) for p in passes] == [
        (0, 0, 0, 0),
        (2, 8, 0, 8),  # chunk 1: both rows emitted at every step, one request was gone by the drain
        (1, 8, 0, 0),  # chunk 2 was dispatched without it
        (1, 4, 4, 0),
        (1, 0, 8, 0),
    ]
    delta = {k: v - before[k] for k, v in _counters().items()}
    assert delta == {"steps": 4 * STEPS, "spent": 12, "tokens": 20, "chunks": 4}
    assert eng.row_steps_status()["dropped"] == 8


def test_a_speculative_round_is_one_step_of_its_own_kind(record):
    """A round is one verify forward whose rows emit several tokens: its pass
    says ``spec=1``, counts one step, spends nothing, and readers leave it
    out of the chunks' shares."""
    from areal_tpu.api.config import SpeculativeConfig

    eng = tiny_decode_engine(max_batch_size=2, max_seq_len=256, speculative=SpeculativeConfig(enabled=True, drafter="ngram"))
    prompt = [5, 8, 1, 5, 8, 1, 5, 8, 1, 5, 8]  # periodic: drafts land
    req = ModelRequest(rid="s", input_ids=prompt, gconfig=GenerationHyperparameters(max_new_tokens=33, greedy=True))
    got = _serve(eng, [req])
    assert len(got["s"].output_tokens) == 33
    passes = record()
    assert passes and all(p.get("spec") == 1 and set(p) == ARGS | {"spec"} for p in passes)
    rounds = eng.stats["spec_rounds"]
    assert [p["steps"] for p in passes] == [1] * rounds and [p["rows"] for p in passes] == [1] * rounds
    assert sum(p["tokens"] for p in passes) == 33 > rounds and all(p["spent"] == p["dropped"] == 0 for p in passes)
    assert eng.row_steps_status() == {"slots": 2, "steps": rounds, "live": 33, "spent": 0, "dropped": 0}
