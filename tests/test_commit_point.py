"""The decode loop commits the next chunk's batch part-way through the
running chunk (inference/commit_point.py; docs/serving.md "The pass and its
commit point").

The estimate is held as arithmetic on numbers fed in. The engine is held at
tiny size on a simulated clock: a chunk lasts ``Sim.D`` of its seconds
whatever the CPU takes, the loop's wait moves that clock and nothing sleeps,
so no test here times a CPU run.
"""

import threading
import time

import jax
import numpy as np
import pytest

from areal_tpu.api.config import MeshConfig, ServerConfig
from areal_tpu.api.io_struct import GenerationHyperparameters, ModelRequest
from areal_tpu.inference import commit_point
from areal_tpu.inference.commit_point import COMMIT_FRACTION, ChunkPacer
from areal_tpu.inference.decode_engine import DecodeEngine
from areal_tpu.inference.server import flatten_params
from areal_tpu.models import qwen
from areal_tpu.observability import catalog

from tpu_testing import TINY_QWEN2


def test_estimate_is_the_least_interval_and_resets_to_commit_now():
    key, other = (4, False, True, False), (8, False, True, False)
    p = ChunkPacer()
    assert p.estimate() is None and p.commit_point(key) is None  # first chunk: now
    p.host_work(0.004)
    p.pulled(10.0, key)
    assert p.estimate() is None  # one return is no interval
    p.pulled(10.25, key)
    assert p.estimate() == pytest.approx(0.25)
    assert p.commit_point(key) == pytest.approx(10.25 + COMMIT_FRACTION * 0.25)
    # a prefill queued before the next chunk lengthens ONE interval
    p.pulled(10.57, key)
    assert p.estimate() == pytest.approx(0.25)
    p.pulled(10.82, key)
    assert p.commit_point(key) == pytest.approx(10.82 + COMMIT_FRACTION * 0.25)
    # the running chunk is another program than the estimate's: now
    assert p.commit_point(other) is None
    # an idle poll, a pause, a hold fence, a released cache, a speculative
    # round: the loop calls reset(), and the next interval counts for nothing
    p.reset()
    assert p.estimate() is None and p.commit_point(key) is None
    p.pulled(20.0, key)
    assert p.estimate() is None
    p.pulled(20.3, key)
    assert p.estimate() == pytest.approx(0.3)
    # a changed program key clears it at the pull
    p.pulled(20.6, other)
    assert p.estimate() is None and p.commit_point(other) is None
    p.pulled(20.9, other)
    assert p.commit_point(other) == pytest.approx(20.9 + COMMIT_FRACTION * 0.3)
    # too little slack behind the commit point for the host's own work (a
    # tiny engine whose chunk is a few host-times long): now
    p.host_work((1 - COMMIT_FRACTION) * 0.3 / commit_point.SLACK_HOST_MULTIPLE * 1.01)
    assert p.commit_point(other) is None


def _engine():
    cfg = ServerConfig(
        max_batch_size=4,
        max_seq_len=256,
        decode_steps_per_call=4,
        seed=0,
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
    )
    params = qwen.init_params(jax.random.PRNGKey(0), TINY_QWEN2)
    eng = DecodeEngine(cfg, params=params, model_cfg=TINY_QWEN2)
    eng.initialize()
    return eng


def _req(rid: str, n: int, seed: int) -> ModelRequest:
    ids = np.random.default_rng(seed).integers(1, 200, 12).tolist()
    return ModelRequest(
        rid=rid,
        input_ids=ids,
        gconfig=GenerationHyperparameters(max_new_tokens=n, greedy=True, ignore_eos=True),
    )


class Sim:
    """A device on a clock of its own: chunks run back to back, each ``D``
    long; the loop's wait moves the clock and returns at once. ``on_hold``
    is called once, at the start of the first hold for a commit point after
    it is set, with the hold's length (on the loop's thread, before the
    clock moves; True: woken, the clock stays); ``on_pull`` once, at the
    next pull (after the pass's commit)."""

    D = 1.0

    def __init__(self, eng: DecodeEngine):
        self.eng = eng
        self.now = 0.0
        self.ends: list[float] = []  # when each dispatched chunk ends
        self.idle = 0.0  # seconds the device waited for a chunk
        self.passes = self.passes_done = self.resets = 0
        self.waits: list[tuple[int, float, float]] = []  # (pass, clock, seconds asked), every call
        self.dispatched_at: dict[int, float] = {}  # pass -> clock at its dispatch
        self.first_chunk: dict[str, int] = {}  # rid -> pass that dispatched its first chunk
        self.first_token: dict[str, int] = {}  # rid -> pass that credited its first token
        self.on_hold = None
        self.on_pull = None
        eng._pace_clock = lambda: self.now
        eng._pace_wait = self._wait
        self._real = (eng._run_pass, eng._dispatch_chunk, eng._pull, eng._pacer.reset)
        eng._run_pass, eng._dispatch_chunk, eng._pull = self._run_pass, self._dispatch, self._pulled
        eng._pacer.reset = self._reset

    def holds(self) -> list[tuple[int, float, float]]:
        """The first wait of each pass that held."""
        first = {}
        for w in self.waits:
            first.setdefault(w[0], w)
        return list(first.values())

    def _reset(self):
        self.resets += 1
        self._real[3]()

    def _wait(self, left: float) -> bool:
        first = not self.waits or self.waits[-1][0] != self.passes
        self.waits.append((self.passes, self.now, left))
        hook = self.on_hold if first else None
        if hook is not None:
            self.on_hold = None
            if hook(left):
                return True
        self.now += left
        return False

    def _run_pass(self, pending, step_tl, span):
        self.passes += 1
        out = self._real[0](pending, step_tl, span)
        for task in self.eng._slot_task:
            if task is not None and task.first_token_time is not None:
                self.first_token.setdefault(task.req.rid, self.passes)
        self.passes_done = self.passes
        return out

    def _dispatch(self):
        rec = self._real[1]()
        if rec is not None:
            start = max(self.now, self.ends[-1] if self.ends else 0.0)
            if self.ends:
                self.idle += start - self.ends[-1]
            self.ends.append(start + self.D)
            self.dispatched_at[self.passes] = self.now
            for task in rec["tasks"]:
                if task is not None:
                    self.first_chunk.setdefault(task.req.rid, self.passes)
        return rec

    def _pulled(self, packed):
        out = self._real[2](packed)
        hook, self.on_pull = self.on_pull, None
        if hook is not None:
            hook()
        # the pull returns when its chunk ends: the oldest not yet pulled
        self.now = max(self.now, self.ends[self.eng.stats["chunks"]])
        return out


def _until(cond, what: str):
    deadline = time.monotonic() + 120
    while not cond():
        assert time.monotonic() < deadline, what
        time.sleep(0.002)


def _submit_all(eng, reqs, got, done):
    for r in reqs:
        def cb(resp, rid=r.rid):
            got[rid] = resp
            if len(got) == done.n:
                done.set()

        eng.submit(r, cb)


def _done(n: int) -> threading.Event:
    ev = threading.Event()
    ev.n = n
    return ev


def test_a_request_before_the_commit_point_rides_that_pass_one_after_it_the_next():
    eng = _engine()
    sim = Sim(eng)
    counter = catalog.engine_metrics().admitted_in_wait
    got, done = {}, _done(3)
    eng.start()
    try:
        before = counter.get()
        _submit_all(eng, [_req("a", 64, 1)], got, done)  # 16 chunks: keeps the device busy
        _until(lambda: len(sim.holds()) >= 2, "the loop never held for a commit point")
        at = {}

        def early(left):
            # part-way to the commit point: woken by the submit, the loop
            # re-waits for the remainder and then admits it in THIS pass
            at["pass"], at["start"], at["length"] = sim.passes, sim.now, left
            sim.now += 0.4 * left
            _submit_all(eng, [_req("b", 12, 2)], got, done)
            # after the commit, inside the same pass's pull: the next pass
            sim.on_pull = lambda: _submit_all(eng, [_req("c", 12, 3)], got, done)
            return True

        sim.on_hold = early
        assert done.wait(120), sorted(got)
    finally:
        eng.stop()
    n = at["pass"]
    # the hold was woken once and ran to the commit point all the same
    assert [w[0] for w in sim.waits].count(n) == 2
    assert sim.dispatched_at[n] == pytest.approx(at["start"] + at["length"])
    assert at["length"] == pytest.approx(COMMIT_FRACTION * sim.D)
    assert sim.first_chunk["b"] == n and sim.first_token["b"] == n + 1
    assert sim.first_chunk["c"] == n + 1 and sim.first_token["c"] == n + 2
    assert counter.get() - before == 1  # b alone arrived inside the pass that admitted it
    assert sim.idle == 0.0  # every chunk was queued before its predecessor ended
    assert {r: len(got[r].output_tokens) for r in got} == {"a": 64, "b": 12, "c": 12}


def _pause(eng):
    eng.pause_generation()


def _hold(eng):
    eng.pause_generation(mode="hold")


def _abort(eng):
    eng.abort_request("a")


def _staged_commit(eng):
    eng.begin_staged_update()
    eng.stage_weight_bucket({k: np.asarray(v) for k, v in flatten_params(eng.params).items()})
    eng.commit_staged_weights(version=1)


def _stop(eng):
    eng.stop()


@pytest.mark.parametrize("act", [_pause, _hold, _abort, _staged_commit, _stop], ids=lambda f: f.__name__.strip("_"))
def test_what_needs_the_loop_ends_the_hold_at_once(act):
    eng = _engine()
    sim = Sim(eng)
    got, done = {}, _done(1)
    eng.start()
    try:
        _submit_all(eng, [_req("a", 96, 1)], got, done)
        _until(lambda: len(sim.holds()) >= 2, "the loop never held for a commit point")
        in_hold, woken = threading.Event(), {}

        def park(left):
            # the real wait: returns when the action sets the loop's event
            woken["pass"], woken["at"] = sim.passes, sim.now
            in_hold.set()
            assert eng._wakeup.wait(60)
            return True

        sim.on_hold = park
        assert in_hold.wait(60)
        act(eng)
        _until(lambda: sim.passes_done >= woken["pass"], "the pass never went on")
        # the pass went on at the instant of the action: it asked for no more
        # waiting, and the clock stood still until its dispatch
        assert [w[0] for w in sim.waits].count(woken["pass"]) == 1
        assert sim.dispatched_at.get(woken["pass"], woken["at"]) == woken["at"]
        if act is _staged_commit:
            assert eng.get_version() == 1
        if act is _abort:
            assert done.wait(60) and got["a"].stop_reason == "cancelled"
        if act in (_pause, _hold):
            assert (eng._hold_ack if act is _hold else eng._pause_ack).wait(30)
            resets = sim.resets
            eng.continue_generation()
            # a pause or a fence leaves no estimate: the next chunk is committed at once
            _until(lambda: sim.resets > resets, "no reset after the fence")
    finally:
        eng.stop()


def test_greedy_twins_with_and_without_the_hold():
    def run(hold: bool):
        eng = _engine()
        sim = Sim(eng)
        if not hold:
            eng._pacer.commit_point = lambda key: None
        reqs = [_req(f"r{i}", 24 + 4 * i, 10 + i) for i in range(1, 6)]
        got, done = {}, _done(6)
        eng.start()
        try:
            _submit_all(eng, [_req("r0", 48, 10)], got, done)
            if hold:
                # the rest arrive inside a hold and ride that pass's chunk
                sim.on_hold = lambda left: _submit_all(eng, reqs, got, done)
            else:
                _until(lambda: sim.passes >= 4, "no pass")
                _submit_all(eng, reqs, got, done)
            assert done.wait(120), sorted(got)
        finally:
            eng.stop()
        return got, sim

    with_hold, sim = run(True)
    without, sim0 = run(False)
    assert sim.holds() and not sim0.waits
    for rid, a in with_hold.items():
        b = without[rid]
        assert a.output_tokens == b.output_tokens, rid
        assert a.output_logprobs == b.output_logprobs, rid


def test_an_engine_with_a_prefix_cache_never_waits_for_siblings():
    """``_await_siblings`` is for models whose late sibling can alias nothing
    (no prefix cache); here a late sibling hits the radix tree, so a start
    from idle admits at once."""
    eng = _engine()
    assert eng.slots.radix is not None
    eng._await_siblings = lambda: pytest.fail("an engine with a prefix cache waited for siblings")
    eng.start()
    try:
        got, done = {}, _done(2)
        _submit_all(eng, [_req("a", 5, 1), _req("b", 5, 2)], got, done)
        assert done.wait(120)
        assert all(len(got[k].output_tokens) == 5 for k in "ab")
    finally:
        eng.stop()
