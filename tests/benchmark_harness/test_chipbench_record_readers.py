"""The reader of the program's span record (``layer_metrics/readers/
span_record.py``) on hand-built records, where every value can be worked out
on paper; the six metric files it serves; and one traced CPU rehearsal of a
rollout cell and of the train cell that reports all of them. A rehearsal's
numbers are no measurement, and nothing here asserts a time."""

import glob
import json
import os

import pytest
from chipbench_util import ROOT, bench, check_rehearsal, load_run, rehearse

from areal_tpu.api.config import PerfTracerConfig
from areal_tpu.utils import perf_tracer
from areal_tpu.utils.perf_tracer import RecordEntry, SpanRecord

SETUP = ("setup_engine_init_s", "setup_program_build_s", "setup_xla_load_s", "setup_uncovered_s")
S = 1_000_000_000
OFF = 5_000.0  # the record's clock is 5,000 s ahead of the trace's


@pytest.fixture()
def lib():
    load_run()
    from benchlib import trace_reduce, trace_scopes

    return trace_reduce, trace_scopes


def _e(name, start_s, dur_s, thread=1, args=None, ph="X"):
    return RecordEntry(name, int(start_s * S), int((start_s + dur_s) * S), thread, args, None, ph)


def _passes(t0, durs, thread=1):
    """Back-to-back passes from ``t0`` on, each with a device_wait inside."""
    out, t = [], t0
    for d in durs:
        out += [_e("areal.decode.device_wait", t + 0.01, d - 0.02, thread), _e("areal.decode.pass", t, d, thread, {"active": 4, "tokens": 128, "held_us": 0, "cpu_us": 900})]
        t += d
    return out


def _facts(lib, monkeypatch, entries, window_at, window_s=None, process_start=5_000.0, fast=0.0):
    """A traced run whose profiler session began at ``window_at`` (record's
    clock): the trace holds every span of thread 1 from there on, on its own
    clock, which ticks ``fast`` of a second a second ahead of the record's."""
    tr, ts = lib
    seen = [e for e in entries if e.thread == 1 and e.start_ns >= window_at * S and not e.name.startswith("areal.xla.")]
    on_trace = lambda ns: window_at - OFF + (ns / S - window_at) * (1 + fast)  # noqa: E731
    spans = sorted((ts.Span("python3#4", e.name, on_trace(e.start_ns), (e.end_ns - e.start_ns) / S * (1 + fast), {}) for e in seen), key=lambda s: (s.start_s, -s.dur_s))
    monkeypatch.setattr(ts, "for_run", lambda facts: ts.Scoped("hand-made", {}, spans, None))
    rec = SpanRecord(int(process_start * S), sorted(entries, key=lambda e: e.end_ns), {1: "loop", 2: "main"})
    facts = {"trace": tr.Trace([], [], window_at - OFF, window_at - OFF + 8.0), "record": rec, "values": {"setup_s": window_at - process_start}}
    if window_s is not None:
        facts["window_s"] = window_s
    return facts


def _read(name, facts):
    metric = bench().layer_metric(name)
    return bench().reader(metric["reader"]).read(metric, facts)


def test_set_up_is_cut_at_the_windows_first_instant_and_builds_are_a_union(lib, monkeypatch, capsys):
    # process start 5,000; the window's first instant 5,100: 100 s of set-up
    entries = [
        _e("areal.setup.engine_init", 5_010, 20, 2, {"engine": "decode"}),
        # three builds: two overlap across threads (5,040-5,052 and 5,050-5,060), one straddles the window's start (5,098-5,104)
        _e("areal.program.build", 5_040, 12, 1, {"program": "chunk", "key": "(32, 32)"}),
        _e("areal.program.build", 5_050, 10, 2, {"program": "prefill", "key": "(8, 256)"}),
        _e("areal.program.build", 5_098, 6, 1, {"program": "upd", "key": "(4,)"}),
        _e("areal.xla.trace", 5_040, 1, 1, {"fun": "chunk"}),
        _e("areal.xla.lower", 5_041, 2, 1, {"fun": "jit_chunk"}),
        _e("areal.xla.compile", 5_043, 8, 1, {"fun": "jit_chunk"}),
        _e("areal.xla.cache_load", 5_044, 3, 1),  # a hit's read lies inside the compile event it served
        _e("areal.xla.compile", 5_005, 4, 2, {"fun": "jit_build"}),  # the benchmark's own program: outside every build
    ] + _passes(5_100.5, [0.25] * 12)
    facts = _facts(lib, monkeypatch, entries, 5_100.0, window_s=3.0)
    got = {name: _read(name, facts) for name in SETUP}
    assert got["setup_engine_init_s"] == pytest.approx(20.0)
    assert got["setup_program_build_s"] == pytest.approx(20.0 + 2.0)  # union 5,040-5,060, and 5,098-5,100 of the third
    assert got["setup_xla_load_s"] == pytest.approx(8.0 + 4.0)  # the cache read is inside its compile event
    # inside some areal.* span: 5,005-5,009, 5,010-5,030, 5,040-5,060, 5,098-5,100
    assert got["setup_uncovered_s"] == pytest.approx(100.0 - (4 + 20 + 20 + 2))
    out = capsys.readouterr().out
    assert "set-up by the record: 100.000 s from the process's start to the window's first instant (the run's setup_s 100.000)" in out
    assert "3 x areal.program.build before the window, 22.000 s in their union: trace 1.000, lower 2.000, compile 5.000, cache_load 3.000, the rest (the first execution) 11.000" in out
    assert "longest: chunk (32, 32) 12.000 s; prefill (8, 256) 10.000 s; upd (4,) 6.000 s" in out
    assert "compile 2 (12.000 s, 4.000 outside every areal.program.build), cache_load 1 (3.000 s, 0.000 outside" in out
    assert "outside a build, by function: jit_build 4.000 s" in out
    assert "longest gaps of set-up inside no areal.* span: 38.000 s at +60.000 (after areal.program.build, before areal.program.build); 10.000 s at +30.000 (after areal.setup.engine_init" in out


def test_excess_is_what_lies_over_k_medians_in_the_whole_window(lib, monkeypatch, capsys):
    k = bench().layer_metric("pass_excess_ms")["k"]
    # 39 passes of 250 ms, one of 2,550 ms beside a collection on another thread, one just under k x 250
    durs = [0.25] * 20 + [2.55] + [0.25] * 10 + [k * 0.25 - 0.001] + [0.25] * 9
    entries = (
        [_e("areal.setup.engine_init", 5_010, 20, 2)]
        + _passes(5_099.0, [0.2503, 0.2491, 0.2507, 0.2499])  # before the window: not counted
        + _passes(5_100.0, durs)
        + [_e("areal.gc", 5_105.1, 2.2, 2, {"collected": 3}), _e("areal.program.build", 5_109.0, 0.05, 2, {"program": "upd", "key": "(2,)"})]
    )
    window = sum(durs)
    facts = _facts(lib, monkeypatch, entries, 5_100.0, window_s=window)
    facts["trace_span"] = (5_100.0, 5_104.0)  # the cell began to stop and parse its trace 1 s before the slow pass began
    assert _read("pass_excess_ms", facts) == pytest.approx(1e3 * (2.55 - k * 0.25))
    out = capsys.readouterr().out
    assert "[began 1.0 s after the benchmark started to stop and parse its trace]" in out
    assert f"41 x areal.decode.pass in the window of {window:.3f} s: median 250.00 ms, longest 2550.00 ms (10.20 x the median); 1 over {k} x the median" in out
    assert "slow areal.decode.pass at +5.000 s: 2550.00 ms {'active': 4, 'tokens': 128, 'held_us': 0, 'cpu_us': 900}; self ms by phase: decode.device_wait 2530.000, decode.pass 20.000" in out
    assert "1 overlapping entries of other threads: areal.gc 2200.000 ms at +100.000 (main) {'collected': 3}" in out
    assert "1 x areal.program.build inside the window; upd (2,) 50.0 ms at +9.000 s" in out
    # a sound window reads 0, and the train cell's runs to the end of its last step
    steps = [_e("areal.train.step", 5_100.0 + 1.2 * i, 1.19, 1, {"cpu_us": 40_000}) for i in range(24)]
    # (the profiler's clock 150 us a second fast: a step reads 0.18 ms longer there, the eighth starts 1.3 ms later)
    facts = _facts(lib, monkeypatch, [_e("areal.setup.engine_init", 5_010, 9, 1)] + steps, 5_100.0, fast=150e-6)
    assert _read("step_excess_ms", facts) == 0.0
    out = capsys.readouterr().out
    assert "clocks matched by 24 of the trace's first 24 spans on python3#4" in out and "record - trace = 5000.000000 s" in out
    assert "24 x areal.train.step in the window of 28.790 s" in out


def test_no_record_no_value_and_never_an_error(lib, monkeypatch, capsys):
    tr, ts = lib
    entries = [_e("areal.setup.engine_init", 5_010, 20, 2)] + _passes(5_100.0, [0.25] * 12)
    facts = _facts(lib, monkeypatch, entries, 5_100.0, window_s=3.0)
    names = SETUP + ("pass_excess_ms", "step_excess_ms")
    # an untraced run; an empty record; a program that keeps none (the parent); clocks that cannot be matched
    assert all(_read(n, {**facts, "trace": None}) is None for n in names)
    assert all(_read(n, {**facts, "record": SpanRecord(0, [], {})}) is None for n in names)
    monkeypatch.setattr(perf_tracer, "get_tracer", lambda: object())
    assert all(_read(n, {k: v for k, v in facts.items() if k != "record"}) is None for n in names)
    shifted = SpanRecord(facts["record"].process_start_ns, [e._replace(end_ns=e.end_ns + 7_000_000) for e in facts["record"].entries], {})
    assert all(_read(n, {**facts, "record": shifted}) is None for n in names)
    monkeypatch.setattr(ts, "for_run", lambda facts: ts.Scoped("hand-made", {}, [], None))
    assert all(_read(n, facts) is None for n in names)
    assert _read("step_excess_ms", _facts(lib, monkeypatch, entries, 5_100.0)) is None  # no train step in a rollout's record
    out = capsys.readouterr().out
    for why in ("the span record is empty", "the program keeps no span record", "clocks not matched", "no areal.* span in the trace", "no areal.train.step inside the window"):
        assert why in out, why


def test_the_new_files_resolve_and_agree_with_their_entries():
    b = bench()
    assert b.problems() == []
    cells = [w["name"] for w in b.doc["workloads"]]
    rollout = [c for c in cells if "rollout_tok_s" in {m["name"] for m in b.metrics_of(c, "end_to_end")}]
    want = {**{n: ("start-up", "s", "setup_s", cells) for n in SETUP}}
    want["pass_excess_ms"] = ("decode engine", "ms", "tpot_p95_ms", rollout)
    want["step_excess_ms"] = ("train engine", "ms", "train_tok_s", [c for c in cells if c not in rollout])
    entries = {m["name"]: m for m in b.doc["per_layer"]}
    assert list(entries)[-6:] == list(want)  # appended, in this order
    for name, (layer, unit, moves, on) in want.items():
        entry, lm = entries[name], b.layer_metric(name)
        assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (entry["layer"], entry["unit"], entry["moves"], entry["workloads"]) == (layer, unit, moves, on)
        assert (entry["better"], entry["source"]) == ("lower", "program_span")
        assert {k: lm[k] for k in ("layer", "unit", "better", "source", "moves")} == {k: entry[k] for k in ("layer", "unit", "better", "source", "moves")}
        assert lm["reader"] == "span_record" and hasattr(b.reader("span_record"), "read")
    assert {b.layer_metric(n)["mode"] for n in SETUP} == {"setup"}
    assert b.layer_metric("pass_excess_ms")["k"] == b.layer_metric("step_excess_ms")["k"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert len(f.read()) < 64 * 1024


@pytest.fixture()
def own_record(lib, monkeypatch, tmp_path):
    """The process's tracer for one rehearsal alone (a test worker's record
    holds every engine it ran before), and the run's trace file found under
    the test's directory, where ``trace_scopes`` looks under the checkout."""
    _, ts = lib
    monkeypatch.setattr(perf_tracer, "_TRACER", perf_tracer.PerfTracer(PerfTracerConfig()))
    monkeypatch.setattr(ts, "newest_xplane", lambda root=None: max(glob.glob(str(tmp_path / "trace/plugins/profile/*/*.xplane.pb")), key=os.path.getmtime, default=None))
    return perf_tracer.get_tracer()


@pytest.mark.parametrize("cell,excess", [("rollout-1.5b-grpo", "pass_excess_ms"), ("train-1.5b-packed4k", "step_excess_ms")])
def test_a_traced_rehearsal_reports_its_record_metrics(cell, excess, own_record, tmp_path):
    res = rehearse(cell, 1, tmp_path)
    if cell.startswith("rollout") and not res["correct"] and res["rehearsal"]["check"]["radix_hits"]:
        pytest.xfail("logprobs off after a radix hit inside a group (test_chipbench_rehearse_rollout.py)")
    check_rehearsal(cell, 1, res)
    got = res["metrics"]
    assert set(SETUP) | {excess} <= set(got), sorted(got)
    assert all(got[n]["value"] > 0 and got[n]["unit"] == "s" for n in SETUP)
    assert got[excess]["value"] >= 0 and got[excess]["unit"] == "ms"
    # with the defaults the record holds the run: its set-up, a build a program, every pass or step
    names = [e.name for e in own_record.record().entries]
    assert names.count("areal.setup.engine_init") == 1 and "areal.program.build" in names
    assert {"areal.xla.trace", "areal.xla.lower", "areal.xla.compile"} <= set(names)
    if cell.startswith("rollout"):
        assert names.count("areal.decode.pass") > 0 and "areal.request.first_token" in names
    else:
        assert names.count("areal.train.step") >= res["attempted"] + 2  # the window's steps and the warm ones
    json.dumps(res)
